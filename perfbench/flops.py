"""Peaks by ``device_kind`` and the operations one trained token needs.

The peak table is ``bench.py``'s (bf16 matmul peak per chip, from Google
Cloud's TPU documentation; v5e: 197 TFLOP/s). A kind that is not in it is an
error, never an assumed peak. The operation count is the usual model-FLOPs
one: 6 per matmul parameter (forward 2, backward 4) plus attention's
12 * layers * width * sequence, with no discount for the causal mask and
nothing for recomputation. The sizes come from the configuration file's
``flops`` group, so a new configuration brings its own numbers, not code.
Where not every layer attends to the whole sequence, the group states
``attention_keys``: one entry a layer that is run, the keys a query of that
layer is counted with (``null`` for a full layer, a window layer's window;
each is cut to the sequence). Attention is then 12 * width * their sum, and
``layers`` counts the matmuls alone.
"""

from __future__ import annotations

PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    raise KeyError(f"no peak FLOP/s known for device_kind {device_kind!r}")


def matmul_params(f: dict) -> int:
    """Parameters that sit in a matrix multiplication, per token: the
    attention projections, the MLP and the output head. Embedding lookups,
    position tables, norms and biases are left out."""
    attn_width = f["heads"] * f["head_size"]
    kv_width = f["kv_heads"] * f["head_size"]
    attn = 2 * f["width"] * attn_width + 2 * f["width"] * kv_width
    mlp = f["mlp_matrices"] * f["width"] * f["mlp_width"]
    return f["layers"] * (attn + mlp) + f["vocabulary"] * f["width"]


def flops_per_token(f: dict, sequence: int) -> float:
    attn_width = f["heads"] * f["head_size"]
    keys = f.get("attention_keys", [None] * f["layers"])  # absent: every layer full
    seen = sum(sequence if k is None else min(sequence, k) for k in keys)
    return 6.0 * matmul_params(f) + 12.0 * attn_width * seen
