"""Operations and bytes of the kernels this benchmark reads a roofline share
for, from shapes and counters alone (``on-chip-measurement`` guide, 4).

A share is the least time the chip could take, the larger of operations over
the peak FLOP/s (``flops.PEAK_FLOPS``) and bytes over the peak bytes/s
(``PEAK_BYTES_PER_S``, below), over the kernel's time in the device trace. The
counts are what the algorithm needs, not what an implementation does: a
causal band is counted by the pairs it holds and not by S squared, a grouped
product by the pairs actually routed here and not by tokens x k, and a
recomputation in a backward pass that the algorithm itself prescribes (flash
attention's scores) is counted once. A share over 100 % means a count here is
too high or the time leaves out part of the work.
"""

from __future__ import annotations

# HBM bandwidth per chip (Google Cloud's TPU documentation; v5e: 819 GB/s). A
# kind that is not here is an error, never an assumed peak.
PEAK_BYTES_PER_S = {"v5e": 819e9, "v5 lite": 819e9}


def peak_bytes_per_s(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, val in PEAK_BYTES_PER_S.items():
        if key in kind:
            return val
    raise KeyError(f"no peak bytes/s known for device_kind {device_kind!r}")


def band_pairs(sequence: int, window: int | None) -> int:
    """(query, key) pairs of one causal sequence: query i sees min(i + 1,
    window) keys."""
    if window is None or window >= sequence:
        return sequence * (sequence + 1) // 2
    return window * (window + 1) // 2 + (sequence - window) * window


def flash_attention(batch: int, sequence: int, heads: int, kv_heads: int, head_size: int,
                    window: int | None, element_bytes: int = 2) -> dict:
    """Forward and backward of one layer's attention core. Forward: QK^T and
    PV, 4 x head_size a pair and head. Backward: the scores again, dP, dV, dQ
    and dK, 10 x head_size. Bytes: q, k, v read and o written; then q, k, v,
    o, dO read and dQ, dK, dV written (the row statistics are a 128th)."""
    pairs = batch * heads * band_pairs(sequence, window)
    q = batch * sequence * heads * head_size * element_bytes
    kv = batch * sequence * kv_heads * head_size * element_bytes
    return {
        "flops": 14.0 * head_size * pairs,
        "bytes": float((2 * q + 2 * kv) + (4 * q + 4 * kv)),
    }


def grouped_swiglu(pairs: float, width: int, expert_width: int, held: int, layers: int,
                   element_bytes: int = 2) -> dict:
    """Forward and backward of the routed experts over ``pairs`` (token,
    expert) pairs in all, ``layers`` layers of ``held`` experts. Three products
    forward (2 x width x expert_width each a pair), their data and weight
    gradients backward: 18 x width x expert_width a pair. Bytes: each layer's
    three weight tables read forward and backward and their gradients written
    once; a pair's input read, output written, and the same for gradients. It
    is the count of any gated expert of three products (gate, up, down),
    whatever the activation on the gate: SiLU here, ReLU in another family;
    the activation itself is elementwise and not counted."""
    table = 3 * held * width * expert_width * element_bytes
    rows = pairs * width * element_bytes
    return {
        "flops": 18.0 * width * expert_width * pairs,
        "bytes": float(layers * 3 * table + 4 * rows),
    }


def roofline_share(counts: dict, seconds: float, peak_flops: float, peak_bytes: float) -> float:
    """Percent of the roofline: the larger bound's time over the measured."""
    return 100.0 * max(counts["flops"] / peak_flops, counts["bytes"] / peak_bytes) / seconds
