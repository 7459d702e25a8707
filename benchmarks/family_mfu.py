"""Same-protocol per-family MFU table (VERDICT r5 task 6).

Round 4's per-family numbers were not apples-to-apples: GPT-2 had the
tuned S=1024 headline, Llama-GQA only an S=4096 long-context row, Mixtral
only S=2048 — so the "0.50 single-chip ceiling" claim was demonstrated for
one family. This runs every family through the SAME two protocols
(B·S matched: 16x1024 and 4x4096, bf16, flash attention, full
fwd+bwd+AdamW step, chained-value-fetch timing) and tile-sweeps the
GQA head-dim-128 family, whose flash tiles had never been tuned
separately from GPT-2's D=64.

MFU accounting matches bench.py: 6N_active FLOPs/token for matmuls +
12·L·(H·D)·S attention scores; MoE counts only the K-of-E routed expert
FLOPs as active.

Run on the bench chip:
  python benchmarks/family_mfu.py
Writes FAMILY_MFU_r05.json (merge-don't-clobber, mfu_probe convention).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
PEAK = {"v5 lite": 197e12, "v5e": 197e12, "v4": 275e12}


def _peak(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for k, v in PEAK.items():
        if k in kind:
            return v
    return 197e12


def _time_step(step, state, batch, steps=10, warmup=2):
    """bench.py's timing discipline: chained steps (donated state is the
    data dependency), ONE value fetch at the end — a per-step host sync
    would add a host round-trip to every step."""
    for _ in range(warmup):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # sync the warmup out of the window
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # hard sync for the whole chain
    return (time.perf_counter() - t0) / steps, state


def build_family(name: str, flash_kwargs=None, seq_len: int = 1024):
    """(model, n_params_active, attn_dims (L, HD)) for one family."""
    import functools

    import jax

    from hypha_tpu.ops.flash_attention import flash_attention

    attn = (
        functools.partial(flash_attention, **flash_kwargs)
        if flash_kwargs else flash_attention
    )
    if name == "gpt2":
        import dataclasses

        from hypha_tpu.models import GPT2, GPT2Config

        # n_positions follows the protocol's S (learned positions cap the
        # context; the extra wpe rows don't change per-token FLOPs).
        cfg = dataclasses.replace(
            GPT2Config.small(), n_positions=max(1024, seq_len)
        )
        model = GPT2(cfg, attn_impl=attn)
        dims = (cfg.n_layer, cfg.n_embd)
    elif name == "llama-gqa":
        # Head-dim 128 (the Llama-2/Mistral layout), GQA 4:1 — the family
        # whose flash tiles were never swept separately from D=64.
        from hypha_tpu.models import Llama, LlamaConfig

        cfg = LlamaConfig(
            vocab_size=32_000, hidden_size=1024, intermediate_size=2816,
            num_layers=12, num_heads=8, num_kv_heads=2, max_seq_len=4096,
        )
        model = Llama(cfg, attn_impl=attn)
        dims = (cfg.num_layers, cfg.num_heads * cfg.head_dim)
    elif name == "mixtral":
        from hypha_tpu.models import Mixtral, MixtralConfig

        cfg = MixtralConfig(
            vocab_size=32_000, hidden_size=768, intermediate_size=2048,
            num_layers=12, num_heads=12, num_kv_heads=4, num_experts=8,
            experts_per_token=2, max_seq_len=4096,
        )
        model = Mixtral(cfg, attn_impl=attn)
        dims = (cfg.num_layers, cfg.num_heads * cfg.head_dim)
    else:
        raise ValueError(name)
    return model, cfg, dims


def active_params(name: str, cfg, params) -> int:
    """Matmul-active params for the 6N accounting.

    The input-embedding GATHER does ~zero FLOPs, so an UNTIED embed_tokens
    table must not count toward 6N (the lm_head projection does, and a
    tied table like GPT-2's wte is stored once and used by the head, so it
    stays). MoE counts only the K-of-E routed expert share.
    """
    import jax

    total = sum(int(l.size) for l in jax.tree.leaves(params))
    if name == "gpt2":
        return total  # tied wte = head weights; wpe is an add (negligible)
    total -= cfg.vocab_size * cfg.hidden_size  # untied embed_tokens gather
    if name != "mixtral":
        return total
    # Only K of E experts run per token: discount the unrouted share of the
    # stacked expert tensors.
    expert = (
        cfg.num_layers * cfg.num_experts * 3
        * cfg.hidden_size * cfg.intermediate_size
    )
    frac = 1 - cfg.experts_per_token / cfg.num_experts
    return int(total - frac * expert)


def run_row(name: str, B: int, S: int, flash_kwargs=None) -> dict:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import TrainState, build_optimizer, make_train_step
    from hypha_tpu.messages import Adam

    model, cfg, (L, HD) = build_family(name, flash_kwargs, seq_len=S)
    ids = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    params = model.init(jax.random.key(0), ids)
    state = TrainState.create(params, build_optimizer(Adam(lr=1e-4)))
    n_active = active_params(name, cfg, params["params"] if "params" in params else params)
    step = make_train_step(model.apply, has_aux=(name == "mixtral"))
    sec, state = _time_step(step, state, {"input_ids": ids})
    tok_s = B * S / sec
    flops_tok = 6 * n_active + 12 * L * HD * S
    dev = jax.devices()[0]
    mfu = flops_tok * tok_s / _peak(dev)
    return {
        "family": name,
        "batch": B,
        "seq": S,
        "active_params_m": round(n_active / 1e6, 1),
        "tokens_per_sec": round(tok_s, 0),
        "step_ms": round(sec * 1e3, 1),
        "mfu": round(mfu, 4),
        "tiles": flash_kwargs or "defaults",
        "bringup_s": round(time.perf_counter() - t0, 1),
    }


def main() -> None:
    import jax

    dev = jax.devices()[0]
    out_path = REPO / "FAMILY_MFU_r05.json"
    results = (
        json.loads(out_path.read_text()) if out_path.exists() else {}
    )
    results["platform"] = dev.platform
    results["device_kind"] = getattr(dev, "device_kind", "")
    results.setdefault("rows", {})

    protocols = [(16, 1024), (4, 4096)]
    for name in ("gpt2", "llama-gqa", "mixtral"):
        for B, S in protocols:
            key = f"{name}_B{B}_S{S}"
            if key in results["rows"]:
                continue
            try:
                results["rows"][key] = run_row(name, B, S)
            except Exception as e:
                results["rows"][key] = {"error": f"{type(e).__name__}: {e}"[:300]}
            print(json.dumps(results["rows"][key]), flush=True)
            out_path.write_text(json.dumps(results, indent=1))

    # Tile sweep for the D=128 family at the long protocol — GQA head-dim
    # 128 tiles were inherited from the D=64 sweep, unverified.
    results.setdefault("gqa_tile_sweep", {})
    sweep = [
        {"block_q": 512, "block_k": 512},  # r4 fwd default
        {"block_q": 256, "block_k": 512},
        {"block_q": 512, "block_k": 256},
        {"block_q": 256, "block_k": 256},
        # bwd tiles (fwd pinned at default): D=128 doubles the per-tile
        # VMEM footprint vs the D=64 sweep that chose (1024, 512)
        {"block_q_bwd": 512, "block_k_bwd": 512},
        {"block_q_bwd": 512, "block_k_bwd": 256},
        {"block_q_bwd": 1024, "block_k_bwd": 256},
    ]
    for kw in sweep:
        key = "_".join(f"{k.replace('block_', '')}{v}" for k, v in kw.items())
        if key in results["gqa_tile_sweep"]:
            continue
        try:
            results["gqa_tile_sweep"][key] = run_row("llama-gqa", 4, 4096, kw)
        except Exception as e:
            results["gqa_tile_sweep"][key] = {"error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps({key: results["gqa_tile_sweep"][key]}), flush=True)
        out_path.write_text(json.dumps(results, indent=1))

    print(f"[family_mfu] wrote {out_path}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
