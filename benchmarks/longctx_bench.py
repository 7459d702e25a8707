"""Long-context training on one chip: GPT-2 + pallas flash at S up to 8k.

The reference has NO long-context mechanism — sequence length is bounded by
what one worker's torch SDPA handles (SURVEY §2.8: SP/CP absent). Here the
flash kernel streams K/V through VMEM, so attention memory is O(S·D) instead
of O(S²): dense XLA attention stops compiling at S=4096 on a v5e chip while
the flash path keeps training. Multi-chip sequence parallelism on top of
this is ops/ring_attention.py (exercised on the virtual mesh + dryrun).

Writes one JSON dict per sequence length: tokens/s/chip + step time, with
the dense path's outcome recorded for contrast. Run on hardware:

    python benchmarks/longctx_bench.py
"""

from __future__ import annotations

import functools
import json
import sys
import time


def _bench_step(S: int, B: int, attn, steps: int = 5) -> dict:
    import jax

    from hypha_tpu.executor.train import TrainState, build_optimizer, make_train_step
    from hypha_tpu.messages import Adam
    from hypha_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config(
        vocab_size=50257, n_positions=S, n_embd=768, n_layer=12, n_head=12
    )
    model = GPT2(cfg, attn_impl=attn)
    ids = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), ids)
    state = TrainState.create(params, build_optimizer(Adam(lr=1e-4)))
    step = make_train_step(model.apply)
    batch = {"input_ids": ids}
    t0 = time.perf_counter()
    state, m = step(state, batch)
    float(m["loss"])  # value fetch = hard sync
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    loss = float(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    n_params = sum(x.size for x in jax.tree.leaves(params))
    flops_tok = 6 * n_params + 12 * cfg.n_layer * cfg.n_embd * S
    return {
        "batch": B,
        "seq": S,
        "tokens_per_sec": round(B * S / dt, 1),
        "step_ms": round(dt * 1e3, 1),
        "mfu_v5e": round(flops_tok * B * S / dt / 197e12, 4),
        "compile_s": round(compile_s, 1),
        "loss": round(loss, 3),
    }


def main() -> None:
    import jax

    from hypha_tpu.ops.flash_attention import flash_attention

    platform = jax.devices()[0].platform
    flash = functools.partial(flash_attention, interpret=False)
    results: dict = {"platform": platform, "device_kind": getattr(jax.devices()[0], "device_kind", "")}
    for S, B in ((2048, 8), (4096, 4), (8192, 2)):
        try:
            results[f"flash_S{S}"] = _bench_step(S, B, flash)
        except Exception as e:
            results[f"flash_S{S}"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        try:
            results[f"dense_S{S}"] = _bench_step(S, B, None)
        except Exception as e:
            # Expected at long S: the dense S² path exhausts the compiler.
            results[f"dense_S{S}"] = {"error": f"{type(e).__name__}: {e}"[:160]}
    print(json.dumps(results))


if __name__ == "__main__":
    sys.exit(main())
