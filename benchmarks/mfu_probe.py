"""MFU probe: where does the non-MXU time in the headline step go?

The 109k tok/s / 0.477 MFU GPT-2 step (bench.py) leaves ~52% of the chip
idle. No device trace was available when this was written (r4), so
this measures by ABLATION — separately-jitted variants of the step, each
timed with chained data dependencies and value-fetch syncs (the only
honest timing on this backend):

  full          fwd + bwd + AdamW          (the headline)
  no_opt        fwd + bwd only             -> optimizer cost
  fwd           loss only                  -> backward/forward split
  dense         full, XLA dense attention  -> flash kernel win
  ce_plain      full, naive log-softmax CE -> streaming-CE win
  blocks        full, flash tile variants  -> remaining tile headroom
  batch         full at other batch sizes  -> occupancy headroom

Writes MFUPROBE_r04.json; run on the bench chip:
  python benchmarks/mfu_probe.py
"""

from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent


def _time_step(step, state, batch, reps=6):
    """Chained reps with a per-rep value fetch; median seconds/step."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        float(metrics["loss"])  # hard sync
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), state


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from hypha_tpu.executor.train import (
        TrainState,
        build_optimizer,
        make_loss_fn,
    )
    from hypha_tpu.messages import Adam, Loss
    from hypha_tpu.models import GPT2, GPT2Config
    from hypha_tpu.ops.flash_attention import flash_attention

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg = GPT2Config.small()
    B, S = 16, 1024
    flash = functools.partial(flash_attention, interpret=(False if on_tpu else None))

    def build(attn):
        model = GPT2(cfg, attn)
        return model

    def make_state(model, ids):
        params = model.init(jax.random.key(0), ids)
        return TrainState.create(params, build_optimizer(Adam(lr=1e-4)))

    ids = np.asarray(
        jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    )
    batch = {"input_ids": ids}
    results: dict = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "config": f"gpt2-small B={B} S={S}",
    }
    tok = B * S

    model = build(flash)
    loss_fn = make_loss_fn(model.apply)

    # --- full step (headline) + no-opt + fwd-only ablations
    def full_step(state, batch):
        (total, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, state.step
        )
        new = state.apply_gradients(grads)
        return new, {"loss": loss}

    def noopt_step(state, batch):
        (total, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, state.step
        )
        # consume grads w/o optimizer: fold their norm into metrics
        return state.replace(step=state.step + 1), {
            "loss": loss + 0.0 * optax.global_norm(grads)
        }

    def fwd_step(state, batch):
        total, (loss, aux) = loss_fn(state.params, batch, state.step)
        return state.replace(step=state.step + 1), {"loss": loss}

    state = make_state(model, ids)
    for name, fn in (
        ("full", full_step), ("no_opt", noopt_step), ("fwd", fwd_step),
    ):
        jitted = jax.jit(fn, donate_argnums=(0,))
        t0 = time.perf_counter()
        state2, m = jitted(state, batch)
        float(m["loss"])
        compile_s = time.perf_counter() - t0
        dt, state = _time_step(jitted, state2, batch)
        results[name] = {
            "ms": round(dt * 1e3, 2),
            "tok_s": round(tok / dt, 0),
            "compile_s": round(compile_s, 1),
        }
        print(name, results[name], flush=True)

    # --- dense attention and naive CE comparisons (full step)
    dense_model = build(None)
    dense_loss = make_loss_fn(dense_model.apply)

    def dense_step(state, batch):
        (_t, (loss, _a)), grads = jax.value_and_grad(dense_loss, has_aux=True)(
            state.params, batch, state.step
        )
        return state.apply_gradients(grads), {"loss": loss}

    def plain_ce_loss(params, batch, step_no):
        logits = model.apply(params, batch["input_ids"])
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = batch["input_ids"][:, 1:]
        nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll), (jnp.mean(nll), jnp.float32(0))

    def plain_ce_step(state, batch):
        (_t, (loss, _a)), grads = jax.value_and_grad(
            plain_ce_loss, has_aux=True
        )(state.params, batch, state.step)
        return state.apply_gradients(grads), {"loss": loss}

    for name, fn in (("dense_attn", dense_step), ("plain_ce", plain_ce_step)):
        try:
            jitted = jax.jit(fn, donate_argnums=(0,))
            st = make_state(model, ids)
            st, m = jitted(st, batch)
            float(m["loss"])
            dt, _ = _time_step(jitted, st, batch)
            results[name] = {"ms": round(dt * 1e3, 2), "tok_s": round(tok / dt, 0)}
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {e}"[:140]}
        print(name, results[name], flush=True)

    # --- flash tile variants on the full step
    for bq, bk, bqb, bkb in (
        (512, 256, 512, 512),   # r3 defaults (baseline sanity)
        (512, 512, 512, 512),
        (1024, 256, 512, 512),
        (512, 256, 1024, 512),
        (512, 256, 512, 256),
        (512, 256, 256, 512),
        (512, 512, 1024, 512),  # combined best halves -> the r4 defaults
    ):
        key = f"tiles_f{bq}x{bk}_b{bqb}x{bkb}"
        try:
            attn = functools.partial(
                flash_attention, block_q=bq, block_k=bk,
                block_q_bwd=bqb, block_k_bwd=bkb,
                interpret=(False if on_tpu else None),
            )
            m2 = build(attn)
            lf2 = make_loss_fn(m2.apply)

            def tile_step(state, batch, lf2=lf2):
                (_t, (loss, _a)), grads = jax.value_and_grad(lf2, has_aux=True)(
                    state.params, batch, state.step
                )
                return state.apply_gradients(grads), {"loss": loss}

            jitted = jax.jit(tile_step, donate_argnums=(0,))
            st = make_state(m2, ids)
            st, m = jitted(st, batch)
            float(m["loss"])
            dt, _ = _time_step(jitted, st, batch)
            results[key] = {"ms": round(dt * 1e3, 2), "tok_s": round(tok / dt, 0)}
        except Exception as e:
            results[key] = {"error": f"{type(e).__name__}: {e}"[:140]}
        print(key, results[key], flush=True)

    # --- occupancy: other batch sizes (32 known to break remote-compile)
    for b2 in (8, 24):
        try:
            ids2 = np.asarray(
                jax.random.randint(jax.random.key(2), (b2, S), 0, cfg.vocab_size)
            )
            st = make_state(model, ids2)
            jitted = jax.jit(full_step, donate_argnums=(0,))
            st, m = jitted(st, {"input_ids": ids2})
            float(m["loss"])
            dt, _ = _time_step(jitted, st, {"input_ids": ids2})
            results[f"batch{b2}"] = {
                "ms": round(dt * 1e3, 2),
                "tok_s": round(b2 * S / dt, 0),
            }
        except Exception as e:
            results[f"batch{b2}"] = {"error": f"{type(e).__name__}: {e}"[:140]}
        print(f"batch{b2}", results[f"batch{b2}"], flush=True)

    # MERGE into the artifact: it also carries sections this script does
    # not produce (headline_protocol_tiles, chunked_ce — recorded by their
    # own runs); a rerun must refresh the ablation rows without deleting
    # the evidence behind the kernel defaults.
    out_path = REPO / "MFUPROBE_r04.json"
    merged = {}
    if out_path.exists():
        merged = json.loads(out_path.read_text())
    merged.update(results)
    out_path.write_text(json.dumps(merged, indent=1))
    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    sys.exit(main())
