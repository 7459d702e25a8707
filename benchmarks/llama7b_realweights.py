"""Llama-2-7B on REAL converted weights: conversion parity + serving.

Closes VERDICT r3 missing #1 ("the 7B/real-weights end of the north star
has never actually run"). Consumes the sharded HF repo written by
``make_llama7b_ckpt.py`` (3 safetensors shards + model.safetensors.index.json,
written by torch ``save_pretrained`` — the exact layout the reference's
executor loads via AutoModelForCausalLM, executors/accelerate/.../model.py:48-123)
and its recorded torch oracle.

Two phases:

``convert`` (CPU, f32): stream-convert the full 6.74B-param repo through
  ``models.convert.convert_checkpoint`` and prove CONVERSION FIDELITY —
  last-position logits match torch f32 and the 8-token greedy continuations
  are IDENTICAL, for every prompt. Writes ``CONVERT_r04.json``.

``serve`` (TPU, bf16): stream the same repo to the chip in bf16 (one host
  tensor in flight — the f32 tree would be 27 GB, over HBM), compare logits
  against the recorded torch bf16-weights oracle, and measure real-weights
  decode throughput. Writes ``SERVING_r04.json``.

Run:  python benchmarks/llama7b_realweights.py convert [ckpt_dir]
      python benchmarks/llama7b_realweights.py serve [ckpt_dir]
"""

from __future__ import annotations

import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent


def _model(dtype: str):
    from hypha_tpu.models import Llama
    from hypha_tpu.models.llama import LlamaConfig

    import dataclasses

    cfg = dataclasses.replace(
        LlamaConfig.llama2_7b(), max_seq_len=1024, dtype=dtype
    )
    return Llama(cfg), cfg


def _template(model, cfg):
    import jax

    probe = np.zeros((1, 8), np.int32)
    return jax.eval_shape(lambda: model.init(jax.random.key(0), probe))


def _peak_rss_gb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2)


def convert_phase(ckpt: Path) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from hypha_tpu.executor.generate import generate
    from hypha_tpu.models.convert import convert_checkpoint

    oracle = np.load(ckpt / "oracle.npz")
    prompts = oracle["prompts"]

    model, cfg = _model("float32")
    template = _template(model, cfg)
    t0 = time.time()
    params = convert_checkpoint(
        "llama", ckpt, template, put=lambda _n, a: jax.device_put(a)
    )
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    convert_s = time.time() - t0
    print(f"converted {n_params/1e9:.2f}B params in {convert_s:.0f}s, "
          f"peak rss {_peak_rss_gb()} GB", flush=True)

    t0 = time.time()
    fwd = jax.jit(model.apply)
    results = []
    all_greedy_ok = True
    for i, p in enumerate(prompts):
        logits = np.asarray(fwd(params, p[None, :].astype(np.int32)))[0, -1]
        want = oracle["logits_f32"][i]
        max_abs = float(np.max(np.abs(logits - want)))
        scale = float(np.max(np.abs(want)))
        top1 = int(np.argmax(logits)) == int(np.argmax(want))
        greedy = np.asarray(
            generate(model, params, p[None, :].astype(np.int32),
                     oracle["greedy_f32"].shape[1])
        )[0]
        greedy_ok = bool(np.array_equal(greedy, oracle["greedy_f32"][i]))
        all_greedy_ok &= greedy_ok
        results.append({
            "prompt": i,
            "max_abs_logit_diff": round(max_abs, 5),
            "logit_scale": round(scale, 3),
            "top1_match": top1,
            "greedy_8tok_identical": greedy_ok,
        })
        print(results[-1], flush=True)
        assert top1, f"prompt {i}: top-1 token diverged from torch"
        assert max_abs < 5e-2 * max(scale, 1.0), (
            f"prompt {i}: logit drift {max_abs} vs scale {scale}"
        )
    assert all_greedy_ok, "greedy continuations diverged from torch"
    out = {
        "checkpoint": str(ckpt),
        "writer": json.loads((ckpt / "WRITER.json").read_text()),
        "params": n_params,
        "convert_s": round(convert_s, 1),
        "peak_rss_gb": _peak_rss_gb(),
        "parity_s": round(time.time() - t0, 1),
        "dtype": "float32 weights + compute, vs torch f32 oracle",
        "prompts": results,
        "conclusion": "sharded 7B HF repo converts with exact greedy parity",
    }
    (REPO / "CONVERT_r04.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)


def serve_phase(ckpt: Path) -> None:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.generate import generate
    from hypha_tpu.models.convert import convert_checkpoint

    oracle = np.load(ckpt / "oracle.npz")
    prompts = oracle["prompts"]
    n_greedy = oracle["greedy_bf16"].shape[1]

    model, cfg = _model("bfloat16")
    template = _template(model, cfg)
    t0 = time.time()
    params = convert_checkpoint(
        "llama", ckpt, template,
        dtype=jnp.bfloat16,
        put=lambda _n, a: jax.device_put(a),
    )
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[-1])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    convert_s = time.time() - t0
    print(f"streamed {n_params/1e9:.2f}B bf16 params to device in "
          f"{convert_s:.0f}s, peak host rss {_peak_rss_gb()} GB", flush=True)

    # -- parity vs the recorded torch bf16-weights oracle
    fwd = jax.jit(model.apply)
    parity = []
    for i, p in enumerate(prompts):
        logits = np.asarray(
            fwd(params, p[None, :].astype(np.int32)).astype(jnp.float32)
        )[0, -1]
        wantb = oracle["logits_bf16"][i]
        wantf = oracle["logits_f32"][i]
        greedy = np.asarray(
            generate(model, params, p[None, :].astype(np.int32), n_greedy)
        )[0]
        parity.append({
            "prompt": i,
            "max_abs_vs_torch_bf16": round(float(np.max(np.abs(logits - wantb))), 4),
            "max_abs_vs_torch_f32": round(float(np.max(np.abs(logits - wantf))), 4),
            "logit_scale": round(float(np.max(np.abs(wantf))), 3),
            "top1_match_vs_bf16": int(np.argmax(logits)) == int(np.argmax(wantb)),
            "greedy_match_vs_bf16": int(
                np.sum(greedy == oracle["greedy_bf16"][i])
            ),
            "greedy_match_vs_f32": int(
                np.sum(greedy == oracle["greedy_f32"][i])
            ),
            "greedy_total": int(n_greedy),
        })
        print(parity[-1], flush=True)

    # -- real-weights decode throughput (chained on data dependency,
    # synced by value fetch)
    B, P, N = 1, 128, 128
    ids = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    t0 = time.time()
    o = generate(model, params, ids, N)
    int(jax.device_get(o[0, 0]))
    compile_s = time.time() - t0
    x = ids
    reps = 3
    t0 = time.time()
    for _ in range(reps):
        x = generate(model, params, x, N)
    int(jax.device_get(x[0, -1]))
    dt = (time.time() - t0) / reps
    dev = jax.devices()[0]
    out = {
        "model": "llama2-7b REAL converted weights (sharded HF repo, bf16)",
        "checkpoint": str(ckpt),
        "writer": json.loads((ckpt / "WRITER.json").read_text()),
        "params": n_params,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "stream_to_device_s": round(convert_s, 1),
        "peak_host_rss_gb": _peak_rss_gb(),
        "parity": parity,
        "batch": B,
        "prompt_len": P,
        "new_tokens": N,
        "decode_tokens_per_sec": round(B * N / dt, 1),
        "ms_per_token": round(dt * 1e3 / N, 1),
        "effective_weight_read_gbps": round(n_params * 2 / (dt / N) / 1e9, 0),
        "compile_s": round(compile_s, 0),
    }
    (REPO / "SERVING_r04.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)


def main() -> None:
    phase = sys.argv[1] if len(sys.argv) > 1 else "convert"
    ckpt = Path(sys.argv[2]) if len(sys.argv) > 2 else Path("/tmp/llama2_7b")
    if phase == "convert":
        convert_phase(ckpt)
    elif phase == "serve":
        serve_phase(ckpt)
    else:
        raise SystemExit(f"unknown phase {phase!r}")


if __name__ == "__main__":
    main()
