"""Benchmark: tokens/sec/chip + MFU of the jitted DiLoCo inner train step on
the flagship model (GPT-2-small 124M, bf16), the metric BASELINE.md asks this
repo to establish. Prints ONE JSON line on stdout; diagnostics go to stderr.

One process, on JAX's default backend, which must be a TPU: there is no CPU
fallback, the pallas flash kernel is compiled (``interpret=False``), and a
``device_kind`` missing from ``_PEAK_FLOPS`` is an error, never an assumed
peak.

The reference publishes no model-level numbers (BASELINE.json published={});
``vs_baseline`` is measured against the reference-stack estimate in
``BENCH_BASELINE.json``.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# bf16 peak FLOP/s per chip by device-kind substring (public TPU specs).
_PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    raise RuntimeError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r}; "
        "add it to _PEAK_FLOPS with its source"
    )


def _time_steps(step, state, batch, steps: int, warmup: int):
    import jax

    t_c0 = time.perf_counter()
    for _ in range(warmup):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t_c0
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    return state, metrics, compile_s, dt, float(metrics["loss"])


def _run_config(cfg, B: int, S: int, steps: int, warmup: int, attn, label: str):
    """Build model+optimizer for ``cfg`` and time the fused train step."""
    import jax

    from hypha_tpu.executor.train import TrainState, build_optimizer, make_train_step
    from hypha_tpu.messages import Adam
    from hypha_tpu.models import GPT2

    model = GPT2(cfg, attn_impl=attn)
    ids = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    params = model.init(jax.random.key(0), ids)
    jax.block_until_ready(params)
    _log(f"{label}: init {time.perf_counter() - t0:.1f}s")
    state = TrainState.create(params, build_optimizer(Adam(lr=1e-4)))
    step = make_train_step(model.apply)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    state, metrics, compile_s, dt, loss = _time_steps(
        step, state, {"input_ids": ids}, steps, warmup
    )
    tok_s = B * S * steps / dt
    _log(
        f"{label}: params {n_params / 1e6:.1f}M warmup+compile {compile_s:.1f}s "
        f"{steps} steps in {dt:.2f}s -> {tok_s:,.0f} tok/s loss {loss:.3f}"
    )
    return n_params, tok_s, compile_s, loss


def _bench_line() -> dict:
    """Run the benchmark on the default backend, which must be a TPU."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import GPT2Config
    from hypha_tpu.ops.flash_attention import flash_attention

    t_init = time.perf_counter()
    devices = jax.devices()
    init_s = time.perf_counter() - t_init
    platform = devices[0].platform
    kind = devices[0].device_kind
    _log(f"backend up in {init_s:.1f}s: platform={platform} kind={kind!r} n={len(devices)}")
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip; the default backend is {platform!r}"
        )
    peak = _peak_flops(devices[0])

    attn = functools.partial(flash_attention, interpret=False)
    attn_path = "pallas-flash(interpret=False)"

    cfg = GPT2Config.small()  # 124M params, bf16 activations
    B, S = 16, 1024
    steps, warmup = 20, 3
    if jnp.dtype(cfg.dtype) != jnp.bfloat16:
        raise RuntimeError("flagship bench must run bf16")
    n_params, tokens_per_sec, compile_s, loss = _run_config(
        cfg, B, S, steps, warmup, attn, "flagship"
    )
    n_chips = 1  # single-chip inner-loop benchmark
    value = tokens_per_sec / n_chips

    # Training FLOPs/token (PaLM appendix accounting): 6N for the matmuls
    # (fwd 2N + bwd 4N) + 12·L·E·S for attention score/value products.
    flops_per_token = 6 * n_params + 12 * cfg.n_layer * cfg.n_embd * S
    achieved_flops = flops_per_token * tokens_per_sec
    mfu = achieved_flops / (peak * n_chips)

    with open(os.path.join(_REPO, "BENCH_BASELINE.json")) as f:
        bl = json.load(f)
    baseline = bl["tokens_per_sec_per_chip"]
    # Hardware-normalized efficiency: our measured MFU over the baseline
    # stack's assumed MFU — the honest cross-hardware comparison when the
    # bench chip (v5e, 197 bf16 TFLOP/s) and the reference's assumed A100
    # (312) have different peaks.
    baseline_mfu = bl["assumed_mfu"]

    return {
        "metric": "gpt2s_train_tokens_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(value / baseline, 3),
        "platform": platform,
        "device_kind": kind,
        "device_count": len(devices),
        "attention": attn_path,
        "batch": B,
        "seq": S,
        "steps": steps,
        "params": n_params,
        "mfu": round(mfu, 4),
        "mfu_vs_baseline_mfu": round(mfu / baseline_mfu, 3),
        "tflops_per_chip": round(achieved_flops / 1e12, 2),
        "loss": loss,
        "backend_init_s": round(init_s, 1),
        "compile_s": round(compile_s, 1),
    }


def main() -> None:
    from hypha_tpu.hw import enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    print(json.dumps(_bench_line()))


def _chaos_main(spec: str, trace_dir: str | None = None) -> int:
    """``bench.py --chaos <spec> [--trace <dir>]`` (kill-worker:<round>,
    kill-ps:<round>, partition-ps:<round>:<s>, kill-scheduler:<round>,
    partition-scheduler:<round>:<s>, slow-worker:<x>,
    bw-cap:<peer>:<mbps>, jitter:<peer>:<s>, ...): run the orchestrated
    fault-injection scenario (benchmarks/ft_chaos.py — 4 workers, elastic
    membership, durable PS for the ps scenarios; scheduler scenarios run
    the two-pass bit-equality harness with a restarted scheduler
    re-adopting the live executions) on the CPU backend and persist the
    result as FTBENCH_<scenario>.json next to this script. Specs compose
    with commas (``kill-worker:2,bw-cap:w1:10``) so one run can mix an
    event with steady degrade conditions.

    ``--trace <dir>`` turns on end-to-end round tracing + flight-recorder
    spill into ``dir`` and runs the timeline merger over it afterward
    (``python -m hypha_tpu.telemetry.timeline <dir>`` re-renders it any
    time). A telemetry metrics snapshot is dumped next to the artifact
    either way, so every chaos bench gets metrics for free."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # control-plane bench: no accelerator
    sys.path.insert(0, os.path.join(_REPO, "benchmarks"))
    from ft_chaos import run_chaos_scenario

    line = run_chaos_scenario(spec, trace_dir=trace_dir)
    safe = "".join(c if (c.isalnum() or c in "-_") else "-" for c in spec)
    out_path = os.path.join(_REPO, f"FTBENCH_{safe}.json")
    with open(out_path, "w") as f:
        json.dump(line, f, indent=2)
        f.write("\n")
    _log(f"wrote {out_path}")
    from hypha_tpu.telemetry import metrics_snapshot

    snap_path = os.path.join(_REPO, f"FTBENCH_{safe}.telemetry.json")
    with open(snap_path, "w") as f:
        json.dump(metrics_snapshot(), f, indent=2)
        f.write("\n")
    _log(f"wrote {snap_path}")
    if trace_dir:
        from hypha_tpu.telemetry import timeline as tl

        merged = tl.build_timeline(trace_dir)
        with open(os.path.join(trace_dir, "timeline.json"), "w") as f:
            json.dump(merged, f, indent=2)
            f.write("\n")
        print(tl.render_text(merged), file=sys.stderr)
        _log(f"wrote {os.path.join(trace_dir, 'timeline.json')}")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    try:
        if len(sys.argv) >= 2 and sys.argv[1] == "--chaos":
            args = sys.argv[2:]
            trace_dir = None
            if "--trace" in args:
                i = args.index("--trace")
                if i + 1 >= len(args):
                    raise SystemExit("--trace needs a directory")
                trace_dir = args[i + 1]
                del args[i : i + 2]
            sys.exit(
                _chaos_main(
                    args[0] if args else "kill-worker:1", trace_dir=trace_dir
                )
            )
        main()
    except Exception as e:  # emit a parseable line, then fail
        import traceback

        traceback.print_exc()
        print(json.dumps({"metric": "error", "value": 0, "unit": "", "vs_baseline": None, "error": str(e)}))
        sys.exit(1)
