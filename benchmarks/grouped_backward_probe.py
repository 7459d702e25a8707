"""What does a trip of the grouped product's backward walk pay for?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 51 quotes its table). One routed layer alone,
``value_and_grad`` in the tokens, the matrices and the routing weights, bf16,
at the four sparse cells' shapes and loads, with the window of
``ops/grouped_matmul.py`` (``_window``: how many contiguous experts a trip's
weight gradient is taken over) set to 2, 4, 8 and to all held experts. At all
held every trip adds a cotangent dense over them into the whole float32 sum,
which is the walk as it was before PR 51 (the probe's first runs held it
against that commit's module: equal to 0.3 ms and bit for bit).

The routing (each pair's token and the experts' group sizes) is an argument of
the jitted call, as the router makes it: closed over as constants, the compiler
knows the trip count, drops a loop of one trip and times a program the model
never runs (this probe's first lesson). ``--repeats`` timed calls a window after
two warm-ups, by turns, each to ``block_until_ready``; a line a window with its
median, least and most, ``grad_experts_per_trip`` by ``plan_trips``, and how
far its results lie from the first window's.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller
for a rehearsal (``--shrink 16``), and every line names the platform.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from hypha_tpu.ops import grouped_matmul

# tokens, choices a token, experts held, width, expert width, form, trip, pairs a
# token held here, and the load: ``skew`` h, one expert holding h times what
# each other does, or ``shares`` spelled out.
CELLS = {
    "lfm2": dict(T=16384, K=4, G=8, D=2048, F=1536, form="swiglu", chunk=2048, ppt=0.52, skew=1),
    # LFM2's shape, five experts nearly empty between full ones: the trips change kind
    "lfm2mix": dict(T=16384, K=4, G=8, D=2048, F=1536, form="swiglu", chunk=2048, ppt=0.52,
                    shares=[1500, 100, 100, 100, 100, 100, 3000, 3500]),
    "trinity": dict(T=8192, K=8, G=8, D=2048, F=1024, form="swiglu", chunk=2048, ppt=0.41, skew=8),
    "nemotron": dict(T=8192, K=6, G=8, D=2688, F=1856, form="relu2", chunk=2048, ppt=0.18, skew=40),
    "keye10": dict(T=16384, K=8, G=16, D=2048, F=768, form="swiglu", chunk=16384, ppt=1.0, skew=1),
    "keye48": dict(T=16384, K=8, G=16, D=2048, F=768, form="swiglu", chunk=16384, ppt=4.8, skew=1),
}


def inputs(c: dict, seed: int):
    T, K, G, D, F = c["T"], c["K"], c["G"], c["D"], c["F"]
    rng = np.random.default_rng(seed)
    if "shares" in c:
        share = np.asarray(c["shares"], float)
    else:
        share = np.ones(G)
        share[rng.integers(G)] = c["skew"]
        if c["skew"] == 1:  # even, within a tenth
            share = share * (1 + 0.1 * rng.standard_normal(G).clip(-2, 2))
    sizes = np.floor(share / share.sum() * round(c["ppt"] * T)).astype(np.int32)
    # an expert is chosen once a token at the most: a permutation's head an expert
    tokens = np.concatenate(
        [rng.permutation(T)[:s] for s in sizes] + [rng.integers(0, T, T * K - sizes.sum())])
    keys = jax.random.split(jax.random.key(seed), 6)
    shapes = [(G, D, F)] * (2 if c["form"] == "swiglu" else 1) + [(G, F, D)]
    ws = tuple((jax.random.normal(k, s, jnp.float32) * 0.02).astype(jnp.bfloat16)
               for k, s in zip(keys[:3], shapes))
    x = jax.random.normal(keys[3], (T, D), jnp.bfloat16)
    wts = jax.random.uniform(keys[4], (T * K,), jnp.float32)
    weigh = jax.random.normal(keys[5], (T, D), jnp.float32)
    return x, ws, wts, jnp.asarray(tokens, jnp.int32), jnp.asarray(sizes), weigh


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--windows", default="0,2,4,8", help="0: all held experts, every trip dense")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shrink", type=int, default=1, help="divide T, D, F and the trip (a rehearsal)")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"no TPU (first device: {device.platform}): nothing timed", file=sys.stderr)
        return 3
    where = {"platform": device.platform, "device_kind": device.device_kind}

    for name in args.cells.split(","):
        c = dict(CELLS[name])
        for key in ("T", "D", "F", "chunk"):
            c[key] = max(c[key] // args.shrink, 8)
        x, ws, wts, tokens, sizes, weigh = inputs(c, args.seed)
        widths = sorted({min(int(w) or c["G"], c["G"]) for w in args.windows.split(",")})
        fns, plans = {}, {}
        for width in widths:
            def loss(x, ws, wts, tokens, sizes, width=width):
                grouped_matmul._window = lambda held: min(held, width)  # read while tracing
                y = grouped_matmul.grouped_experts(x, ws, tokens, wts, sizes, form=c["form"], chunk=c["chunk"])
                return (y * weigh).sum()

            fns[width] = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            for _ in range(2):  # compile, then once more
                last = jax.block_until_ready(fns[width](x, ws, wts, tokens, sizes))
            plan = jax.jit(lambda sizes: grouped_matmul.plan_trips(sizes, c["chunk"], tokens.shape[0]))(sizes)
            plans[width] = int(plan["grad_experts"]) / max(int(plan["trips"]), 1), int(plan["trips"])
        walls, results = {w: [] for w in fns}, {}
        for _ in range(args.repeats):
            for width, fn in fns.items():
                t0 = time.perf_counter()
                results[width] = jax.block_until_ready(fn(x, ws, wts, tokens, sizes))
                walls[width].append((time.perf_counter() - t0) * 1e3)
        first = jax.tree.leaves(results[widths[-1]])  # all held experts where asked for: the walk as it was
        for width, w in walls.items():
            apart = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                        for a, b in zip(jax.tree.leaves(results[width]), first))
            print(json.dumps({
                "cell": name, "window": width, "held": c["G"], "median_ms": round(statistics.median(w), 3),
                "min_ms": round(min(w), 3), "max_ms": round(max(w), 3), "repeats": len(w),
                "pairs": int(sizes.sum()), "load_max": int(sizes.max()), "trips": plans[width][1],
                "grad_experts_per_trip": round(plans[width][0], 3),
                "largest_difference_from_the_widest": apart, **where}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
