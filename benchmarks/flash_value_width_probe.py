"""Is one flash call with a value of 128 cheaper than two with values of 64?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 48 quotes its table). The kernel alone at the
shapes of ``phi-4-mini-flash-d5.steps`` (one sequence of 8192, 40 query heads
to 20 key heads of 64, bf16), window ``None`` and 512, forward and ``jax.grad``,
in the two forms differential attention can hand it a pair's doubled value:

  (two)  ``impl(q, k12, [v1, v1])`` then ``impl(q, k12, [v2, v2])``, values of
         64, the outputs laid side by side: each softmax map computed twice;
  (one)  ``impl(q, k12, [[v1, v2], [v1, v2]])``, a value of 128: each map once.

The forms are spelled out here so that the probe reads the same whatever
``models/phi4flash.py`` does. ``--repeats`` timed calls a form after two
warm-ups, by turns (two, one, two, one, ...), each to ``block_until_ready``;
a line a form with its median, least and most, then the ratio of the medians
and how far the two forms' results lie apart.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller
for a rehearsal (``--seq 256 --heads 4 --kv-heads 2`` runs interpreted), and
every line names the platform it was read on.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from hypha_tpu.ops.flash_attention import flash_attention


def forms(impl, window):
    """The two forms over q [B, S, H, hd], k12 [B, S, Hkv, hd] (the k1 heads,
    then the k2 heads) and v1, v2 [B, S, Hkv / 2, hd] -> o [B, S, H, 2 hd]."""

    def two(q, k12, v1, v2):
        o1 = impl(q, k12, jnp.concatenate([v1, v1], axis=2), causal=True, window=window)
        o2 = impl(q, k12, jnp.concatenate([v2, v2], axis=2), causal=True, window=window)
        return jnp.concatenate([o1, o2], axis=-1)

    def one(q, k12, v1, v2):
        value = jnp.concatenate([v1, v2], axis=-1)
        return impl(q, k12, jnp.concatenate([value, value], axis=2), causal=True, window=window)

    return {"two_calls_of_64": two, "one_call_of_128": one}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=40)
    ap.add_argument("--kv-heads", type=int, default=20)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--repeats", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = jax.devices()[0]
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    if device.platform != "tpu" and not rehearsal:
        print(f"no TPU (first device: {device.platform}): nothing timed", file=sys.stderr)
        return 3
    where = {"platform": device.platform, "device_kind": device.device_kind}
    impl = functools.partial(flash_attention, interpret=rehearsal)

    keys = jax.random.split(jax.random.key(args.seed), 5)
    s, h, hkv, hd = args.seq, args.heads, args.kv_heads, args.head_dim
    q = jax.random.normal(keys[0], (1, s, h, hd), jnp.bfloat16)
    k12 = jax.random.normal(keys[1], (1, s, hkv, hd), jnp.bfloat16)
    v1 = jax.random.normal(keys[2], (1, s, hkv // 2, hd), jnp.bfloat16)
    v2 = jax.random.normal(keys[3], (1, s, hkv // 2, hd), jnp.bfloat16)
    weigh = jax.random.normal(keys[4], (1, s, h, 2 * hd), jnp.float32)

    for window in (None, args.window):
        for direction in ("forward", "grad"):
            fns, last = {}, {}
            for name, fn in forms(impl, window).items():
                if direction == "grad":
                    loss = lambda q, k, a, b, fn=fn: (fn(q, k, a, b).astype(jnp.float32) * weigh).sum()
                    fn = jax.grad(loss, argnums=(0, 1, 2, 3))
                fns[name] = jax.jit(fn)
                for _ in range(2):  # compile, then once more
                    jax.block_until_ready(fns[name](q, k12, v1, v2))
            walls = {name: [] for name in fns}
            for _ in range(args.repeats):
                for name, fn in fns.items():
                    t0 = time.perf_counter()
                    last[name] = jax.block_until_ready(fn(q, k12, v1, v2))
                    walls[name].append((time.perf_counter() - t0) * 1e3)
            medians = {name: statistics.median(w) for name, w in walls.items()}
            for name, w in walls.items():
                print(json.dumps({"window": window, "direction": direction, "form": name,
                                  "median_ms": round(medians[name], 3), "min_ms": round(min(w), 3),
                                  "max_ms": round(max(w), 3), "repeats": len(w), **where}))
            apart = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                        for a, b in zip(jax.tree.leaves(last["two_calls_of_64"]),
                                        jax.tree.leaves(last["one_call_of_128"])))
            print(json.dumps({"window": window, "direction": direction,
                              "one_over_two": round(medians["one_call_of_128"] / medians["two_calls_of_64"], 4),
                              "largest_difference": apart, **where}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
