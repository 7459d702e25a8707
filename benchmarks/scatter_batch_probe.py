"""What does an indexed row cost on this chip, and how does its batch decide?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 52 quotes its table and ``docs/performance.md``
the rule it gave). The routed layer's combine alone: ``acc.at[rows].add(upd)``
into a donated float32 ``acc`` [T, D], in batches of 2048 to 16384 rows of
which half or all are valid, the indices as a trip of the grouped product's
walk has them (the tokens of contiguous experts: ascending runs of about a
thousand, a batch of 2048 two of them, a buffer of several trips as many as it
has thousands) and the masked rows last, in four forms:

``today``        the rows as they come, the masked ones adding zeros;
``sorted``       the rows sorted by token, the updates gathered into that order
                 (``upd[perm]``: the permutation is made once a layer, outside
                 the walk, so it is an argument here and the gather is timed),
                 ``indices_are_sorted=True``;
``sorted_drop``  as ``sorted`` with the masked rows' index ``T`` (they sort last
                 and ``mode="drop"`` leaves them out);
``gather``       the walk's ``x[rows]`` from a bfloat16 ``x`` [T, D]: the yardstick.

The second section (``--pairs``) holds the router's pair scalars, forward and
backward: ``take_along_axis(scores, idx)`` then ``w.reshape(-1)[order]`` as the
layer did before PR 52, against the one-hot selection and the sort that carries
the weights (``ops.grouped_matmul.sort_pairs_weighted``).

The indices are arguments of the jitted call, never constants (``PERF.md`` §6,
PR 51: a probe knows no more than the program). A form is timed as ``--chain``
calls dispatched back to back, each taking the last one's ``acc``, to one
``block_until_ready``: the device runs them without a gap where a call takes
longer than its dispatch (some 30 µs), so read the gather's 2048-row line as an
upper bound. ``--repeats`` such chains by turns after a warm-up; a line a form
with its median µs a call, µs a row of the batch and of the valid rows, and
what the compiled text holds: ``indices_are_sorted=true`` on the scatter,
whether the compiler put a ``sort`` of its own before it, and the scoped VMEM
of the scatter's fusion. ``--describe`` compiles for a described v5e and prints
the lowerings alone: no chip, no times.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller
for a rehearsal (``--shrink 16``), and every line names the platform.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, ".")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

RUN = 1024  # rows of one expert in a trip, about (LFM2: 1065)


def indices(T: int, batch: int, valid_share: float, seed: int):
    """A batch's token rows (ascending runs of distinct tokens, the masked tail
    last), its mask, and for the two sorted forms the permutation and the
    sorted rows (masked rows as they are, and as ``T``)."""
    rng = np.random.default_rng(seed)
    n_valid = round(batch * valid_share)
    run = min(RUN, T)
    rows = np.concatenate(
        [np.sort(rng.permutation(T)[:run]) for _ in range(-(-batch // run))])[:batch]
    rows[n_valid:] = np.sort(rng.integers(0, T, batch - n_valid))  # pairs held elsewhere
    valid = np.arange(batch) < n_valid
    out = {"rows": rows, "valid": valid}
    for name, key in (("sorted", rows), ("sorted_drop", np.where(valid, rows, T))):
        perm = np.argsort(key, kind="stable")
        out[name] = (perm, key[perm], valid[perm])
    return {k: jax.tree.map(lambda a: jnp.asarray(a, jnp.int32 if a.dtype != bool else bool), v)
            for k, v in out.items()}


def _today(acc, rows, valid, upd):
    return acc.at[rows].add(jnp.where(valid[:, None], upd, 0))


def _by_token(acc, perm, rows, valid, upd):
    return acc.at[rows].add(jnp.where(valid[:, None], upd[perm], 0), indices_are_sorted=True, mode="drop")


def _gather(x, rows, valid):
    return jnp.where(valid[:, None], x[rows], 0)


FNS = {"today": _today, "sorted": _by_token, "sorted_drop": _by_token, "gather": _gather}


def lowering(compiled) -> dict:
    """Which scatter the compiled program holds."""
    text = compiled.as_text()
    scoped = [int(m) for line in text.splitlines() if "scatter" in line and "fusion(" in line
              for m in re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', line)]
    return {
        "indices_are_sorted": "indices_are_sorted=true" in text,
        "compiler_sort": " sort(" in text,
        "scoped_vmem": max(scoped, default=0),
    }


def pair_forms(G: int):
    from hypha_tpu.models.routed import chosen_scores
    from hypha_tpu.ops import grouped_matmul as gm

    def before(scores, idx, weigh):
        w = jnp.take_along_axis(scores, idx, axis=-1)
        order = jnp.argsort(gm._held_key(idx, 0, G)[0], stable=True)  # ``sort_pairs`` as it was then
        return (w.reshape(-1)[order] * weigh).sum()

    def after(scores, idx, weigh):
        w = chosen_scores(scores, idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype))
        return (gm.sort_pairs_weighted(idx, w, 0, G)[1] * weigh).sum()

    return {"before": before, "after": after}


def timed(fns: dict, calls: dict, repeats: int, chain: int):
    """Median µs a call of each form: ``chain`` calls back to back, by turns."""
    walls = {name: [] for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            state = calls[name]()
            t0 = time.perf_counter()
            for _ in range(chain):
                state = fn(state)
            jax.block_until_ready(state)
            walls[name].append((time.perf_counter() - t0) / chain * 1e6)
    return {name: (statistics.median(w), min(w), max(w)) for name, w in walls.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="8192,16384")
    ap.add_argument("--widths", default="2048,2688")
    ap.add_argument("--batches", default="2048,4096,8192,10240,16384")
    ap.add_argument("--valid", default="0.5,1.0")
    ap.add_argument("--forms", default=",".join(FNS))
    ap.add_argument("--pairs", action="store_true", help="the router's pair scalars, not the rows")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shrink", type=int, default=1, help="divide T, D and the batches (a rehearsal)")
    ap.add_argument("--describe", action="store_true", help="compile for a described v5e, print the lowerings, time nothing")
    args = ap.parse_args()

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        where = {"platform": "described v5e", "device_kind": chip.device_set.pop().device_kind}
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    else:
        device = jax.devices()[0]
        if device.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            print(f"no TPU (first device: {device.platform}): nothing timed", file=sys.stderr)
            return 3
        where = {"platform": device.platform, "device_kind": device.device_kind}

    ints = lambda s: [max(int(v) // args.shrink, 8) for v in s.split(",")]
    if args.pairs:
        for name, (T, K, E, G) in {"lfm2": (16384, 4, 64, 8), "trinity": (8192, 8, 128, 8),
                                   "nemotron": (8192, 6, 128, 8), "keye": (16384, 8, 128, 16)}.items():
            T = max(T // args.shrink, 8)
            keys = jax.random.split(jax.random.key(args.seed), 3)
            scores = jax.nn.sigmoid(jax.random.normal(keys[0], (T, E), jnp.float32))
            _, idx = jax.lax.top_k(scores, K)
            weigh = jax.random.normal(keys[1], (T * K,), jnp.float32)
            fns = {n: jax.jit(jax.value_and_grad(f)) for n, f in pair_forms(G).items()}
            if args.describe:
                for n, fn in fns.items():
                    text = fn.lower(like(scores), like(idx), like(weigh)).compile().as_text()
                    print(json.dumps({"pairs_of": name, "form": n, "gathers": text.count(" gather("),
                                      "scatters": text.count(" scatter("), "sorts": text.count(" sort("), **where}), flush=True)
                continue
            got = {n: jax.block_until_ready(fn(scores, idx, weigh)) for n, fn in fns.items()}
            same = all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(got["before"]), jax.tree.leaves(got["after"])))
            calls = {n: (lambda: None) for n in fns}
            run = {n: (lambda _, fn=fn: fn(scores, idx, weigh)) for n, fn in fns.items()}
            for n, (med, lo, hi) in timed(run, calls, args.repeats, args.chain).items():
                print(json.dumps({"pairs_of": name, "T": T, "K": K, "E": E, "form": n, "median_us": round(med, 1),
                                  "min_us": round(lo, 1), "max_us": round(hi, 1), "bit_for_bit": same, **where}), flush=True)
        return 0

    wanted = args.forms.split(",")
    for T in ints(args.tokens):
        for D in ints(args.widths):
            x = jax.ShapeDtypeStruct((T, D), jnp.bfloat16) if args.describe else \
                jax.random.normal(jax.random.key(args.seed), (T, D), jnp.bfloat16)
            for batch in ints(args.batches):
                for share in (float(v) for v in args.valid.split(",")):
                    ix = indices(T, batch, share, args.seed)
                    upd = jax.ShapeDtypeStruct((batch, D), jnp.float32) if args.describe else \
                        jax.random.normal(jax.random.key(args.seed + 1), (batch, D), jnp.float32)
                    operands = {
                        "today": (ix["rows"], ix["valid"], upd), "sorted": (*ix["sorted"], upd),
                        "sorted_drop": (*ix["sorted_drop"], upd), "gather": (ix["rows"], ix["valid"]),
                    }
                    jitted, run, calls, lowered = {}, {}, {}, {}
                    for name in wanted:
                        fn, ops = FNS[name], operands[name]
                        head = x if name == "gather" else jax.ShapeDtypeStruct((T, D), jnp.float32)
                        jitted[name] = jax.jit(fn, donate_argnums=() if name == "gather" else (0,))
                        shapes = [head, *ops] if not args.describe else [like(a) for a in (head, *ops)]
                        compiled = jitted[name].lower(*shapes).compile()
                        lowered[name] = lowering(compiled)
                        if name == "gather":
                            run[name] = lambda _, c=compiled, ops=ops: c(x, *ops)
                            calls[name] = lambda: None
                        else:
                            run[name] = lambda acc, c=compiled, ops=ops: c(acc, *ops)
                            calls[name] = lambda: jnp.zeros((T, D), jnp.float32)
                    if args.describe:
                        for name in wanted:
                            print(json.dumps({"T": T, "D": D, "batch": batch, "valid": share, "form": name,
                                              **lowered[name], **where}), flush=True)
                        continue
                    if len({"today", "sorted", "sorted_drop"} & set(wanted)) > 1:
                        sums = {n: run[n](calls[n]()) for n in wanted if n != "gather"}
                        first = next(iter(sums.values()))
                        apart = {n: float(jnp.abs(s - first).max()) for n, s in sums.items()}
                    else:
                        apart = {}
                    for name, (med, lo, hi) in timed(run, calls, args.repeats, args.chain).items():
                        print(json.dumps({
                            "T": T, "D": D, "batch": batch, "valid": share, "form": name,
                            "median_us": round(med, 1), "min_us": round(lo, 1), "max_us": round(hi, 1),
                            "us_a_row": round(med / batch, 4), "us_a_valid_row": round(med / (batch * share), 4),
                            "largest_difference_from_the_first": apart.get(name), **lowered[name], **where,
                        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
