"""What does a grid step cost that does no work?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 56 quotes its table). The three flash kernels
alone at the seven shapes the cells call them with (bf16, the default tiles),
forward and ``jax.grad`` (forward + the two backward kernels), in two trees:

  (parent)  ``--parent`` (a ``git archive`` export of the commit before PR 56):
            a dense grid ``(batch·head, q tiles, k tiles)``; a tile above the
            diagonal or outside the band skips its work and keeps its step;
  (change)  this tree: a causal call's grid lists the needed tiles and no other.

``--repeats`` timed calls a tree after two warm-ups, by turns (parent, change,
parent, change, ...), each to ``block_until_ready``; a line a tree with its
median, least and most and, read from the traced program, the grid steps of
the three kernels a query head beside the tiles ``_block_needed`` admits (the
mechanism's counter: the share of steps that do work). Then a line a shape and
direction with the ratio of the medians and whether every result (the output,
a selection's log-sum-exp, the three gradients) is the parent's bit for bit.

The gate, written before the first run: forward + backward at Trinity's window
shape at least 15 % faster, at the full triangle with heads of 128 (Nemotron's
shape) at least 8 %, no shape's forward + backward more than 1 % slower (the
timer's own noise at these lengths), and every bit equal. The probe exits 1
where the gate fails.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller
for a rehearsal (``--shrink 4 --repeats 1`` runs interpreted at a quarter of
every length, one key head a shape; its times say nothing and the gate is not
read), and every line names the platform it was read on.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

# name: (batch, sequence, query heads, key heads, head size, value's width, window, under a selection)
SHAPES = {
    "nemotron_full": (1, 8192, 32, 2, 128, 128, None, False),
    "lfm2_full": (2, 8192, 32, 8, 64, 64, None, False),
    "phi4_full": (1, 8192, 40, 20, 64, 128, None, False),
    "keye_selected": (1, 16384, 32, 4, 128, 128, None, True),
    "mistral_full": (4, 4096, 32, 8, 128, 128, None, False),
    "trinity_window": (1, 8192, 32, 4, 128, 128, 2048, False),
    "phi4_window": (1, 8192, 40, 20, 64, 128, 512, False),
}
GATE = {"trinity_window": 0.85, "nemotron_full": 0.92}  # change over parent, forward + backward, at most
NO_SLOWER = 1.01


def tree(path):
    """``hypha_tpu.ops.flash_attention`` of the checkout at ``path``, beside
    whatever tree's modules the process already holds."""
    held = {name: sys.modules.pop(name) for name in list(sys.modules) if name.split(".")[0] == "hypha_tpu"}
    sys.path.insert(0, path)
    try:
        module = importlib.import_module("hypha_tpu.ops.flash_attention")
        assert os.path.abspath(module.__file__).startswith(os.path.abspath(path) + os.sep), module.__file__
    finally:
        sys.path.remove(path)
        for name in [name for name in sys.modules if name.split(".")[0] == "hypha_tpu"]:
            del sys.modules[name]
        sys.modules.update(held)
    return module


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(inner)


def steps_a_head(fn, args, heads) -> float:
    """Grid steps of every kernel ``fn`` traces to, a query head and sequence."""
    calls = pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    return sum(math.prod(call.params["grid_mapping"].grid) for call in calls) / heads


def needed_a_head(module, seq, window, tiles, direction) -> int:
    """Tiles ``_block_needed`` admits in the kernels ``direction`` runs."""
    def count(bq, bk):
        return int(np.sum(module._block_needed(*np.indices((seq // bq, seq // bk)), bq, bk, window)))

    forward, backward = count(*tiles[:2]), count(*tiles[2:])
    return forward if direction == "forward" else forward + 2 * backward


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=".export/parent")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--repeats", type=int, default=12)
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/flash_grid_probe.jsonl")
    args = ap.parse_args()

    device = jax.devices()[0]
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    if device.platform != "tpu" and not rehearsal:
        print(f"no TPU (first device: {device.platform}): nothing timed", file=sys.stderr)
        return 3
    where = {"platform": device.platform, "device_kind": device.device_kind}
    trees = {"parent": tree(args.parent), "change": tree(".")}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "w")

    def say(line):
        text = json.dumps({**line, **where})
        print(text, flush=True)
        out.write(text + "\n")

    ratios, same = {}, True
    for name in args.shapes.split(","):
        b, s, h, hkv, hd, dv, window, selected = SHAPES[name]
        tiles = (512, 512, 1024, 512)  # the defaults: _pick_block's caps, which divide every length here
        blocks = {}
        if args.shrink > 1:
            s, h, hkv = s // args.shrink, h // hkv, 1
            window = window and window // args.shrink
            tiles = tuple(t // args.shrink for t in tiles)
            blocks = dict(zip(("block_q", "block_k", "block_q_bwd", "block_k_bwd"), tiles))
        keys = jax.random.split(jax.random.key(args.seed), 5)
        q = jax.random.normal(keys[0], (b, s, h, hd), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, s, hkv, hd), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, s, hkv, dv), jnp.bfloat16)
        weigh = jax.random.normal(keys[3], (b, s, h, dv), jnp.float32)
        picked = {}
        if selected:  # any words are a selection: half of all pairs
            words = jax.random.bits(keys[4], (b, s, -(-s // 4096) * 128), jnp.uint32)
            picked = {"selection": jax.lax.bitcast_convert_type(words, jnp.int32)}

        for direction in ("forward", "grad"):
            fns, last, walls, counted = {}, {}, {}, {}
            for side, module in trees.items():
                call = functools.partial(module.flash_attention, causal=True, window=window,
                                         interpret=rehearsal, **blocks, **picked)
                fn = call
                if direction == "grad":
                    def fn(q, k, v, call=call):
                        o = call(q, k, v)
                        return ((o[0] if selected else o).astype(jnp.float32) * weigh).sum()
                    fn = jax.grad(fn, argnums=(0, 1, 2))
                counted[side] = steps_a_head(fn, (q, k, v), b * h)
                fns[side] = jax.jit(fn)
                for _ in range(2):  # compile, then once more
                    jax.block_until_ready(fns[side](q, k, v))
                walls[side] = []
            for _ in range(args.repeats):
                for side, fn in fns.items():
                    t0 = time.perf_counter()
                    last[side] = jax.block_until_ready(fn(q, k, v))
                    walls[side].append((time.perf_counter() - t0) * 1e3)
            medians = {side: statistics.median(w) for side, w in walls.items()}
            needed = needed_a_head(trees["change"], s, window, tiles, direction)
            for side, w in walls.items():
                say({"shape": name, "direction": direction, "tree": side,
                     "median_ms": round(medians[side], 3), "min_ms": round(min(w), 3), "max_ms": round(max(w), 3),
                     "ms_a_head": round(medians[side] / (b * h), 4), "repeats": len(w),
                     "steps_a_head": counted[side], "needed_tiles_a_head": needed,
                     "working_share_of_steps": round(needed / counted[side], 4)})
            equal = all(bool((a == c).all()) and a.dtype == c.dtype and a.shape == c.shape
                        for a, c in zip(jax.tree.leaves(last["parent"]), jax.tree.leaves(last["change"])))
            same &= equal
            ratios[name, direction] = medians["change"] / medians["parent"]
            say({"shape": name, "direction": direction, "change_over_parent": round(ratios[name, direction], 4),
                 "results_compared": len(jax.tree.leaves(last["change"])), "bit_for_bit": equal})

    if rehearsal:
        say({"gate": "not read: a rehearsal's times are the interpreter's", "bit_for_bit": same})
        return 0 if same else 1
    whole = {name: r for (name, direction), r in ratios.items() if direction == "grad"}
    passed = (same and all(whole.get(name, 0.0) <= most for name, most in GATE.items())
              and all(r <= NO_SLOWER for r in whole.values()))
    say({"gate": "passed" if passed else "failed", "needs_at_most": GATE, "no_shape_over": NO_SLOWER,
         "forward_and_backward": {name: round(r, 4) for name, r in whole.items()}, "bit_for_bit": same})
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
