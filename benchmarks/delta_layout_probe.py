"""In which order does a worker's delta leave the chip, and what does asking cost?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 47 quotes its table). On a cell's seeded
``params`` and an anchor, ``--rounds`` rounds each of three ways to make the
round's pseudo-gradient and bring it to the host:

  (i)   ``jit(p - a)`` as it is compiled by default: every leaf in the
        device's own order, which ``device_get`` keeps on the host;
  (ii)  the same program with ``out_shardings`` a tree of
        ``Format(Layout(major_to_minor=range(ndim)), leaf.sharding)``: the
        chip lays every result out row-major;
  (iii) the leaves whose device layout is not row-major reshaped to one
        dimension inside the jit, viewed back with ``reshape`` on the host.

A line a round: the jitted call's wall to ``block_until_ready``, the
``device_get``'s wall, the sum of ``np.ascontiguousarray`` over the leaves
(what ``compress.write_delta`` pays before it can write a leaf's own memory),
how many leaves and bytes came back not C-contiguous, and the device's
``peak_bytes_in_use``. The last line says whether (ii) and (iii) gave (i)'s
values bit for bit. Nothing here reads ``hypha_tpu.executor.diloco``: the
forms are spelled out so that the probe reads the same on any commit.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller
for a rehearsal, and every line names the platform it was read on.

  python3 benchmarks/delta_layout_probe.py                 # the Nemotron cell
  python3 benchmarks/delta_layout_probe.py --cell mistral-7b-d1.steps
  JAX_PLATFORMS=cpu python3 benchmarks/delta_layout_probe.py \
      --set hidden_size=64 --set vocab_size=256 ...        # a rehearsal
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout


def row_major(leaf) -> bool:
    return tuple(leaf.format.layout.major_to_minor) == tuple(range(leaf.ndim))


def delta(p, a):
    return jax.tree.map(lambda p, a: (p - a).astype(jnp.float32), p, a)


def seeded(cell_name: str, seed: int, sets: dict):
    """The cell's ``params`` as the worker seeds them, and an anchor."""
    from hypha_tpu.models import build_model
    from hypha_tpu.ops.flash_attention import flash_attention
    from perfbench import data, manifest

    cell = manifest.resolve(cell_name)
    conf = dict(
        s.removeprefix("job.model_config.").split("=", 1)
        for s in cell.config["job_sets"]
        if s.startswith("job.model_config.")
    )
    conf = {k: json.loads(v) for k, v in conf.items()} | sets
    family = next(
        s.split("=", 1)[1] for s in cell.config["job_sets"] if s.startswith("job.model_family=")
    )
    interpret = jax.default_backend() != "tpu"
    model, _ = build_model(
        {"family": family, "config": conf},
        functools.partial(flash_attention, interpret=interpret),
    )
    seq = min(int(conf.get("max_seq_len", 64)), 64)  # init needs shapes, not the cell's S
    ids = jnp.zeros((1, seq), jnp.int32)
    anchor = jax.jit(model.init)(jax.random.key(data.model_seed(seed)), ids)["params"]
    # What a round's steps leave: every weight moved a little, out of a jit.
    params = jax.jit(lambda t: jax.tree.map(lambda x: x * 1.001 + 1e-4, t))(anchor)
    return jax.block_until_ready(params), anchor


def forms(params):
    leaves = jax.tree.leaves(params)
    asked = jax.tree.map(
        lambda p: Format(Layout(major_to_minor=tuple(range(p.ndim))), p.sharding), params
    )
    flat = jax.tree.map(lambda p: not row_major(p), params)
    shapes = jax.tree.map(lambda p: p.shape, params)

    def delta_1d(p, a):
        return jax.tree.map(
            lambda p, a, f: (p - a).astype(jnp.float32).reshape(-1 if f else p.shape), p, a, flat
        )

    def view_back(host):
        return jax.tree.map(
            lambda h, s: h.reshape(s), host, shapes
        )

    print(json.dumps({
        "leaves": len(leaves),
        "bytes": sum(p.size * 4 for p in leaves),
        "not_row_major_on_device": [
            {"shape": list(p.shape), "major_to_minor": list(p.format.layout.major_to_minor)}
            for p in leaves if not row_major(p)
        ],
    }), flush=True)
    return {
        "i_default": (jax.jit(delta), lambda host: host),
        "ii_out_layout": (jax.jit(delta, out_shardings=asked), lambda host: host),
        "iii_one_dimension": (jax.jit(delta_1d), view_back),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="nemotron-twotower-ctx-d7.steps")
    ap.add_argument("--seed", type=int, default=2147480005)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="a model_config key to override (a rehearsal's small widths)")
    args = ap.parse_args()
    sets = {k: json.loads(v) for k, v in (s.split("=", 1) for s in args.set)}
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform, "device_kind": device.device_kind,
                      "cell": args.cell, "seed": args.seed}), flush=True)
    if device.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"no TPU: the first device is {device.platform!r}; a rehearsal off the "
              "chip asks for it with JAX_PLATFORMS=cpu", file=sys.stderr)
        sys.exit(3)
    params, anchor = seeded(args.cell, args.seed, sets)
    kept: dict[str, dict] = {}
    for name, (program, on_host) in forms(params).items():
        for rnd in range(args.rounds):
            t0 = time.perf_counter()
            out = jax.block_until_ready(program(params, anchor))
            t1 = time.perf_counter()
            host = on_host(jax.device_get(out))
            t2 = time.perf_counter()
            got = jax.tree.leaves(host)
            strided = [h for h in got if not h.flags.c_contiguous]
            contiguous = [np.ascontiguousarray(h) for h in got]
            t3 = time.perf_counter()
            stats = device.memory_stats() or {}
            print(json.dumps({
                "platform": device.platform, "form": name, "round": rnd,
                "call_s": round(t1 - t0, 4), "device_get_s": round(t2 - t1, 4),
                "extract_s": round(t2 - t0, 4), "ascontiguous_s": round(t3 - t2, 4),
                "leaves_not_c_contiguous": len(strided),
                "bytes_not_c_contiguous": sum(h.nbytes for h in strided),
                "strides": sorted({(h.shape, h.strides) for h in strided}),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            }), flush=True)
            del out, host, got, strided
            if rnd < args.rounds - 1:
                del contiguous
        kept[name] = contiguous
    first = kept.pop("i_default")
    print(json.dumps({
        f"{name}_equals_i_bit_for_bit": all(
            a.shape == b.shape and a.tobytes() == b.tobytes() for a, b in zip(first, other)
        )
        for name, other in kept.items()
    }), flush=True)


if __name__ == "__main__":
    main()
