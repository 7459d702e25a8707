"""What does one layer's Mamba-2 scan cost on this chip, as ``einsum``s and as
the kernels?

A probe for the chip, not a test (run from the root of a checkout, through the
chip tool; ``PERF.md`` §6, PR 54 quotes its lines). ``hypha_tpu.ops.ssd_scan``
at the shapes of ``nemotron-twotower-ctx-d7.steps``: one sequence of 8192, 64
heads of 64 in 8 groups, a state of 128, chunk 128, bfloat16 inputs and a
float32 step, drawn as the layer hands them over (``dt`` after its softplus,
``A = -(1 ... heads)``). Each form is timed forward (``y`` and the last state)
and forward and backward (``jax.grad`` of a weighted sum of ``y`` over all five
inputs), in two ways:

``einsum``   the form the program runs off the chip (``ops.ssd_scan._scan``),
             which was the chip's too until PR 54: PR 46 read 2.67 ms and 8.78;
``kernels``  the Pallas kernels (``interpret=False``), the running sum of ``dt
             A`` as the module makes it (a product with a triangle of ones);
``log_step`` the same kernels with that sum made on the vector unit instead,
             seven shifted adds along the lanes (the probe's own function, put
             in the module's place for these lines alone);
``groups_N`` the same kernels with ``N`` groups of heads a grid step
             (``--groups 1,2,4``: the module's ``GROUPS_A_PROGRAM`` set for
             these lines alone; from 4 on a step's blocks outgrow the 16 MB of
             VMEM a kernel is given by default, and the probe asks for 100).

A form is timed as ``--chain`` calls dispatched back to back to one
``block_until_ready`` (a call takes longer than its dispatch), ``--repeats``
such chains by turns after a warm-up; a line a form with the median ms a call
and the range. The last lines give how far the kernels' results lie from the
``einsum`` form's on this chip, as a share of each result's largest value.

``--mixer`` is the second section: one whole Mamba-2 mixer of the cell
(``models.nemotron_h._Mamba2``: both projections, the convolution, the scan, ``D
x`` and the gated norm) forward and backward under the profiler, the device's
time a call booked to the innermost of the scopes ``ssd_scan``, ``gated_norm``,
``in_proj`` and ``out_proj`` an operation ran under (what is left is the
convolution, the splits, the step's softplus and ``D x``), and the operations
that took most, each with its scope: what ``PERF.md`` §5 says of the mixer
outside its scan.

A measurement is a chip's: where the first device is no TPU the probe exits 3
before it times anything, unless ``JAX_PLATFORMS=cpu`` was set by the caller for
a rehearsal (``--shrink 16`` and the kernels interpreted), and every line names
the platform.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, ".")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from hypha_tpu.ops import ssd_scan as op

HEADS, HEAD, GROUPS, STATE = 64, 64, 8, 128


def inputs(s: int, seed: int):
    k = jax.random.split(jax.random.key(seed), 6)
    bf = jnp.bfloat16
    return (
        jax.random.normal(k[0], (1, s, HEADS, HEAD), bf),
        jax.nn.softplus(jax.random.normal(k[1], (1, s, HEADS)) - 4.0),
        -jnp.arange(1, HEADS + 1, dtype=jnp.float32),
        jax.random.normal(k[2], (1, s, GROUPS, STATE), bf),
        jax.random.normal(k[3], (1, s, GROUPS, STATE), bf),
    ), jax.random.normal(k[4], (1, s, HEADS, HEAD))


def einsum_form(x, dt, a, b, c):
    batch, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    y, last = op._scan(x.reshape(batch, s, g, r, p), dt.reshape(batch, s, g, r), a.reshape(g, r), b, c,
                       jnp.zeros((batch, g, r, p, n), jnp.float32), op.CHUNK)
    return y.reshape(batch, s, h, p), last.reshape(batch, h, p, n)


def running_log_step(da):
    """The running sum along the lanes as seven shifted adds of one tile."""
    from jax.experimental.pallas import tpu as pltpu

    lane = jax.lax.broadcasted_iota(jnp.int32, da.shape, 1)
    k = 1
    while k < da.shape[1]:
        da = da + jnp.where(lane >= k, pltpu.roll(da, k, 1), 0.0)
        k *= 2
    return da


SCOPES = ("ssd_scan", "gated_norm", "in_proj", "out_proj")


def mixer(args, device: str, trace_dir: str) -> list:
    """One mixer's device time by scope, from the profiler's trace of ``--chain`` calls."""
    import gzip
    from pathlib import Path

    from hypha_tpu.models.nemotron_h import NemotronHConfig, _Mamba2
    from perfbench.readers.device_scope import device_events
    from perfbench.xplane import op_name, self_times

    cfg = NemotronHConfig()
    k = jax.random.split(jax.random.key(args.seed), 3)
    u = jax.random.normal(k[0], (1, 8192 // args.shrink, cfg.hidden_size), jnp.bfloat16)
    weigh = jax.random.normal(k[1], u.shape, jnp.bfloat16)
    layer = _Mamba2(cfg)
    variables = jax.jit(layer.init)(k[2], u)
    step = jax.jit(jax.grad(lambda v, u, w: jnp.sum((layer.apply(v, u) * w).astype(jnp.float32)), argnums=(0, 1)))
    for _ in range(2):
        jax.block_until_ready(step(variables, u, weigh))
    with jax.profiler.trace(trace_dir):
        for _ in range(args.chain):
            out = step(variables, u, weigh)
        jax.block_until_ready(out)
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.trace.json.gz"))
    if not files:
        return [{"device": device, "mixer": "no trace was written"}]
    with gzip.open(files[-1]) as f:
        events = device_events(json.load(f))

    def scope(e):
        path = e.get("args", {}).get("tf_op", "").rstrip(":").split("/")
        return next((s for s in SCOPES if s in path), "rest")

    times = self_times([(int(e["ts"] * 1000), int(e["dur"] * 1000), f"{scope(e)} {op_name(e['name'])}")
                        for e in events])
    by_scope = {}
    for name, ns in times.items():
        by_scope[name.split(" ", 1)[0]] = by_scope.get(name.split(" ", 1)[0], 0) + ns
    ms = lambda ns: round(ns / args.chain / 1e6, 4)
    lines = [{"device": device, "mixer": "forward_and_backward", "sequence": u.shape[1],
              "ms_by_scope": {k: ms(v) for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])},
              "ms_all": ms(sum(by_scope.values()))}]
    lines += [{"device": device, "mixer_op": name, "ms": ms(ns)}
              for name, ns in sorted(times.items(), key=lambda kv: -kv[1])[:args.top]]
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mixer", action="store_true", help="the whole mixer by scope, and nothing else")
    ap.add_argument("--top", type=int, default=30, help="operations the mixer's section lists")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--groups", default="", help="further forms: the kernels at these groups a grid step, e.g. 1,2,4")
    ap.add_argument("--shrink", type=int, default=1, help="divide the sequence (a rehearsal off the chip)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    if platform != "tpu" and not rehearsal:
        print(f"no chip: the first device is {platform}", file=sys.stderr)
        return 3
    device = f"{platform}:{jax.devices()[0].device_kind}"
    if args.mixer:
        with tempfile.TemporaryDirectory() as trace_dir:  # tens of MB: read here, not brought back
            return report(mixer(args, device, trace_dir), args.out)
    (x, dt, a, b, c), weigh = inputs(8192 // args.shrink, args.seed)
    interpret = platform != "tpu"

    def kernels(x, dt, a, b, c):
        return op.ssd_scan(x, dt, a, b, c, interpret=interpret)

    def passes(form):
        grad = jax.grad(lambda *t: jnp.sum(form(*t[:5])[0] * t[5]), argnums=tuple(range(5)))
        return {"forward": lambda *t: form(*t[:5]), "forward_and_backward": grad}

    def roomy(interpret):
        from jax.experimental.pallas import tpu as pltpu

        return {} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=100 * 2**20)}

    module = {k: getattr(op, k) for k in ("_running", "GROUPS_A_PROGRAM", "_tpu_kwargs")}
    forms = {"einsum": (einsum_form, {}), "kernels": (kernels, {}), "log_step": (kernels, {"_running": running_log_step})}
    for n in filter(None, args.groups.split(",")):
        forms[f"groups_{n}"] = (kernels, {"GROUPS_A_PROGRAM": int(n), **({"_tpu_kwargs": roomy} if int(n) > 2 else {})})
    fns, results, lines = {}, {}, []
    for name, (form, patches) in forms.items():
        for k, v in {**module, **patches}.items():  # read when a kernel is traced: each form is traced and compiled here, once
            setattr(op, k, v)
        jax.clear_caches()
        for which, fn in passes(form).items():
            t0 = time.perf_counter()
            fns[name, which] = fn = jax.jit(fn).lower(x, dt, a, b, c, weigh).compile()
            results[name, which] = jax.block_until_ready(fn(x, dt, a, b, c, weigh))
            print(f"{device} {name} {which}: compiled and run in {time.perf_counter() - t0:.2f} s, "
                  f"{len(fn.as_text())} characters of program", flush=True)
    for k, v in module.items():
        setattr(op, k, v)
    times = {key: [] for key in fns}
    for _ in range(args.repeats):
        for key, fn in fns.items():
            t0 = time.perf_counter()
            out = None
            for _ in range(args.chain):
                out = fn(x, dt, a, b, c, weigh)
            jax.block_until_ready(out)
            times[key].append((time.perf_counter() - t0) * 1e3 / args.chain)
    for (name, which), ms in times.items():
        line = {"device": device, "form": name, "pass": which, "sequence": x.shape[1],
                "ms_median": round(statistics.median(ms), 4), "ms_min": round(min(ms), 4), "ms_max": round(max(ms), 4)}
        lines.append(line)

    def apart(got, want):
        got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
        return round(float(jnp.abs(got - want).max() / jnp.abs(want).max()), 6)

    for name in [f for f in forms if f != "einsum"]:
        line = {"device": device, "form": name, "apart_from_einsum": {
            "y": apart(results[name, "forward"][0], results["einsum", "forward"][0]),
            "last_state": apart(results[name, "forward"][1], results["einsum", "forward"][1]),
            **{k: apart(g, w) for k, g, w in zip(("dx", "ddt", "da", "db", "dc"),
                                                 results[name, "forward_and_backward"],
                                                 results["einsum", "forward_and_backward"])}}}
        lines.append(line)
    return report(lines, args.out)


def report(lines: list, out: str) -> int:
    for line in lines:
        print(json.dumps(line), flush=True)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
