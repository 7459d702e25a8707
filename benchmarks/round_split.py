"""One traced run of a benchmark cell, with what ``perfbench/run.py`` leaves out.

``python3 benchmarks/round_split.py --workload <cell> --seed <n> [--clock]``
runs the cell through the benchmark's own ``perfbench/run.py`` with
``--trace 1`` and two additions, both made here so that no file of the
benchmark changes:

* the per-layer metrics of ``perfbench/pending_per_layer.json`` (the split of
  the outer sync by phase span, PERF.md 3) are reported beside the ones
  ``BENCHMARK.json`` lists;
* ``--clock`` checks the assumption ``perfbench/xplane.py`` makes when it
  lines program spans up with device events: that a time in the profiler's
  trace plus the wall-clock mark taken when the trace opened is a wall-clock
  time. The worker enters ``StepTraceAnnotation("inner_step", step_num=n)``
  around each inner step and writes a ``step`` span with wall-clock times
  for the same interval; before the raw trace is reduced and deleted, every
  annotation in it is paired with its span and the differences are printed
  (``{"phase": "clock", ...}``) and kept in ``chiprun_out/perfbench/<cell>/
  clock.json``. That difference is the error of every ``idle_gaps`` entry.

Like the benchmark, the harness process never imports jax: the trace is read
in a child (``--reduce``), after the worker is gone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PHASES = {"encode", "upload", "await_update", "merge"}  # the worker's sync spans
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def clock_offsets(profile_dir: Path, span_dir: Path) -> dict:
    """Annotation start + the opening mark, minus the span's wall start, for
    every ``inner_step`` annotation in the trace. Needs jax's trace reader."""
    from jax.profiler import ProfileData

    from perfbench import logs

    marks = json.loads((profile_dir / "marks.json").read_text())
    files = sorted(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return {"error": "no xplane file"}
    steps = sorted(
        (s for s in logs.read_spans(span_dir) if s["name"] == "step" and s["node"] == "w0"),
        key=lambda s: s["start_ns"],
    )
    by_number = dict(enumerate(steps))  # step_num counts the job's steps from 0
    starts, ends, named = [], [], set()
    data = ProfileData.from_file(str(files[-1]))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                named.add(ev.name)
                if ev.name != "inner_step":
                    continue
                span = by_number.get(dict(ev.stats).get("step_num"))
                if span is None:
                    continue
                at = marks["start_wall_ns"] + int(ev.start_ns)
                starts.append(at - span["start_ns"])
                ends.append(at + int(ev.duration_ns) - span["end_ns"])
    if not starts:
        return {"error": "no inner_step annotation matched a step span",
                "step_spans": len(steps)}
    worst = lambda v: max(v, key=abs)
    return {
        "idle_gaps_all": all_idle_gaps(data, marks, logs.read_spans(span_dir)),
        "steps": len(starts),
        "start_offset_us": {"median": statistics.median(starts) / 1e3,
                            "worst": worst(starts) / 1e3},
        "end_offset_us": {"median": statistics.median(ends) / 1e3,
                          "worst": worst(ends) / 1e3},
        "phase_annotations": sorted(
            n for n in named if n.split(".")[0] in PHASES and n.count(".") <= 1
        ),
    }


def all_idle_gaps(data, marks: dict, spans: list[dict]) -> dict[str, float]:
    """``perfbench.xplane``'s attribution of the device's idle gaps to the
    open program spans, every entry of it: the result line keeps ten."""
    from collections import defaultdict

    from perfbench import xplane

    out: dict[str, float] = defaultdict(float)
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        busy = xplane.merge([
            (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
            for line in plane.lines if line.name == xplane.OPS_LINE
            for ev in line.events
        ])
        if not busy:
            continue
        shift = marks["start_wall_ns"]
        hi = max(marks["stop_wall_ns"], busy[-1][1] + shift)
        for gap in xplane.gaps([(s + shift, e + shift) for s, e in busy], shift, hi):
            if gap[1] - gap[0] >= xplane.SMALL_GAP_NS:
                for name, ns in xplane.attribute(gap, spans).items():
                    out[name] += ns / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seed", default="0")
    parser.add_argument("--seconds", default=None)
    parser.add_argument("--clock", action="store_true")
    parser.add_argument("--reduce", nargs=2, metavar=("PROFILE_DIR", "SPAN_DIR"))
    args = parser.parse_args(argv)
    if args.reduce:
        print(json.dumps(clock_offsets(Path(args.reduce[0]), Path(args.reduce[1]))))
        return 0
    if not args.workload:
        parser.error("--workload is required")

    from perfbench import cluster, manifest, run

    listed = manifest.load_manifest

    def with_pending(root: Path = ROOT) -> dict:
        m = listed(root)
        pending = json.loads((root / "perfbench" / "pending_per_layer.json").read_text())
        have = {e["name"] for e in m["per_layer"]}
        m["per_layer"] += [e for e in pending["per_layer"] if e["name"] not in have]
        return m

    manifest.load_manifest = with_pending
    if args.clock:
        reduce_profile = cluster.reduce_profile

        def check_then_reduce(root: Path, out_dir: Path, env: dict):
            r = subprocess.run(
                [sys.executable, __file__, "--reduce", str(out_dir / "profile"),
                 str(out_dir / "spans")],
                capture_output=True, text=True, timeout=300,
                env=dict(env, JAX_PLATFORMS="cpu"),
            )
            try:
                clock = json.loads(r.stdout.strip().splitlines()[-1])
            except (IndexError, ValueError):
                clock = {"error": r.stderr[-2000:]}
            (out_dir.parent / "clock.json").write_text(json.dumps(clock, indent=1))
            run.note({"phase": "clock", **clock})
            return reduce_profile(root, out_dir, env)

        cluster.reduce_profile = check_then_reduce
    bench_args = ["--workload", args.workload, "--seed", args.seed, "--trace", "1"]
    if args.seconds is not None:
        bench_args += ["--seconds", args.seconds]
    os.chdir(ROOT)
    return run.main(bench_args)


if __name__ == "__main__":
    raise SystemExit(main())
