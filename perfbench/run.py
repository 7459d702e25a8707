"""Run one cell once.

``python3 perfbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout, on the machine that holds the
chip. The last line of stdout is the contract's JSON object; everything else
is on earlier lines, on stderr, or under ``chiprun_out/perfbench/``. With no
TPU behind worker ``w0`` (a CPU rehearsal) the run still completes, says what
it found on stderr, prints no result and exits 3. Without a memory-backed
``/dev/shm`` with room for the roles' files it does not start: one ground for
every run (PERF.md 2), no result, exit 3.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import cluster, manifest, measure, readers  # noqa: E402

EXIT_FAILED_RUN = 1
EXIT_USAGE = 2
EXIT_NO_ACCELERATOR = 3
EXIT_NO_GROUND = 3  # as off the TPU: this machine cannot give the cell's numbers


def note(obj: dict) -> None:
    """An earlier line of stdout: for people and for PERF.md, not the driver."""
    print(json.dumps(obj), flush=True)


def main(argv: list[str] | None = None) -> int:
    t_start, t_wall = time.monotonic(), time.time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seed < 0:
        parser.error("--seed must not be negative")
    if not (ROOT / "hypha_tpu" / "cli.py").is_file():
        print(f"perfbench: no hypha_tpu package under {ROOT}", file=sys.stderr)
        return EXIT_USAGE
    try:
        cell = manifest.resolve(args.workload, ROOT)
        seconds = args.seconds
        if seconds is None:
            seconds = float(manifest.load_manifest(ROOT)["run_seconds"])
    except manifest.ManifestError as e:
        print(f"perfbench: {e.args[0]}", file=sys.stderr)
        return EXIT_USAGE

    short = cluster.no_work_ground()
    if short:
        print(f"perfbench: no ground to measure on: {short}; no result", file=sys.stderr)
        return EXIT_NO_GROUND

    def on_signal(signum, _frame):  # unwind through the finallys
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    trace = bool(args.trace)
    note({"phase": "start", "workload": cell.name, "seed": args.seed,
          "seconds": seconds, "trace": trace})
    run = cluster.run_cell(cell, args.seed, seconds, trace, t_start, t_wall, ROOT)
    e2e = measure.end_to_end(run)
    layer = None
    if trace:
        try:
            layer = readers.read_all(cell, run)
        finally:
            cluster.drop_raw_trace(run.out_dir)
    result = measure.result(run, cell, trace, layer)
    for r in run.rounds:
        mine = next((m for m in run.measured if m["round"] == r["round"]), {})
        note({"phase": "round", "measured": bool(mine), "wall_by_harness": mine.get("wall"), **r})
    for o in run.outer:
        note({"phase": "outer_step", **o})
    later = measure.later_rounds(run)
    note({"phase": "checks", **run.checks, "margins": run.margins, "later_rounds": later})
    if run.reference is not None:
        note({"phase": "reference", **run.reference})
    # In a traced run this is what tracing cost: set it beside the plain run's.
    note({"phase": "end_to_end", "trace": trace, "cause": run.cause,
          "cluster_starts": run.attempts, "work_fs": "shm",
          "libtpu_mapped_by": run.holders, "attention": run.attention,
          "logs": str(run.out_dir), **e2e})
    if trace and run.profile:
        note({"phase": "profile", **{k: v for k, v in run.profile.items() if k != "breakdown"}})
    if not result["correct"]:
        # By name on stderr too, whose tail is what a driver's record keeps.
        broken = [k for k, ok in run.checks.items() if not ok]
        print(f"perfbench: incorrect: checks failed: {broken}; cause: {run.cause}; "
              f"rounds closed: {sorted(run.arrivals)}; measured: {len(run.measured)}",
              file=sys.stderr)
    if later:
        print(f"perfbench: not compared: first loss of the measured rounds {later['first_loss']}: "
              f"the largest {later['largest']} in round {later['largest_in_round']}",
              file=sys.stderr)
    # Each number compared beside its limit: the last lines of stderr.
    for name, held in run.margins.items():
        print(f"perfbench: compared: {name}: {json.dumps(held)} -> "
              f"{'inside' if measure.inside(held) else 'OUTSIDE'}", file=sys.stderr)
    device = run.device or {}
    if device.get("platform") != "tpu" or device.get("count", 0) < cell.chips:
        print(
            f"perfbench: worker w0 reported {device or 'no device'}, the cell "
            f"needs {cell.chips} TPU chip(s); no result. What a rehearsal "
            f"would have printed:\nrehearsal: {json.dumps(result)}",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    print(json.dumps(result), flush=True)
    return 0 if run.measured else EXIT_FAILED_RUN


if __name__ == "__main__":
    raise SystemExit(main())
