"""The profiler's trace of one round, reduced to what the result line carries.

``python -m perfbench.xplane <profile dir> <spans dir>`` prints one JSON
object: ``busy_s`` (the union of the intervals in which an operation ran on
the device, averaged over the device planes found), ``window_s`` (from the
moment the trace opened to the moment it was closed, by the worker's wall
clock; the device's event times count from the opening), and ``breakdown``: the device operations that took most time, and
the idle gaps summed by the program span the host was in (the program's own
``HYPHA_TRACE_DIR`` spans, which carry wall-clock times). It runs in a
process of its own because reading the file needs jax; it never touches a
device.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

from . import logs

OPS_LINE = "XLA Ops"
SMALL_GAP_NS = 1_000_000


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap: tuple[int, int], spans: list[dict]) -> dict[str, int]:
    """Split one idle gap among the program spans that cover it, the
    shortest span first, so that the finest span that was open gets each
    piece (``ps:outer_step`` before the ``ps:quorum_wait`` and the
    ``scheduler:round`` around it). What no span covers is ``no span``."""
    left = [gap]
    out: dict[str, int] = defaultdict(int)
    for sp in sorted(spans, key=lambda sp: sp["end_ns"] - sp["start_ns"]):
        if sp["end_ns"] <= gap[0] or sp["start_ns"] >= gap[1]:
            continue
        rest = []
        for s, e in left:
            lo, hi = max(s, sp["start_ns"]), min(e, sp["end_ns"])
            if hi <= lo:
                rest.append((s, e))
                continue
            out[f"{sp['node']}:{sp['name']}"] += hi - lo
            rest += [(a, b) for a, b in ((s, lo), (hi, e)) if b > a]
        left = rest
    out["no span"] += sum(e - s for s, e in left)
    return {k: v for k, v in out.items() if v > 0}


def op_name(text: str) -> str:
    """An event's name is the whole HLO instruction; keep what groups it:
    the Pallas kernels together, any other by its name without the number."""
    if "tpu_custom_call" in text:
        return "tpu_custom_call (Pallas kernels)"
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() else head


def reduce(profile_dir: Path, spans: list[dict]) -> dict:
    from jax.profiler import ProfileData

    marks = json.loads((profile_dir / "marks.json").read_text())
    files = sorted(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return {"error": "no xplane file", "marks": marks}
    data = ProfileData.from_file(str(files[-1]))
    seen = {p.name: [ln.name for ln in p.lines] for p in data.planes}
    planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    window_ns = marks["stop_wall_ns"] - marks["start_wall_ns"]
    busy_ns, op_ns, gap_ns = [], defaultdict(int), defaultdict(int)
    for plane in planes:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                intervals.append((s, s + d))
                op_ns[op_name(ev.name)] += d
        if not intervals:
            continue
        busy = merge(intervals)
        busy_ns.append(sum(e - s for s, e in busy))
        # Device times count from the trace's opening; the spans are wall clock.
        shift = marks["start_wall_ns"]
        lo, hi = shift, max(marks["stop_wall_ns"], busy[-1][1] + shift)
        small = 0
        for g in gaps([(s + shift, e + shift) for s, e in busy], lo, hi):
            if g[1] - g[0] < SMALL_GAP_NS:
                small += g[1] - g[0]
            else:
                for name, ns in attribute(g, spans).items():
                    gap_ns[name] += ns
        gap_ns["between operations, under 1 ms each"] += small
    if not busy_ns:
        return {"error": "no device events", "planes": seen, "marks": marks}
    n = len(busy_ns)
    top = lambda d: [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": window_ns / 1e9,
        "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(gap_ns)},
        "planes": seen,
        "device_planes": n,
    }


def main(argv: list[str]) -> int:
    print(json.dumps(reduce(Path(argv[0]), logs.read_spans(Path(argv[1])))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
