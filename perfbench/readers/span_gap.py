"""Seconds between two program spans of one round: from the end of one to the
start of the next.

``{"reader": "span_gap", "node": "w0", "from": "upload", "to": "merge",
"reduce": "median"}``: per measured round, the start of the round's first
``to`` span minus the end of its last ``from`` span before that. What a node
waited between two of its own phases (the worker between its upload's end
and its merge's start: transport and parameter server together) needs no
span of its own, so a program from before that wait had one reports it too.
"""

from __future__ import annotations

from .log_field import REDUCE


def read(spec: dict, run, cell, values: dict) -> float | None:
    measured = {r["round"] for r in run.measured}
    ends: dict[int, list[int]] = {}
    starts: dict[int, list[int]] = {}
    for sp in run.spans:
        if "node" in spec and sp.get("node") != spec["node"]:
            continue
        rnd = (sp.get("attrs") or {}).get("round")
        if rnd not in measured:
            continue
        if sp.get("name") == spec["from"]:
            ends.setdefault(rnd, []).append(sp["mono_end_ns"])
        elif sp.get("name") == spec["to"]:
            starts.setdefault(rnd, []).append(sp["mono_start_ns"])
    gaps = []
    for rnd in sorted(set(ends) & set(starts)):
        start = min(starts[rnd])
        before = [e for e in ends[rnd] if e <= start]
        if before:
            gaps.append((start - max(before)) / 1e9)
    if not gaps:
        return None
    return REDUCE[spec.get("reduce", "median")](gaps)
