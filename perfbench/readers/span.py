"""A program span's duration (``HYPHA_TRACE_DIR`` records), over the
measured rounds.

``{"reader": "span", "name": "encode", "node": "w0" (optional), "reduce":
"median", "per_round": "sum" | "max", "absent": 0.0 (optional)}``. Several
spans of one name in one round (a fold per delta, say) are first put together
per round. ``absent`` is for a span the program drops when it is short (the
worker's ``cleanup`` exists only from 10 ms, ``trace.SLOW_CLEANUP_S``): what
the reader says where the node wrote spans in a measured round and none of
this name. Without it, and in a run with no spans, it says nothing.
"""

from __future__ import annotations

from collections import defaultdict

from .log_field import REDUCE


def read(spec: dict, run, cell, values: dict) -> float | None:
    measured = {r["round"] for r in run.measured}
    per_round: dict[int, list[float]] = defaultdict(list)
    node_traced = False  # the node wrote some span in a measured round
    for sp in run.spans:
        if "node" in spec and sp.get("node") != spec["node"]:
            continue
        rnd = (sp.get("attrs") or {}).get("round")
        if rnd not in measured:
            continue
        node_traced = True
        if sp.get("name") == spec["name"]:
            per_round[rnd].append((sp["mono_end_ns"] - sp["mono_start_ns"]) / 1e9)
    if not per_round:
        return spec.get("absent") if node_traced else None
    fold = REDUCE[spec.get("per_round", "sum")]
    return REDUCE[spec.get("reduce", "median")]([fold(v) for v in per_round.values()])
