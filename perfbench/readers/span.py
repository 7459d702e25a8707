"""A program span's duration (``HYPHA_TRACE_DIR`` records), over the
measured rounds.

``{"reader": "span", "name": "encode", "node": "w0" (optional), "reduce":
"median", "per_round": "sum" | "max"}``. Several spans of one name in one
round (a fold per delta, say) are first put together per round.
"""

from __future__ import annotations

from collections import defaultdict

from .log_field import REDUCE


def read(spec: dict, run, cell, values: dict) -> float | None:
    measured = {r["round"] for r in run.measured}
    per_round: dict[int, list[float]] = defaultdict(list)
    for sp in run.spans:
        if sp.get("name") != spec["name"]:
            continue
        if "node" in spec and sp.get("node") != spec["node"]:
            continue
        rnd = (sp.get("attrs") or {}).get("round")
        if rnd in measured:
            per_round[rnd].append((sp["mono_end_ns"] - sp["mono_start_ns"]) / 1e9)
    if not per_round:
        return None
    fold = REDUCE[spec.get("per_round", "sum")]
    return REDUCE[spec.get("reduce", "median")]([fold(v) for v in per_round.values()])
