"""A number a program span carries among its attributes (``HYPHA_TRACE_DIR``
records), over the measured rounds.

``{"reader": "span_attr", "node": "w0" (optional), "name": "receive" |
"names": ["encode", "merge"], "attr": "read_s" | "attrs": ["cpu_user_s",
"cpu_sys_s"], "per_round": "sum" | "max", "reduce": "median" | "max",
"scale": 1.0, "absent": 0.0 (optional)}``. The rounds are the ones
``span.py`` takes. A span's value is the sum of the named attributes it has
as numbers; a span of the name that has none of them is passed over (a
program from before the attribute existed). Several spans of one round are
put together per round first, then the rounds reduced, then scaled.
``absent`` is what the reader says where the node wrote spans in a measured
round and none of them carried the number (an instant record that exists
only when something went wrong: ``loop_stall``). Without it, and in a run
with no spans, it says nothing.
"""

from __future__ import annotations

from collections import defaultdict

from .log_field import REDUCE


def read(spec: dict, run, cell, values: dict) -> float | None:
    names = set(spec["names"]) if "names" in spec else {spec["name"]}
    attrs = spec["attrs"] if "attrs" in spec else [spec["attr"]]
    measured = {r["round"] for r in run.measured}
    per_round: dict[int, list[float]] = defaultdict(list)
    node_traced = False  # the node wrote some span in a measured round
    for sp in getattr(run, "spans", None) or ():  # a run that kept no spans reads nothing
        if "node" in spec and sp.get("node") != spec["node"]:
            continue
        carried = sp.get("attrs") or {}
        rnd = carried.get("round")
        if rnd not in measured:
            continue
        node_traced = True
        if sp.get("name") not in names:
            continue
        found = [carried[a] for a in attrs
                 if isinstance(carried.get(a), (int, float)) and not isinstance(carried[a], bool)]
        if found:
            per_round[rnd].append(sum(found))
    if not per_round:
        return spec.get("absent") if node_traced else None
    fold = REDUCE[spec.get("per_round", "sum")]
    reduced = REDUCE[spec.get("reduce", "median")]([fold(v) for v in per_round.values()])
    return reduced * spec.get("scale", 1.0)
