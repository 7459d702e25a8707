"""A field of a role's log lines.

``{"reader": "log_field", "role": "w0", "line": "round_done" | "outer_step" |
<regex>, "field": "median_step_s", "rounds": "measured" | "round0" | "all",
"reduce": "median" | "first" | "max" | "sum", "scale": 1000}``. The two
named lines are the ones the harness has already parsed; any other ``line``
is a regular expression over the role's log, its ``key=value`` pairs typed.
"""

from __future__ import annotations

import re
import statistics

from .. import logs

REDUCE = {
    "median": statistics.median, "first": lambda v: v[0], "last": lambda v: v[-1],
    "max": max, "min": min, "sum": sum,
}


def rows(spec: dict, run) -> list[dict]:
    which = spec.get("rounds", "measured")
    measured = {r["round"] for r in run.measured}
    if spec["line"] == "round_done":
        pool = run.rounds
    elif spec["line"] == "outer_step":
        pool = run.outer
    else:
        text = run.texts.get(spec["role"], "")
        return [logs.parse_fields(m.group(0)) for m in re.finditer(spec["line"], text)]
    if which == "measured":
        return [r for r in pool if r["round"] in measured]
    if which == "round0":
        return [r for r in pool if r["round"] == 0]
    return list(pool)


def read(spec: dict, run, cell, values: dict) -> float | None:
    vals = [r[spec["field"]] for r in rows(spec, run)
            if isinstance(r.get(spec["field"]), (int, float))]
    if not vals:
        return None
    return REDUCE[spec.get("reduce", "median")](vals) * spec.get("scale", 1)
