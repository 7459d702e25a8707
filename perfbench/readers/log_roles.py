"""A field of a log line that several roles write, over all of them.

``{"reader": "log_roles", "roles": ["ps", "w0"], "line": <regex>, "field":
"margin_s", "reduce": "min"}``: ``log_field`` over each role's whole log,
the values put together before they are reduced. What every worker logs
about itself (the margin its lease had at a renewal) and the run wants once.
"""

from __future__ import annotations

from . import log_field


def read(spec: dict, run, cell, values: dict) -> float | None:
    vals = [
        row[spec["field"]]
        for role in spec["roles"]
        for row in log_field.rows({**spec, "role": role}, run)
        if isinstance(row.get(spec["field"]), (int, float))
    ]
    if not vals:
        return None
    return log_field.REDUCE[spec.get("reduce", "min")](vals) * spec.get("scale", 1)
