"""Seconds between two moments: a harness event or a role's log line.

``{"reader": "log_interval", "from": {...}, "to": {...}}`` where each end is
``{"event": "scheduler_start"}`` or ``{"role": "w0", "line": <regex>}`` (the
first matching line's own timestamp).
"""

from __future__ import annotations

from .. import logs


def moment(end: dict, run) -> float | None:
    if "event" in end:
        return run.events.get(end["event"])
    line = logs.find_line(run.texts.get(end["role"], ""), end["line"])
    return logs.line_time(line) if line else None


def read(spec: dict, run, cell, values: dict) -> float | None:
    a, b = moment(spec["from"], run), moment(spec["to"], run)
    if a is None or b is None:
        return None
    return b - a
