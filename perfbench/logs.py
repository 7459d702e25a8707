"""What the five roles log, as values. ``parse_fields`` and the patterns are
a copy of ``chip_smoke.py``'s: later PRs may change that script, not this."""

from __future__ import annotations

import json
import re
import time
from pathlib import Path

DEVICE_LINE = r"device: platform=(\S+) kind='([^']*)' count=(\d+)"
ROUND_LINE = r"round (\d+) done: .*"
OUTER_LINE = r"ps outer step: .*"
DELTA_LINE = r"round (\d+) (?:fragment \d+ )?delta \d+(?:/\d+)? \(from \S+\)"
# Causes worth a name of their own on stderr, in the order they are looked for.
NAMED_CAUSES = (
    # An offer that was not taken expires too, and says only "lease X
    # expired"; the job's own lease lost is the cancel line.
    ("lease expired", r"cancelling job \S+ \(lease \S+ expired\)"),
    # The scheduler ending on a request about a lease that the other side no
    # longer knew (PERF.md 7, the auction's race): named, not tried again.
    ("a request about a lease was refused", r"RequestError: '[0-9a-f-]{36}'"),
    ("no route to ps", r"no route to ps"),
    ("device out of memory", r"RESOURCE_EXHAUSTED|[Oo]ut of memory"),
)
_STAMP = re.compile(r"^(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) ")


def parse_fields(line: str) -> dict:
    """``key=value`` pairs of one log line, numbers typed."""
    out: dict = {}
    for key, raw in re.findall(r"(\w+)=('[^']*'|\S+)", line):
        raw = raw.strip("'")
        try:
            out[key] = int(raw)
        except ValueError:
            try:
                out[key] = float(raw)
            except ValueError:
                out[key] = {"True": True, "False": False, "None": None}.get(raw, raw)
    return out


def line_time(line: str) -> float | None:
    """Wall-clock seconds of a ``logging`` line (local time, milliseconds)."""
    m = _STAMP.match(line)
    if not m:
        return None
    return time.mktime(time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")) + int(m.group(2)) / 1e3


def find_line(text: str, pattern: str) -> str | None:
    """The first whole line that ``pattern`` matches."""
    rx = re.compile(pattern)
    for line in text.splitlines():
        if rx.search(line):
            return line
    return None


def rounds(w0_text: str) -> list[dict]:
    """Every ``round N done`` line of the worker, in order, with its time."""
    out = []
    for line in w0_text.splitlines():
        m = re.search(ROUND_LINE, line)
        if m:
            out.append({"round": int(m.group(1)), "t": line_time(line), **parse_fields(m.group(0))})
    return out


def outer_steps(ps_text: str) -> list[dict]:
    return [parse_fields(m.group(0)) for m in re.finditer(OUTER_LINE, ps_text)]


def deltas_pushed(ps_text: str) -> dict[int, int]:
    """round -> how many deltas the PS logged for it: ``round N delta i/n
    (from w0)`` in blocking mode, ``round N fragment f delta i (from w0)`` in
    the streaming and overlap modes."""
    out: dict[int, int] = {}
    for m in re.finditer(DELTA_LINE, ps_text):
        out[int(m.group(1))] = out.get(int(m.group(1)), 0) + 1
    return out


def device(w0_text: str) -> dict | None:
    m = re.search(DEVICE_LINE, w0_text)
    if not m:
        return None
    return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}


def named_cause(texts: dict[str, str]) -> str | None:
    for name, pattern in NAMED_CAUSES:
        for role, text in texts.items():
            if re.search(pattern, text):
                return f"{name} (in the {role} log)"
    return None


def read_spans(span_dir: Path) -> list[dict]:
    """The program's round spans, every node's file."""
    out = []
    for path in sorted(span_dir.glob("spans-*.jsonl")):
        for line in path.read_text(errors="replace").splitlines():
            try:
                out.append(json.loads(line))
            except ValueError:
                pass  # a torn last line
    return out
