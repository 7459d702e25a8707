"""Device time of the events under a ``jax.named_scope``, per inner step,
from the profiler's own trace of the traced round.

``{"reader": "device_scope", "scopes": ["moe_dispatch", "moe_combine"],
"names": ["ragged-dot"], "scale": 1000}``: an event of the device's ``XLA
Ops`` line counts if a component of its operation name (``tf_op``: the
``named_scope`` path that jax gave the instruction) is one of ``scopes``, or
its instruction's own name starts with one of ``names`` (a Pallas kernel is
named after the scope it was called under; a compiler-made call such as the
grouped product's ``ragged-dot`` keeps no scope). The matched intervals are
merged, so a ``while`` and the operations inside it count once, and the sum
is divided by the cell's steps a round: seconds a step, times ``scale``.

It reads ``profile/plugins/profile/*/*.trace.json.gz`` under the run's output
directory and returns ``None`` where that is not there. The harness keeps the
raw trace until every reader has run and removes it then (``run.py``).
"""

from __future__ import annotations

import functools
import gzip
import json
from pathlib import Path

from ..xplane import OPS_LINE, merge


def device_events(trace: dict) -> list[dict]:
    """The complete events of the first TPU's ``XLA Ops`` line."""
    events = trace["traceEvents"]
    pid = next((e["pid"] for e in events if e.get("ph") == "M" and e.get("name") == "process_name"
                and str(e["args"].get("name", "")).startswith("/device:TPU:")), None)
    tid = next((e["tid"] for e in events if e.get("ph") == "M" and e.get("name") == "thread_name"
                and e.get("pid") == pid and e["args"].get("name") == OPS_LINE), None)
    return [e for e in events if e.get("ph") == "X" and e.get("pid") == pid and e.get("tid") == tid]


def matches(event: dict, scopes: list[str], names: list[str]) -> bool:
    if any(event["name"].startswith(n) for n in names):
        return True
    path = event.get("args", {}).get("tf_op", "").rstrip(":").split("/")
    return any(s in path for s in scopes)


def busy_seconds(events: list[dict], scopes: list[str], names: list[str]) -> float:
    """Merged device time of the matching events (``ts``, ``dur``: microseconds)."""
    spans = [(int(e["ts"] * 1000), int((e["ts"] + e["dur"]) * 1000))
             for e in events if matches(e, scopes, names)]
    return sum(b - a for a, b in merge(spans)) / 1e9


@functools.lru_cache(maxsize=1)  # a round's trace is parsed once, not once a metric
def _parsed(path: Path) -> dict:
    with gzip.open(path) as f:
        return json.load(f)


def load(out_dir: Path) -> dict | None:
    files = sorted(Path(out_dir).glob("profile/plugins/profile/*/*.trace.json.gz"))
    return _parsed(files[-1]) if files else None


def read(spec: dict, run, cell, values: dict) -> float | None:
    trace = load(run.out_dir)
    if trace is None:
        return None
    events = device_events(trace)
    if not events:
        return None
    seconds = busy_seconds(events, spec.get("scopes", []), spec.get("names", []))
    return seconds / cell.traffic["inner_steps"] * spec.get("scale", 1)
