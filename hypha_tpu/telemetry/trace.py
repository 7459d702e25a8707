"""Cross-peer round tracing: the Dapper-style span plane for outer rounds.

A DiLoCo outer round is a distributed request — scheduler opens the round,
workers run ``inner_steps`` → ``encode`` → ``upload``, the parameter server
runs ``fold`` / ``quorum_wait`` / ``outer_step`` / ``broadcast``, workers
``merge`` — and this module is the propagation fabric that lets every node
file its spans under ONE trace per round:

  * the scheduler's per-round root span context travels as a
    ``<trace_id>-<parent_span_id>`` string (:data:`~hypha_tpu.messages.
    TRACEPARENT_KEY`) inside SCHEDULE_UPDATE responses, fabric push
    headers, and the round-tagged protocol messages — all None/absent by
    default, so tracing OFF ships today's exact wire bytes;
  * every node appends finished spans to ``spans-<node>.jsonl`` under the
    shared trace directory (one JSON object per line, wall + monotonic
    timestamps, the round/fragment/shard/peer/codec attribute vocabulary);
  * ``python -m hypha_tpu.telemetry.timeline <dir>`` merges the files,
    realigns per-node clocks on round anchors, and prints the per-round
    critical path.

The recorder is deliberately NOT the OTLP tracer in ``telemetry/__init__``:
that one is contextvar-scoped to ``with`` blocks on one thread, while round
spans here begin on one call path and finish on another (a collect loop, a
flight thread) and must serialize to per-node files for offline merge.
Records are file-backed so a crashed node's spans survive for forensics —
the complement of the flight recorder's in-memory ring.

Process-global switch: :func:`enable`, which every CLI role calls from its
``telemetry.trace_dir`` config key (``--set telemetry.trace_dir=…``, the
TOML key, or ``HYPHA_TELEMETRY__TRACE_DIR``); a train executor run as a
child process is handed the directory on its command line. Disabled, every
helper is a cheap no-op returning ``None`` — instrumentation sites never
branch on config themselves.

:class:`phase` is the one timer of a phase inside a larger span: the same
pair of clock readings feeds the role's log line (tracing on or off) and,
when tracing is on, the child span.

Three records say what a span's seconds cannot (all of them only while
tracing is on; off, none of their sites reads a clock or a counter):

  * a span opened with ``usage=True`` carries what its interval cost the
    *process*, all threads: ``cpu_user_s``, ``cpu_sys_s`` and ``minflt`` as
    differences of ``getrusage(RUSAGE_SELF)`` between its opening and the
    moment its end is marked, and ``maxrss_kb`` as read at the end. Where a
    role's phases follow one another the process's difference is the
    phase's; two such spans of one process that overlap each carry the sum;
  * :func:`watch_loop` is the task a node runs on its event loop: it writes
    the instant record ``loop_stall`` where the loop woke it late;
  * :func:`instant` writes a record whose start is its end, stamped with
    the ``round`` of the last span written for its node, so that a reader
    of the measured rounds finds it and no reader of intervals can hand it
    idle time.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import resource
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, IO

from ..messages import TRACEPARENT_KEY
from . import _rand_id
from .flight import _SAFE_NODE

__all__ = [
    "TRACEPARENT_KEY",
    "TraceSpan",
    "NodeTracing",
    "parse_traceparent",
    "enable",
    "disable",
    "active",
    "begin",
    "finish",
    "span",
    "inject",
    "traceparent_of",
    "reparent",
    "phase",
    "instant",
    "watch_loop",
    "SLOW_CLEANUP_S",
    "LOOP_WATCH_S",
]

# One id generator for the whole telemetry package: os.urandom, NOT the
# global random module — deterministic chaos runs seed the global RNG,
# and seeded ids would collide across nodes in one merged timeline.
_rand_hex = _rand_id


def parse_traceparent(value: Any) -> tuple[str, str] | None:
    """``"<32-hex trace id>-<16-hex span id>"`` → the pair, else None.

    Malformed values (wrong length, non-hex, non-string — e.g. a peer
    running a different build) are treated as absent, never an error: a
    bad trace context must not break the data plane.
    """
    if not isinstance(value, str):
        return None
    trace_id, sep, span_id = value.partition("-")
    if not sep or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    return trace_id, span_id


@dataclass(slots=True)
class TraceSpan:
    """One round-trace span; finished spans serialize to the node file."""

    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    node: str
    start_ns: int  # wall clock (time.time_ns)
    start_mono_ns: int  # monotonic (per-node skew-free durations)
    attributes: dict[str, Any] = field(default_factory=dict)
    end_ns: int | None = None
    end_mono_ns: int | None = None
    status_ok: bool = True
    # getrusage at the opening of a ``usage=True`` span, until its end is
    # marked (never serialized).
    usage0: Any = None

    @property
    def traceparent(self) -> str:
        return f"{self.trace_id}-{self.span_id}"

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def to_record(self) -> dict:
        return {
            "node": self.node,
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns if self.end_ns is not None else self.start_ns,
            "mono_start_ns": self.start_mono_ns,
            "mono_end_ns": (
                self.end_mono_ns
                if self.end_mono_ns is not None
                else self.start_mono_ns
            ),
            "ok": self.status_ok,
            "attrs": self.attributes,
        }


class NodeTracing:
    """Span recorder for one trace directory.

    Thread-safe: spans begin/finish from the event loop, training threads
    and stream flight threads alike. Each span is written as one line at
    finish time with an immediate flush, so a crash loses at most the
    spans still open — and a torn final line, which the timeline merger
    tolerates as clean EOF (the durable journal's torn-tail rule).

    ``node`` is the default identity stamped on spans; per-span overrides
    exist because the in-process bench harness runs every role in one
    process and each component labels its own spans (scheduler / psw / w0…).
    """

    def __init__(self, trace_dir: str | Path, node: str = "node") -> None:
        self.trace_dir = Path(trace_dir)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        self.node = str(node)
        self._lock = threading.Lock()
        self._files: dict[str, IO[str]] = {}
        # node -> the ``round`` of the last span written for it
        self._last_round: dict[str, Any] = {}
        self._closed = False

    # ------------------------------------------------------------- spans
    def begin(
        self,
        name: str,
        parent: "TraceSpan | str | None" = None,
        attrs: dict | None = None,
        node: str | None = None,
        usage: bool = False,
    ) -> TraceSpan:
        """Open a span. ``parent`` is a local span, a wire traceparent
        string, or None (starts a fresh trace). ``usage``: the span carries
        what its interval cost the process (module docstring)."""
        if isinstance(parent, TraceSpan):
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            parsed = parse_traceparent(parent)
            if parsed is not None:
                trace_id, parent_id = parsed
            else:
                trace_id, parent_id = _rand_hex(16), None
        return TraceSpan(
            name=name,
            trace_id=trace_id,
            span_id=_rand_hex(8),
            parent_id=parent_id,
            node=str(node) if node else self.node,
            start_ns=time.time_ns(),
            start_mono_ns=time.monotonic_ns(),
            attributes=dict(attrs or {}),
            usage0=resource.getrusage(resource.RUSAGE_SELF) if usage else None,
        )

    def finish(self, span: TraceSpan, ok: bool = True) -> TraceSpan:
        if span.end_mono_ns is None:  # else a deferred phase fixed it
            _mark_end(span)
        span.status_ok = span.status_ok and ok
        self._write(span)
        return span

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        parent: "TraceSpan | str | None" = None,
        attrs: dict | None = None,
        node: str | None = None,
        usage: bool = False,
    ):
        s = self.begin(name, parent=parent, attrs=attrs, node=node, usage=usage)
        try:
            yield s
        except BaseException:
            s.status_ok = False
            raise
        finally:
            self.finish(s)

    def instant(
        self,
        name: str,
        parent: "TraceSpan | str | None" = None,
        attrs: dict | None = None,
        node: str | None = None,
    ) -> TraceSpan:
        """Write a record of one moment: its end is its start. One that
        names no ``round`` gets the round of the last span written for its
        node, where there was one."""
        s = self.begin(name, parent=parent, attrs=attrs, node=node)
        s.end_ns, s.end_mono_ns = s.start_ns, s.start_mono_ns
        if "round" not in s.attributes:
            with self._lock:
                rnd = self._last_round.get(s.node)
            if rnd is not None:
                s.attributes["round"] = rnd
        self._write(s)
        return s

    # --------------------------------------------------------------- io
    def _write(self, span: TraceSpan) -> None:
        line = json.dumps(span.to_record(), default=str) + "\n"
        with self._lock:
            if self._closed:
                return
            f = self._files.get(span.node)
            if f is None:
                safe = _SAFE_NODE.sub("-", span.node) or "node"
                path = self.trace_dir / f"spans-{safe}.jsonl"
                f = open(path, "a", encoding="utf-8")
                self._files[span.node] = f
            rnd = span.attributes.get("round")
            if rnd is not None:
                self._last_round[span.node] = rnd
            f.write(line)
            f.flush()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            for f in self._files.values():
                try:
                    f.close()
                except OSError:
                    pass
            self._files.clear()


# ---------------------------------------------------------------------------
# Process-global switch
# ---------------------------------------------------------------------------

# A sync's file clean-up gets a span only when it takes this long: unlinking
# parameter-sized files can, on some file layers (worker and PS share it).
SLOW_CLEANUP_S = 0.010

_ACTIVE: NodeTracing | None = None
_STATE_LOCK = threading.Lock()


def enable(trace_dir: str | Path, node: str = "node") -> NodeTracing:
    """Turn tracing on for this process, writing under ``trace_dir``."""
    global _ACTIVE
    with _STATE_LOCK:
        if _ACTIVE is not None:
            _ACTIVE.close()
        _ACTIVE = NodeTracing(trace_dir, node)
        return _ACTIVE


def disable() -> None:
    global _ACTIVE
    with _STATE_LOCK:
        if _ACTIVE is not None:
            _ACTIVE.close()
        _ACTIVE = None


def active() -> NodeTracing | None:
    """The process recorder, or None when tracing is off (the default).

    One read of a module global: this runs at every instrumentation site.
    """
    return _ACTIVE


# ------------------------------------------------------------ no-op helpers


def begin(
    name: str,
    parent: "TraceSpan | str | None" = None,
    attrs: dict | None = None,
    node: str | None = None,
    usage: bool = False,
) -> TraceSpan | None:
    """Open a span iff tracing is on; None otherwise (pass to finish)."""
    t = active()
    if t is None:
        return None
    return t.begin(name, parent=parent, attrs=attrs, node=node, usage=usage)


def finish(span: "TraceSpan | None", ok: bool = True) -> None:
    if span is None:
        return
    t = active()
    if t is not None:
        t.finish(span, ok=ok)


@contextlib.contextmanager
def span(
    name: str,
    parent: "TraceSpan | str | None" = None,
    attrs: dict | None = None,
    node: str | None = None,
    usage: bool = False,
):
    """Context-managed span; yields None (and records nothing) when off."""
    t = active()
    if t is None:
        yield None
        return
    with t.span(name, parent=parent, attrs=attrs, node=node, usage=usage) as s:
        yield s


def instant(
    name: str,
    parent: "TraceSpan | str | None" = None,
    attrs: dict | None = None,
    node: str | None = None,
) -> None:
    """A record of one moment (:meth:`NodeTracing.instant`); nothing when off."""
    t = active()
    if t is not None:
        t.instant(name, parent=parent, attrs=attrs, node=node)


# A node's watch task sleeps this long, and a wake-up this late or later is
# a ``loop_stall``: a twentieth of a second is what a lease's renewal or a
# push's next chunk can wait for without anyone noticing.
LOOP_WATCH_S = 0.050


async def watch_loop(node: str) -> None:
    """Say when this event loop was held: sleep :data:`LOOP_WATCH_S`, and
    where the loop hands control back that long or more after it was due,
    write the instant record ``loop_stall`` with ``lag_s`` and
    ``due_mono_ns`` (the loop stood still from then to the record's own
    time). A node starts it only while tracing is on; it ends by
    cancellation."""
    while True:
        due = time.monotonic_ns() + int(LOOP_WATCH_S * 1e9)
        await asyncio.sleep(LOOP_WATCH_S)
        lag_s = (time.monotonic_ns() - due) / 1e9
        if lag_s >= LOOP_WATCH_S:
            instant(
                "loop_stall", attrs={"lag_s": lag_s, "due_mono_ns": due},
                node=node,
            )


def inject(header: dict, context: "TraceSpan | str | None") -> dict:
    """Stamp a trace context into a push/broadcast header, in place.

    ``context`` None (tracing off, or no round context yet) leaves the
    header untouched — no new key, today's exact wire bytes.
    """
    if context is None:
        return header
    header[TRACEPARENT_KEY] = (
        context.traceparent if isinstance(context, TraceSpan) else str(context)
    )
    return header


def traceparent_of(span: "TraceSpan | None") -> str | None:
    return span.traceparent if span is not None else None


def reparent(span: "TraceSpan | None", context: "TraceSpan | str | None") -> None:
    """Late-bind an UNFINISHED, still-parentless span into a trace.

    The parameter server's quorum_wait span opens before any push of the
    round has arrived; the first delta's header then names the round's
    trace. Spans serialize at finish, so rewriting the ids before that is
    safe. A span that already has a parent keeps it.
    """
    if span is None or span.parent_id is not None:
        return
    parsed = parse_traceparent(
        context.traceparent if isinstance(context, TraceSpan) else context
    )
    if parsed is not None:
        span.trace_id, span.parent_id = parsed


def _mark_end(span: "TraceSpan | None") -> None:
    """Fix a span's end at now, without writing it. For a record whose
    attributes are complete only after its interval (the worker's ``step``:
    the status round trip follows the loss fetch); :func:`finish` then
    writes the line with this end."""
    if span is not None:
        span.end_ns = time.time_ns()
        span.end_mono_ns = time.monotonic_ns()
        if span.usage0 is not None:
            u0, u1 = span.usage0, resource.getrusage(resource.RUSAGE_SELF)
            span.usage0 = None
            span.attributes.update(
                cpu_user_s=round(u1.ru_utime - u0.ru_utime, 6),
                cpu_sys_s=round(u1.ru_stime - u0.ru_stime, 6),
                minflt=u1.ru_minflt - u0.ru_minflt,
                maxrss_kb=u1.ru_maxrss,
            )


class phase:
    """Time one phase of a larger span, once, for both of its readers.

    ``with phase("outer_step.mean", parent=outer, into=times, key="mean_s")``
    adds the block's seconds to ``times["mean_s"]`` whether tracing is on or
    off — the roles' log lines are written from that dict — and, when
    tracing is on, records the same interval as a span: the seconds ARE the
    span's monotonic end minus start, not a second clock beside it.

    A ``parent`` that is a local span also lends the child its node and its
    ``round``. ``min_s`` drops the span (never the seconds) of a phase that
    took less. ``annotation`` is a context manager entered around the block:
    the process that holds the chip passes ``jax.profiler.TraceAnnotation``,
    which puts the phase into an open profiler session's trace on the
    device's time base and is a flag check when none is open. ``defer``
    leaves the ended span unwritten until :meth:`write`, for attributes
    that are known only later. ``usage`` is :func:`begin`'s.
    """

    __slots__ = (
        "name", "parent", "attrs", "node", "into", "key", "min_s",
        "annotation", "defer", "usage", "span", "seconds", "_t0",
    )

    def __init__(
        self,
        name: str,
        *,
        parent: "TraceSpan | str | None" = None,
        attrs: dict | None = None,
        node: str | None = None,
        into: dict | None = None,
        key: str | None = None,
        min_s: float = 0.0,
        annotation: Any = None,
        defer: bool = False,
        usage: bool = False,
    ) -> None:
        if isinstance(parent, TraceSpan):
            node = node or parent.node
            if "round" in parent.attributes:
                attrs = {"round": parent.attributes["round"], **(attrs or {})}
        self.name, self.parent, self.attrs, self.node = name, parent, attrs, node
        self.into, self.key, self.min_s = into, key or name, min_s
        self.annotation, self.defer, self.usage = annotation, defer, usage
        self.span: TraceSpan | None = None
        self.seconds = 0.0

    def __enter__(self) -> "phase":
        if self.annotation is not None:
            self.annotation.__enter__()
        self.span = begin(
            self.name, parent=self.parent, attrs=self.attrs, node=self.node,
            usage=self.usage,
        )
        self._t0 = (
            self.span.start_mono_ns if self.span is not None
            else time.monotonic_ns()
        )
        return self

    def set(self, key: str, value: Any) -> None:
        """A span attribute known only once the work is done (bytes read)."""
        if self.span is not None:
            self.span.attributes[key] = value

    def __exit__(self, exc_type, exc, tb) -> bool:
        span = self.span
        _mark_end(span)
        end = span.end_mono_ns if span is not None else time.monotonic_ns()
        self.seconds = (end - self._t0) / 1e9
        if self.into is not None:
            self.into[self.key] = self.into.get(self.key, 0.0) + self.seconds
        if span is not None:
            span.status_ok = exc_type is None
            if not self.defer:
                self.write()
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        return False

    def write(self) -> None:
        if self.span is not None and self.seconds >= self.min_s:
            finish(self.span)
        self.span = None
