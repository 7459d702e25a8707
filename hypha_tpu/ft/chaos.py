"""Deterministic fault injection for tests and benchmarks.

Real failures are timing accidents; tests need them on a schedule. A
:class:`ChaosController` watches the same per-round metrics stream the
orchestrator's MetricsBridge sees and fires scripted actions at exact round
boundaries, against the in-process :class:`~hypha_tpu.worker.runtime.
WorkerNode` objects a test's harness (``tests/harness/ft_chaos.py``) holds:

  * ``kill``       — stop the worker node outright (lease renewals start
    failing, its delta never arrives: the canonical DiLoCo dropout);
  * ``delay``      — add ``delay_s`` to every outbound push (a straggler:
    its delta arrives but may miss the round deadline and be dropped as
    stale);
  * ``partition``  — fail every outbound push *and* request from the worker
    (uplink loss: the worker computes but cannot report; the φ detector is
    the only thing that can see this one);
  * ``kill-ps``      — stop the PARAMETER SERVER's worker node mid-round
    (the durable-PS recovery scenario, ft.durable: the harness restarts
    the node and the journal + generation handshake resume the round);
  * ``partition-ps`` — for ``delay_s`` seconds, drop every push between
    the PS and the workers (both directions): workers must park and
    re-push with backoff (aio.retry), and the PS journal must dedup the
    copies whose first attempt actually landed;
  * ``kill-scheduler`` — stop the SCHEDULER's node mid-round (the durable
    control-plane recovery scenario, ft.durable DurableScheduler: the
    harness restarts the scheduler under the same peer id, which replays
    its journal and re-adopts the live executions in place);
  * ``partition-scheduler`` — for ``delay_s`` seconds, fail every request
    and push from the fleet TOWARD the scheduler (uplink loss: workers'
    Status/UpdateReceived and the PS's Updated park in aio.retry; quorate
    rounds keep closing; the scheduler's own renewals still flow, so no
    lease lapses), then heal.

Degrade modes (net-new, ROADMAP item 4 — heterogeneity is a steady state,
not an event, so these default to ``at_round=0`` and fire on attach):

  * ``slow-worker:<x>`` / ``slow-worker:<peer>:<x>`` — a slow-CPU worker:
    every per-batch Status round-trip is stretched so each inner batch
    takes ~``x``× its natural wall-clock (the training thread blocks on
    the Status response between batches, so the slowdown is real to every
    observer: the scheduler's timing stats, the round deadline, the
    worker itself);
  * ``bw-cap:<peer>:<mbps>`` — cap the peer's LINK at ``mbps``: every
    push from the peer (delta uploads) and to the peer (update
    broadcasts) is streamed through a chunk-throttled source, so the
    RECEIVER measures the cap mid-transfer — exactly what the parameter
    server's LinkTable (ft.adaptive) keys its per-link codec choice on;
  * ``jitter:<peer>:<s>`` — add deterministic pseudo-random delay in
    ``[0, s]`` to every push touching the peer (seeded per target, so a
    re-run sees the identical delay sequence).

Specs compose: ``kill-worker:2,bw-cap:w1:10`` runs both
(:func:`parse_chaos_specs`).

Trigger semantics: action ``at_round=r`` fires the first time a METRICS
event for round ``r-1`` is observed — i.e. while round ``r`` is running —
so "kill worker X mid-round r" is reproducible to the batch. ``at_round=0``
fires on attach (before the job's first batch).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from .. import aio

__all__ = [
    "ChaosAction",
    "ChaosController",
    "parse_chaos_spec",
    "parse_chaos_specs",
]

log = logging.getLogger("hypha.ft.chaos")

_KINDS = (
    "kill", "delay", "partition", "kill-ps", "partition-ps",
    "kill-scheduler", "partition-scheduler",
    "slow", "bw-cap", "jitter",
)

# Kinds that model a steady condition rather than an event: they attach
# immediately unless the spec pins a round.
_DEGRADE_KINDS = ("slow", "bw-cap", "jitter")

# Throttled-push chunk: small enough that a capped toy-scale delta still
# spreads over several sleeps (the receiver must SEE the cap mid-stream).
_THROTTLE_CHUNK = 16 * 1024


@dataclass(slots=True)
class ChaosAction:
    kind: str  # one of _KINDS
    target: str  # worker peer id
    at_round: int = 1
    delay_s: float = 0.0  # kind == "delay" | "partition-ps" | "jitter"
    factor: float = 1.0  # kind == "slow": per-batch wall-clock multiplier
    rate_bps: float = 0.0  # kind == "bw-cap": link cap in BITS/second
    fired_at: float | None = None  # monotonic time the action ran

    def __post_init__(self) -> None:
        if self.kind not in _KINDS:
            raise ValueError(f"unknown chaos kind {self.kind!r}")
        if self.at_round < 0:
            raise ValueError("at_round must be >= 0")
        if self.kind == "slow" and self.factor < 1.0:
            raise ValueError("slow-worker factor must be >= 1.0")
        if self.kind == "bw-cap" and self.rate_bps <= 0:
            raise ValueError("bw-cap rate must be positive")


def _is_number(token: str) -> bool:
    try:
        float(token)
    except ValueError:
        return False
    return True


def parse_chaos_spec(spec: str, target: str) -> ChaosAction:
    """Parse ONE CLI chaos spec into an action.

    ``target`` is the harness's default victim; specs that name a peer
    inline (``bw-cap:w1:10``, ``slow-worker:w2:4``) override it. Numeric
    second fields keep their historical meaning (round for the event
    kinds, factor/rate for the degrade kinds).
    """
    parts = spec.split(":")
    head = parts[0]
    if head in ("kill-worker", "kill"):
        kind = "kill"
    elif head in ("delay-worker", "delay"):
        kind = "delay"
    elif head in ("partition-worker", "partition"):
        kind = "partition"
    elif head in (
        "kill-ps", "partition-ps", "kill-scheduler", "partition-scheduler"
    ):
        kind = head
    elif head in ("slow-worker", "slow"):
        kind = "slow"
    elif head == "bw-cap":
        kind = "bw-cap"
    elif head in ("jitter", "jitter-link"):
        kind = "jitter"
    else:
        raise ValueError(f"unknown chaos spec {spec!r}")
    args = parts[1:]
    if kind in _DEGRADE_KINDS:
        # Optional inline peer first (bw-cap REQUIRES one — a bandwidth cap
        # on "the default victim" is too easy to point at the wrong link).
        if args and not _is_number(args[0]):
            target = args[0]
            args = args[1:]
        elif kind == "bw-cap":
            raise ValueError(f"bw-cap needs a peer: bw-cap:<peer>:<mbps> ({spec!r})")
        if kind == "slow":
            factor = float(args[0]) if args else 4.0
            at_round = int(args[1]) if len(args) > 1 else 0
            return ChaosAction(
                kind=kind, target=target, at_round=at_round, factor=factor
            )
        if kind == "bw-cap":
            if not args:
                raise ValueError(f"bw-cap needs a rate: bw-cap:<peer>:<mbps> ({spec!r})")
            rate_bps = float(args[0]) * 1e6
            at_round = int(args[1]) if len(args) > 1 else 0
            return ChaosAction(
                kind=kind, target=target, at_round=at_round, rate_bps=rate_bps
            )
        delay_s = float(args[0]) if args else 0.25
        at_round = int(args[1]) if len(args) > 1 else 0
        return ChaosAction(
            kind=kind, target=target, at_round=at_round, delay_s=delay_s
        )
    at_round = int(args[0]) if args else 1
    default_delay = 3.0 if kind in ("partition-ps", "partition-scheduler") else 1.0
    delay_s = float(args[1]) if len(args) > 1 else default_delay
    return ChaosAction(kind=kind, target=target, at_round=at_round, delay_s=delay_s)


def parse_chaos_specs(spec: str, target: str) -> list[ChaosAction]:
    """Parse a comma-composed CLI chaos spec (``kill-worker:2,bw-cap:w1:10``)
    into the action list — one scenario can now mix an event with steady
    degrade conditions instead of exactly one action per run."""
    actions = [
        parse_chaos_spec(part.strip(), target)
        for part in spec.split(",")
        if part.strip()
    ]
    if not actions:
        raise ValueError(f"empty chaos spec {spec!r}")
    return actions


class ChaosController:
    """Runs scripted :class:`ChaosAction`s against in-process worker nodes.

    ``workers`` maps peer id → WorkerNode (anything with ``.stop()`` and
    ``.node``). Wire :meth:`metrics_hook` into the orchestrator's metrics
    connector so round completions drive the schedule.
    """

    def __init__(
        self,
        actions: list[ChaosAction],
        workers: dict[str, Any],
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.actions = list(actions)
        self.workers = dict(workers)
        self._clock = clock
        self._tasks: set[asyncio.Task] = set()
        self.fired: list[ChaosAction] = []
        for action in self.actions:
            if action.at_round == 0:
                self._fire(action)

    # ---------------------------------------------------------------- hooks
    def metrics_hook(
        self, inner: Callable[[str, int, dict], None] | None = None
    ) -> Callable[[str, int, dict], None]:
        """A metrics callback for CallbackConnector; chains to ``inner``."""

        def on_metrics(peer: str, round_num: int, metrics: dict) -> None:
            self.on_round_metrics(round_num)
            if inner is not None:
                inner(peer, round_num, metrics)

        return on_metrics

    def on_round_metrics(self, round_num: int) -> None:
        """A worker reported metrics for ``round_num`` (end of that round)."""
        for action in self.actions:
            if action.fired_at is None and action.at_round <= round_num + 1:
                self._fire(action)

    # ---------------------------------------------------------------- firing
    def _fire(self, action: ChaosAction) -> None:
        action.fired_at = self._clock()
        self.fired.append(action)
        # Flight-recorder breadcrumb: a stalled round's forensics must show
        # WHEN the injected fault fired, next to the retries/drops it caused.
        from ..telemetry.flight import FLIGHT

        FLIGHT.record(
            f"chaos.{action.kind}", node=action.target,
            target=action.target, at_round=action.at_round,
            delay_s=action.delay_s, factor=action.factor,
            rate_bps=action.rate_bps,
        )
        worker = self.workers.get(action.target)
        if worker is None:
            log.warning("chaos: no worker %r to %s", action.target, action.kind)
            return
        log.info("chaos: %s %s (round trigger %d)", action.kind, action.target, action.at_round)
        if action.kind in ("kill", "kill-ps", "kill-scheduler"):
            aio.spawn(
                self._kill(worker), tasks=self._tasks, what="chaos kill", logger=log
            )
        elif action.kind == "delay":
            self._wrap_push_delay(worker.node, action.delay_s)
        elif action.kind == "partition":
            self._partition(worker.node)
        elif action.kind == "partition-ps":
            self._partition_ps(action.target, action.delay_s)
        elif action.kind == "partition-scheduler":
            self._partition_scheduler(action.target, action.delay_s)
        elif action.kind == "slow":
            self._wrap_slow_cpu(worker.node, action.factor)
        elif action.kind == "bw-cap":
            self._wrap_bw_cap(action.target, action.rate_bps)
        elif action.kind == "jitter":
            self._wrap_jitter(action.target, action.delay_s)

    @staticmethod
    async def _kill(worker: Any) -> None:
        """Crash semantics: sever the NODE first (instant network death —
        in-flight deltas and heartbeats stop mid-round), then reap the
        worker's local state in the background. A graceful worker.stop()
        alone lets the training thread finish shipping the current round's
        delta, which is a shutdown, not a failure."""
        node_stop = getattr(getattr(worker, "node", None), "stop", None)
        try:
            if callable(node_stop):
                await node_stop()
            await worker.stop()
        except Exception as e:
            # CancelledError propagates: a cancelled kill task must end
            # cancelled, not swallow its own teardown signal.
            log.warning("chaos kill: stop raised %s", e)

    @staticmethod
    def _wrap_push_delay(node: Any, delay_s: float) -> None:
        orig_push = node.push

        async def delayed_push(peer_id: str, resource: Any, source) -> int:
            await asyncio.sleep(delay_s)
            return await orig_push(peer_id, resource, source)

        node.push = delayed_push

    # ------------------------------------------------------- degrade wraps

    @staticmethod
    def _wrap_slow_cpu(node: Any, factor: float) -> None:
        """A slow-CPU worker: stretch every per-batch Status round-trip.

        The training thread synchronously awaits each Status response
        between batches, so sleeping ``(factor - 1) × compute`` in the
        request path makes every inner batch take ~``factor``× its
        natural wall-clock — real to the scheduler's timing statistics,
        the PS round deadline, and the worker alike. The compute estimate
        is the gap since we released the PREVIOUS Status (excluding our
        own injected sleeps, so the slowdown is a stable multiplier
        instead of compounding geometrically)."""
        from ..messages import PROTOCOL_PROGRESS, ProgressKind

        orig_request = node.request
        state = {"last": None}

        async def slow_request(peer_id: str, protocol: str, msg: Any, **kw) -> Any:
            if protocol == PROTOCOL_PROGRESS:
                if getattr(msg, "kind", None) != ProgressKind.STATUS:
                    # Round boundary (update / metrics / update-received):
                    # the gap to the NEXT status is broadcast wait, not
                    # compute — stretching it would model a slow NETWORK
                    # (and make the φ detector see huge one-off stalls),
                    # not a slow CPU. Drop the baseline instead.
                    state["last"] = None
                    return await orig_request(peer_id, protocol, msg, **kw)
                now = time.monotonic()
                last = state["last"]
                if last is not None and now > last:
                    await asyncio.sleep((factor - 1.0) * (now - last))
                result = await orig_request(peer_id, protocol, msg, **kw)
                state["last"] = time.monotonic()
                return result
            return await orig_request(peer_id, protocol, msg, **kw)

        node.request = slow_request

    @staticmethod
    def _throttled_source(source, rate_bps: float):
        """Wrap a push source (bytes | file path | async iterator, as the
        PS's broadcast from memory) in an async iterator that trickles
        chunks at ``rate_bps`` BITS/second — the receiver sees the cap
        DURING the transfer (its save_to measures it), not as an up-front
        delay it cannot attribute to the link."""

        async def gen():
            if hasattr(source, "__aiter__"):
                async for piece in source:
                    for i in range(0, len(piece), _THROTTLE_CHUNK):
                        chunk = piece[i : i + _THROTTLE_CHUNK]
                        await asyncio.sleep(len(chunk) * 8.0 / rate_bps)
                        yield chunk
                return
            if isinstance(source, (bytes, bytearray, memoryview)):
                data = bytes(source)
                for i in range(0, max(len(data), 1), _THROTTLE_CHUNK):
                    chunk = data[i : i + _THROTTLE_CHUNK]
                    await asyncio.sleep(len(chunk) * 8.0 / rate_bps)
                    if chunk:
                        yield chunk
                return
            f = await asyncio.to_thread(open, source, "rb")
            try:
                while True:
                    chunk = await asyncio.to_thread(f.read, _THROTTLE_CHUNK)
                    if not chunk:
                        break
                    await asyncio.sleep(len(chunk) * 8.0 / rate_bps)
                    yield chunk
            finally:
                await asyncio.to_thread(f.close)

        return gen()

    def _maybe_throttled(self, source, rate_bps: float):
        """Throttle byte/file sources; pass anything already streaming
        (an async iterator — e.g. a previously wrapped source) through."""
        if isinstance(source, (bytes, bytearray, memoryview, str, Path)):
            return self._throttled_source(source, rate_bps)
        return source

    def _wrap_bw_cap(self, target: str, rate_bps: float) -> None:
        """Cap every push AND pull payload on the target's LINK (both
        directions): its own uploads (delta pushes) and served pulls
        (a capped DATA NODE's slice streams), plus pushes/pull payloads
        toward it from every other node the controller holds (update
        broadcasts, catch-ups, slices it pulls)."""
        for name, worker in self.workers.items():
            node = getattr(worker, "node", None)
            if node is None:
                continue
            handler = getattr(node, "_pull_handler", None)
            if handler is not None:
                if name == target:

                    async def capped_pull(
                        peer: str, resource: Any, _h=handler
                    ):
                        return self._maybe_throttled(
                            await _h(peer, resource), rate_bps
                        )

                else:

                    async def capped_pull(
                        peer: str, resource: Any, _h=handler
                    ):
                        source = await _h(peer, resource)
                        if peer != target:
                            return source
                        return self._maybe_throttled(source, rate_bps)

                node.on_pull(capped_pull)
            orig_push = node.push

            if name == target:

                async def capped_push(
                    peer_id: str, resource: Any, source, _orig=orig_push
                ) -> int:
                    return await _orig(
                        peer_id, resource,
                        self._throttled_source(source, rate_bps),
                    )

            else:

                async def capped_push(
                    peer_id: str, resource: Any, source, _orig=orig_push
                ) -> int:
                    if peer_id != target:
                        return await _orig(peer_id, resource, source)
                    return await _orig(
                        peer_id, resource,
                        self._throttled_source(source, rate_bps),
                    )

            node.push = capped_push

    def _wrap_jitter(self, target: str, max_delay_s: float) -> None:
        """Deterministic pseudo-random delay in [0, max_delay_s] on every
        push touching the target's link — seeded per target, so a re-run
        sees the identical delay sequence."""
        rng = random.Random(f"hypha-chaos-jitter:{target}:{max_delay_s}")

        for name, worker in self.workers.items():
            node = getattr(worker, "node", None)
            if node is None:
                continue
            orig_push = node.push
            mine = name == target

            async def jittery_push(
                peer_id: str, resource: Any, source,
                _orig=orig_push, _mine=mine,
            ) -> int:
                if _mine or peer_id == target:
                    await asyncio.sleep(rng.uniform(0.0, max_delay_s))
                return await _orig(peer_id, resource, source)

            node.push = jittery_push

    def _partition_ps(self, ps_peer: str, duration_s: float) -> None:
        """Sever the data plane between ``ps_peer`` and every other worker
        for ``duration_s`` seconds, then heal. Workers' pushes toward the
        PS (and the PS's broadcasts out) fail with RequestError — the
        exact shape a mid-restart PS presents — so the client retry path
        (aio.retry in the connectors) is what keeps the round alive."""
        from ..network.node import RequestError

        undo: list[tuple[Any, Any]] = []
        for name, worker in self.workers.items():
            node = getattr(worker, "node", None)
            if node is None:
                continue
            orig_push = node.push
            if name == ps_peer:

                async def ps_push(peer_id: str, resource: Any, source) -> int:
                    raise RequestError(
                        "chaos partition-ps: broadcast push dropped"
                    )

                node.push = ps_push
            else:

                async def worker_push(
                    peer_id: str, resource: Any, source, _orig=orig_push
                ) -> int:
                    if peer_id == ps_peer:
                        raise RequestError(
                            f"chaos partition-ps: push to {ps_peer} dropped"
                        )
                    return await _orig(peer_id, resource, source)

                node.push = worker_push
            undo.append((node, orig_push))

        async def heal() -> None:
            await asyncio.sleep(duration_s)
            for node, orig_push in undo:
                node.push = orig_push
            log.info("chaos: partition-ps around %s healed", ps_peer)

        aio.spawn(heal(), tasks=self._tasks, what="chaos heal", logger=log)

    def _partition_scheduler(self, sched_peer: str, duration_s: float) -> None:
        """Sever the fleet's UPLINK to the scheduler for ``duration_s``
        seconds, then heal. Every other node's requests (Status,
        UpdateReceived, Updated, JobStatus) and pushes toward the
        scheduler fail with RequestError — the exact shape a dead/restart-
        ing scheduler presents — so the park-in-aio.retry paths (bridge
        status sends, the PS's resilient Updated notify) are what keep the
        job alive. The scheduler's own outbound renewals are untouched:
        this models uplink loss, not the full crash (``kill-scheduler``
        covers that one)."""
        from ..network.node import RequestError

        undo: list[tuple[Any, Any, Any]] = []
        for name, worker in self.workers.items():
            if name == sched_peer:
                continue
            node = getattr(worker, "node", None)
            if node is None:
                continue
            orig_push = node.push
            orig_request = node.request

            async def cut_push(
                peer_id: str, resource: Any, source, _orig=orig_push
            ) -> int:
                if peer_id == sched_peer:
                    raise RequestError(
                        f"chaos partition-scheduler: push to {sched_peer} dropped"
                    )
                return await _orig(peer_id, resource, source)

            async def cut_request(
                peer_id: str, protocol: str, msg: Any,
                _orig=orig_request, **kw,
            ) -> Any:
                if peer_id == sched_peer:
                    raise RequestError(
                        f"chaos partition-scheduler: request to {sched_peer} dropped"
                    )
                return await _orig(peer_id, protocol, msg, **kw)

            node.push = cut_push
            node.request = cut_request
            undo.append((node, orig_push, orig_request))

        async def heal() -> None:
            await asyncio.sleep(duration_s)
            for node, orig_push, orig_request in undo:
                node.push = orig_push
                node.request = orig_request
            log.info(
                "chaos: partition-scheduler around %s healed", sched_peer
            )

        aio.spawn(heal(), tasks=self._tasks, what="chaos heal", logger=log)

    @staticmethod
    def _partition(node: Any) -> None:
        from ..network.node import RequestError

        async def dead_push(peer_id: str, resource: Any, source) -> int:
            raise RequestError(f"chaos partition: push to {peer_id} dropped")

        async def dead_request(peer_id: str, protocol: str, msg: Any, **kw) -> Any:
            raise RequestError(f"chaos partition: request to {peer_id} dropped")

        node.push = dead_push
        node.request = dead_request

    # --------------------------------------------------------------- queries
    def fired_at(self, target: str) -> float | None:
        for action in self.fired:
            if action.target == target:
                return action.fired_at
        return None

    async def drain(self) -> None:
        """Wait for in-flight kill tasks (test teardown hygiene)."""
        if self._tasks:
            await asyncio.gather(*list(self._tasks), return_exceptions=True)
