"""Scheduler-side worker handle: lease renewal as liveness.

Reference: crates/scheduler/src/worker.rs:59-177 — the ``Worker`` handle
owns a background renewal loop that re-renews at 2/3 of the granted
timeout; the *first* renewal converts the worker's temporary offer lease
into a live one (acceptance), and a renewal failure is the scheduler's
worker-failure detector, surfacing through ``failed``.
"""

from __future__ import annotations

import asyncio
import logging
import time

from .. import aio
from ..messages import PROTOCOL_API, RenewLease, RenewLeaseResponse, WorkerOffer
from ..network.node import Node, RequestError
from ..telemetry import trace

__all__ = ["WorkerHandle", "WorkerFailure"]

log = logging.getLogger("hypha.scheduler.worker")


class WorkerFailure(RuntimeError):
    def __init__(self, peer_id: str, reason: str) -> None:
        super().__init__(f"worker {peer_id} failed: {reason}")
        self.peer_id = peer_id
        self.reason = reason


class WorkerHandle:
    """An allocated worker under a live, continuously-renewed lease."""

    def __init__(self, node: Node, offer: WorkerOffer) -> None:
        self.node = node
        self.offer = offer
        self.peer_id = offer.peer_id
        self.lease_id = offer.lease_id
        self.batch_size: int = 0  # set by the scheduler's sizing rule
        self.failed: asyncio.Future[WorkerFailure] = (
            asyncio.get_event_loop().create_future()
        )
        # Liveness hook: called with the peer id after every successful
        # renewal — the orchestrator's φ-accrual detector feeds on it
        # alongside the per-batch Status heartbeats (hypha_tpu.ft.detector).
        self.on_renew: "callable | None" = None
        # ``() -> (traceparent, round)`` of the round that is open (the
        # BatchScheduler's root span): a traced renewal is filed under it.
        self.round_ctx: "callable | None" = None
        self._renewal: asyncio.Task | None = None
        self._released = False

    @classmethod
    async def create(cls, node: Node, offer: WorkerOffer) -> "WorkerHandle":
        """Accept the offer: first renewal locks the lease in, then the
        renewal loop keeps it alive (worker.rs:75-146)."""
        handle = cls(node, offer)
        timeout = await handle._renew()
        handle._renewal = asyncio.create_task(handle._renewal_loop(timeout))
        return handle

    @classmethod
    async def adopt(
        cls, node: Node, peer_id: str, lease_id: str
    ) -> "WorkerHandle":
        """Re-arm a JOURNALED lease after a scheduler restart
        (ft.durable DurableScheduler).

        The worker kept the lease alive through the outage (the adoption
        grace holds it past expiry), so the restarted scheduler's first
        renewal — owner-checked against the same scheduler peer id —
        resumes exactly where the dead renewal loop stopped. A renewal
        failure here is the adoption-time worker-death signal: the caller
        falls back to the existing depart/rejoin or ps-restart path.
        """
        from ..resources import Resources

        offer = WorkerOffer(
            request_id="adopt",
            lease_id=lease_id,
            peer_id=peer_id,
            resources=Resources(),
            price=0.0,
            expires_in=0.0,
        )
        return await cls.create(node, offer)

    async def _renew(self) -> float:
        resp = await self.node.request(
            self.peer_id,
            PROTOCOL_API,
            RenewLease(lease_id=self.lease_id),
            timeout=5.0,
        )
        if not isinstance(resp, RenewLeaseResponse):
            raise RequestError(f"unexpected renew response {resp!r}")
        return resp.timeout

    async def _renewal_loop(self, timeout: float) -> None:
        """Re-renew at 2/3 of the granted validity (worker.rs:103-117).

        One immediate retry before declaring failure: renewing at 2/3 of
        the TTL leaves a third of it unspent, so a single RPC timeout on a
        loaded host must not depose a healthy worker — a dead node fails
        both attempts fast and detection latency stays unchanged.

        Every renewal says how late this loop woke against the moment it
        asked for (``late_s``: the scheduler's own event-loop lag, at the
        one moment it costs a job) and the round trip (``rtt_s``); the
        worker's ``lease renewed`` line has the margin that was left."""
        while not self._released:
            due = time.monotonic() + timeout * 2 / 3
            await asyncio.sleep(timeout * 2 / 3)
            if self._released:
                return
            tp, round_num = self.round_ctx() if self.round_ctx else (None, None)
            # The span is the instant this loop woke, written once the round
            # trip is known: an interval as long as the round trip would be
            # the shortest span open while a worker's loop stands still, and
            # a reader that hands idle time to the shortest open span would
            # take it from the phase that worker was in.
            with trace.phase(
                "lease_renew", parent=tp, node="scheduler", defer=True,
                attrs={"peer": self.peer_id}
                | ({} if round_num is None else {"round": round_num}),
            ) as renewal:
                woke = time.monotonic()
            late_s = woke - due
            failure: RequestError | None = None
            try:
                try:
                    timeout = await self._renew()
                except RequestError as e:
                    log.warning(
                        "renewal of %s failed (%s); one retry late_s=%.3f "
                        "rtt_s=%.3f",
                        self.peer_id, e, late_s, time.monotonic() - woke,
                    )
                    timeout = await self._renew()
            except RequestError as e:
                failure = e
            rtt_s = time.monotonic() - woke
            renewal.set("late_s", late_s)
            renewal.set("rtt_s", rtt_s)
            renewal.set("renewed", failure is None)
            renewal.write()
            if failure is not None:
                log.warning(
                    "renewal of %s failed (%s); worker lost late_s=%.3f "
                    "rtt_s=%.3f", self.peer_id, failure, late_s, rtt_s,
                )
                # Resolved with (not raised as) the failure so an un-awaited
                # handle doesn't log "exception never retrieved".
                if not self.failed.done():
                    self.failed.set_result(
                        WorkerFailure(self.peer_id, str(failure))
                    )
                return
            log.info(
                "lease renewal: peer=%s late_s=%.3f rtt_s=%.3f",
                self.peer_id, late_s, rtt_s,
            )
            if self.on_renew is not None:
                self.on_renew(self.peer_id)

    async def release(self) -> None:
        """Stop renewing; the worker-side lease expires on its own and the
        prune loop reclaims the resources."""
        self._released = True
        await aio.reap(self._renewal)
        if not self.failed.done():
            self.failed.cancel()
