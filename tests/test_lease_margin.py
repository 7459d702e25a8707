"""The lease, measured where it is kept (docs/observability.md, "The lease").

A worker logs the margin every renewal found (``lease renewed``), the
scheduler how late its renewal loop woke and the round trip (``lease
renewal``). With the lease's lifetime patched short, an event loop that
stands still for a third of it keeps the job and says how late it was; one
that stands still past it loses the job, and the line that says so carries
the age of the last renewal.
"""

from __future__ import annotations

import asyncio
import logging
import re
import time

import pytest

from hypha_tpu.messages import PriceRange
from hypha_tpu.network import MemoryTransport, Node
from hypha_tpu.scheduler.allocator import GreedyWorkerAllocator
from hypha_tpu.scheduler.task import StatusRouter, Task
from hypha_tpu.scheduler.worker_handle import WorkerHandle
from hypha_tpu.worker import arbiter

from perfbench import logs
from test_auction import _job, _mesh, _mk_worker, _spec, run

LEASE_S = 3.0  # renewed every 2 s, so 1 s of margin
ARBITER, HANDLE = "hypha.worker.arbiter", "hypha.scheduler.worker"


def _fields(caplog, logger: str, pattern: str) -> list[dict]:
    return [
        logs.parse_fields(r.getMessage()) for r in caplog.records
        if r.name == logger and re.search(pattern, r.getMessage())
    ]


async def _leased_job(block_s: float):
    """One worker with a job under a lease; 1.5 s in, the one event loop
    that scheduler and worker share stands still for ``block_s``."""
    hub = MemoryTransport()
    sched = Node(hub.shared(), peer_id="sched")
    await sched.start()
    node, lm, jm, arb, _ = await _mk_worker(hub, "w1")
    await _mesh(hub, sched, [node])
    offers = await GreedyWorkerAllocator(sched).request(
        _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
    )
    handle = await WorkerHandle.create(sched, offers[0])
    router = StatusRouter(sched)
    task = await Task.dispatch(sched, router, _job(), [handle])
    await task.next_status(timeout=5)  # running
    await asyncio.sleep(1.5)
    time.sleep(block_s)  # the fault under test
    await asyncio.sleep(1.0)  # the renewal that was due, and the prune loop
    lost = handle.failed.done()
    jobs = len(jm)
    await handle.release()
    task.close(); router.close()
    await arb.stop(); await node.stop(); await sched.stop()
    return lost, jobs


@pytest.fixture
def short_lease(monkeypatch, caplog):
    monkeypatch.setattr(arbiter, "LEASE_TIMEOUT_S", LEASE_S)
    caplog.set_level(logging.INFO, logger=ARBITER)
    caplog.set_level(logging.INFO, logger=HANDLE)
    return caplog


def test_a_loop_blocked_for_a_third_of_the_lease_keeps_the_job_and_says_how_late(short_lease):
    lost, jobs = run(_leased_job(LEASE_S / 3))
    assert not lost and jobs == 1
    accepted = _fields(short_lease, ARBITER, r"lease accepted: ")
    renewed = _fields(short_lease, ARBITER, r"lease renewed: ")
    renewal = _fields(short_lease, HANDLE, r"lease renewal: ")
    assert len(accepted) == 1 and renewed and renewal
    for line in accepted + renewed:
        assert {"lease", "peer", "margin_s", "ttl_s"} <= set(line)
        assert line["peer"] == "sched" and line["ttl_s"] == LEASE_S
    # The offer lease had under its 0.5 s left; the real renewal came about
    # 0.5 s late into a margin of 1 s.
    assert 0 < accepted[0]["margin_s"] <= arbiter.OFFER_TIMEOUT_S
    assert 0 < renewed[0]["margin_s"] < LEASE_S / 3 - 0.3
    assert renewal[0]["peer"] == "w1"
    assert 0.3 < renewal[0]["late_s"] < LEASE_S / 3
    assert 0 <= renewal[0]["rtt_s"] < 1.0
    assert not _fields(short_lease, ARBITER, r"lease \S+ expired")


def test_a_loop_blocked_past_the_lease_loses_the_job_and_the_line_says_since_when(short_lease):
    lost, jobs = run(_leased_job(2.0))
    assert lost and jobs == 0
    expired = _fields(short_lease, ARBITER, r"lease \S+ expired")
    assert len(expired) == 1 and expired[0]["last_renew_age_s"] >= LEASE_S
    failed = _fields(short_lease, HANDLE, r"renewal of w1 failed .* worker lost")
    assert len(failed) == 1 and failed[0]["late_s"] > 1.0 and failed[0]["rtt_s"] >= 0
    retried = _fields(short_lease, HANDLE, r"renewal of w1 failed .* one retry")
    assert len(retried) == 1 and {"late_s", "rtt_s"} <= set(retried[0])


def test_an_offer_that_was_not_taken_expires_as_never_renewed(short_lease):
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        node, lm, jm, arb, _ = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])
        await GreedyWorkerAllocator(sched).request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        await asyncio.sleep(arbiter.OFFER_TIMEOUT_S + 2 * arbiter.PRUNE_INTERVAL_S)
        await arb.stop(); await node.stop(); await sched.stop()

    run(main())
    expired = _fields(short_lease, ARBITER, r"lease \S+ expired")
    assert [e["last_renew_age_s"] for e in expired] == ["never"]


def test_the_lease_outlasts_the_renewal_rpc_and_its_retry():
    """The repair itself: renewed at 2/3, a lease leaves a third, and the
    scheduler's 5 s RPC timeout and its one retry have to fit into it."""
    assert arbiter.LEASE_TIMEOUT_S / 3 >= 2 * 5.0
