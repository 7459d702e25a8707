"""dRAP auction integration: scheduler ad → worker offers → leases →
dispatch → status, over the in-memory fabric.

Reference roles: crates/worker/src/arbiter.rs (worker side),
crates/scheduler/src/allocator.rs + worker.rs + task.rs (scheduler side),
rfc/2025-08-04 (protocol: ≤4 messages, renewal-as-acceptance, temp leases).
"""

from __future__ import annotations

import asyncio

import pytest

from hypha_tpu.leases import LeaseNotFound
from hypha_tpu.messages import (
    AggregateExecutorConfig,
    Executor,
    ExecutorDescriptor,
    JobSpec,
    Nesterov,
    PriceRange,
    Receive,
    Reference,
    Send,
    WorkerSpec,
)
from hypha_tpu.network import MemoryTransport, Node
from hypha_tpu.resources import Resources
from hypha_tpu.scheduler.allocator import GreedyWorkerAllocator
from hypha_tpu.scheduler.task import StatusRouter, Task
from hypha_tpu.scheduler.worker_handle import WorkerHandle
from hypha_tpu.worker import (
    Arbiter,
    JobManager,
    LeaseManager,
    OfferConfig,
    StaticResourceManager,
)
from hypha_tpu.worker.job_manager import Execution, JobExecutor


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=30))


class FakeExecutor(JobExecutor):
    """Records executions; completes when told."""

    def __init__(self) -> None:
        self.executions: list[Execution] = []

    async def execute(self, job_id, spec, scheduler_peer):
        ex = Execution(job_id)
        self.executions.append(ex)
        return ex


def _spec(tpu=1.0) -> WorkerSpec:
    return WorkerSpec(
        resources=Resources(tpu=tpu, memory=100),
        executor=[ExecutorDescriptor(executor_class="train", name="diloco-jax")],
    )


def _job(job_id="job-1") -> JobSpec:
    peers = Reference.from_peers(["ps"], "updates")
    return JobSpec(
        job_id=job_id,
        executor=Executor(
            kind="aggregate",
            name="diloco-jax",
            aggregate=AggregateExecutorConfig(
                updates=Receive(peers), results=Send(peers), optimizer=Nesterov()
            ),
        ),
    )


async def _mk_worker(hub, name, price=1.0, tpu=4.0, floor=0.0, executors=None):
    node = Node(hub.shared(), peer_id=name)
    await node.start()
    lm = LeaseManager(StaticResourceManager(Resources(tpu=tpu, cpu=8, memory=1000)))
    fake = FakeExecutor()
    execs = executors or {("train", "diloco-jax"): fake, ("aggregate", "diloco-jax"): fake}
    jm = JobManager(node, execs)
    arb = Arbiter(
        node, lm, jm, offer=OfferConfig(price=price, floor=floor)
    )
    await arb.start()
    return node, lm, jm, arb, fake


async def _mesh(hub, sched, workers):
    """Wire gossip mesh scheduler <-> workers directly (no gateway)."""
    for w in workers:
        await sched.dial(w.listen_addrs[0])
        sched.add_gossip_peer(w.peer_id)
        w.add_peer_addr(sched.peer_id, sched.listen_addrs[0])
        w.add_gossip_peer(sched.peer_id)


def test_auction_allocates_best_offers_with_diversity():
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        w1 = await _mk_worker(hub, "w1", price=1.0)
        w2 = await _mk_worker(hub, "w2", price=3.0)
        w3 = await _mk_worker(hub, "w3", price=9.0)  # over the cap
        await _mesh(hub, sched, [w[0] for w in (w1, w2, w3)])

        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=2
        )
        peers = {o.peer_id for o in offers}
        assert peers == {"w1", "w2"}, peers  # w3 over price cap
        # offers are backed by temp leases on the workers
        assert len(w1[1].ledger) == 1 and len(w2[1].ledger) == 1
        for w in (w1, w2, w3):
            await w[3].stop(); await w[0].stop()
        await sched.stop()

    run(main())


def test_floor_and_capacity_filters():
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        # floor above the bid -> no offer; tiny capacity -> no offer
        w1 = await _mk_worker(hub, "w1", floor=10.0)
        w2 = await _mk_worker(hub, "w2", tpu=0.5)
        await _mesh(hub, sched, [w1[0], w2[0]])
        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(tpu=1.0), PriceRange(bid=1.0, max=5.0), timeout=0.6, num_workers=2
        )
        assert offers == []
        for w in (w1, w2):
            await w[3].stop(); await w[0].stop()
        await sched.stop()

    run(main())


def test_lease_lifecycle_renewal_and_dispatch():
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])

        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        assert len(offers) == 1
        # acceptance: first renewal upgrades the 500 ms temp lease to 10 s
        handle = await WorkerHandle.create(sched, offers[0])
        lease = lm.get(handle.lease_id)
        assert lease.remaining() > 5.0

        router = StatusRouter(sched)
        task = await Task.dispatch(sched, router, _job(), [handle])
        # worker reported "running"
        peer, status = await task.next_status(timeout=5)
        assert peer == "w1" and status.state == "running"
        assert len(fake.executions) == 1

        # executor completes -> completed status flows back
        fake.executions[0].finish("completed")
        peer, status = await task.next_status(timeout=5)
        assert status.state == "completed"

        await handle.release()
        task.close()
        router.close()
        await arb.stop(); await node.stop(); await sched.stop()

    run(main())


def test_dispatch_without_lease_rejected():
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])

        from hypha_tpu.messages import PROTOCOL_API, DispatchJob

        resp = await sched.request(
            "w1", PROTOCOL_API, DispatchJob(lease_id="bogus", spec=_job())
        )
        assert not resp.accepted and "no such lease" in resp.message
        await arb.stop(); await node.stop(); await sched.stop()

    run(main())


def test_foreign_peer_cannot_renew_or_dispatch():
    """Lease operations are owner-checked (arbiter.rs:150-200, :212-276)."""

    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        thief = Node(hub.shared(), peer_id="thief")
        await sched.start(); await thief.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])

        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        lease_id = offers[0].lease_id

        from hypha_tpu.messages import PROTOCOL_API, DispatchJob, RenewLease
        from hypha_tpu.network import RequestError

        thief.add_peer_addr("w1", node.listen_addrs[0])
        with pytest.raises(RequestError, match="not owned"):
            await thief.request("w1", PROTOCOL_API, RenewLease(lease_id=lease_id))
        resp = await thief.request(
            "w1", PROTOCOL_API, DispatchJob(lease_id=lease_id, spec=_job())
        )
        assert not resp.accepted and "not yours" in resp.message
        await arb.stop(); await node.stop(); await sched.stop(); await thief.stop()

    run(main())


def test_expired_lease_prunes_and_cancels_jobs():
    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])

        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        handle = await WorkerHandle.create(sched, offers[0])
        router = StatusRouter(sched)
        task = await Task.dispatch(sched, router, _job(), [handle])
        await task.next_status(timeout=5)  # running

        # stop renewing and force-expire the lease: prune loop must cancel
        await handle.release()
        lm.ledger.get(handle.lease_id).timeout = 0.0
        peer, status = await task.next_status(timeout=5)
        assert status.state == "cancelled"
        assert len(jm) == 0
        with pytest.raises(LeaseNotFound):
            lm.get(handle.lease_id)
        # resources are back
        assert lm.resources.available() == lm.resources.capacity()

        task.close(); router.close()
        await arb.stop(); await node.stop(); await sched.stop()

    run(main())


def test_renewal_failure_surfaces_as_worker_failure(monkeypatch):
    # The failure shows at the next renewal, 2/3 of the lease after the last.
    monkeypatch.setattr("hypha_tpu.worker.arbiter.LEASE_TIMEOUT_S", 3.0)

    async def main():
        hub = MemoryTransport()
        sched = Node(hub.shared(), peer_id="sched")
        await sched.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, sched, [node])

        allocator = GreedyWorkerAllocator(sched)
        offers = await allocator.request(
            _spec(), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        handle = await WorkerHandle.create(sched, offers[0])
        # kill the worker: next renewal fails -> failure future resolves
        await arb.stop(); await node.stop()
        failure = await asyncio.wait_for(handle.failed, 15)
        assert failure.peer_id == "w1"
        await handle.release()
        await sched.stop()

    run(main())


def test_cancel_requires_job_under_lease():
    """CancelJob is bound to the lease that dispatched the job: another
    scheduler's valid lease must not be able to cancel this one's job."""

    async def main():
        hub = MemoryTransport()
        s1 = Node(hub.shared(), peer_id="s1")
        s2 = Node(hub.shared(), peer_id="s2")
        await s1.start(); await s2.start()
        node, lm, jm, arb, fake = await _mk_worker(hub, "w1")
        await _mesh(hub, s1, [node])
        await _mesh(hub, s2, [node])

        from hypha_tpu.messages import PROTOCOL_API, CancelJob

        offers1 = await GreedyWorkerAllocator(s1).request(
            _spec(1.0), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        h1 = await WorkerHandle.create(s1, offers1[0])
        router = StatusRouter(s1)
        task = await Task.dispatch(s1, router, _job("job-a"), [h1])

        offers2 = await GreedyWorkerAllocator(s2).request(
            _spec(1.0), PriceRange(bid=1.0, max=5.0), timeout=1.0, num_workers=1
        )
        h2 = await WorkerHandle.create(s2, offers2[0])

        # s2 holds a valid lease but job-a is not under it
        resp = await s2.request(
            "w1", PROTOCOL_API, CancelJob(lease_id=h2.lease_id, job_id="job-a")
        )
        assert not resp.ok and "not under this lease" in resp.message
        assert len(jm) == 1  # job survived

        # the owning lease can cancel it
        resp = await s1.request(
            "w1", PROTOCOL_API, CancelJob(lease_id=h1.lease_id, job_id="job-a")
        )
        assert resp.ok
        await fake.executions[0].wait()  # cancelled

        await h1.release(); await h2.release()
        task.close(); router.close()
        await arb.stop(); await node.stop(); await s1.stop(); await s2.stop()

    run(main())
