"""Durable control plane (hypha_tpu.ft.durable DurableScheduler): scheduler
journal, generation-stamped idempotency, execution re-adoption.

Layers:

  1. unit — scheduler journal framing/compaction (torn-tail tolerance),
     generation stamping + the zombie/stale-generation guards, duplicate
     ScheduleUpdate idempotency, round fast-forward, the straggler
     controller's post-restart warmup, the worker-side adoption grace;
  2. integration — the adoption handshake against a real Arbiter, the
     fake-clock adoption deadline, the quorate-round-closes-without-the-
     scheduler ordering, and the `fault`-marked orchestrated
     kill-scheduler e2e whose final weights must be BIT-equal to a
     no-kill run (the acceptance bar).
"""

from __future__ import annotations

import asyncio
import struct
import time
from pathlib import Path

import pytest

from hypha_tpu import messages
from hypha_tpu.executor.training import adopt_schedule
from hypha_tpu.ft.adaptive import LinkTable, StragglerController
from hypha_tpu.ft.durable import (
    DurableScheduler,
    RoundJournal,
    stale_scheduler_response,
)
from hypha_tpu.ft.membership import FTConfig, RoundMembership
from hypha_tpu.messages import (
    AdoptAck,
    AggregateExecutorConfig,
    Nesterov,
    Progress,
    ProgressKind,
    ProgressResponse,
    ProgressResponseKind,
    Receive,
    Reference,
    SchedulerHello,
    Send,
    TrainExecutorConfig,
)
from hypha_tpu.network.node import RequestError
from hypha_tpu.scheduler.batch_scheduler import BatchScheduler
from hypha_tpu.scheduler.trackers import ProgressTracker
from hypha_tpu.telemetry.ft_metrics import FT_METRICS


def run(coro, timeout=90):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# --------------------------------------------------------------------------
# scheduler journal
# --------------------------------------------------------------------------


def _seed_journal(root: Path) -> DurableScheduler:
    dur = DurableScheduler.open(root, fresh=True)
    dur.note_plan(
        {
            "base_id": "base-1",
            "workers": {
                "w0": {"lease_id": "l0", "batch_size": 2},
                "w1": {"lease_id": "l1", "batch_size": 2},
            },
            "ps_peers": ["psw"],
        }
    )
    dur.note_dispatch("base-1-w0", "w0", "l0", "train", batch_size=2)
    dur.note_dispatch("base-1-w1", "w1", "l1", "train", batch_size=2)
    dur.note_dispatch("base-1-ps", "psw", "lp", "aggregate", shard=0)
    return dur


def test_sched_journal_roundtrip(tmp_path):
    dur = _seed_journal(tmp_path)
    dur.note_round(2, {"round": 2, "per_step": {"w0": 0.5}})
    dur.note_member({"epoch": 4, "active": ["w0", "w1"], "departed": []}, 1)
    dur.close()

    dur2 = DurableScheduler.open(tmp_path)
    assert dur2.generation == 2
    res = dur2.resume
    assert res is not None
    assert res.base_id == "base-1"
    assert res.round == 2
    assert res.ctrl == {"round": 2, "per_step": {"w0": 0.5}}
    assert set(res.dispatches) == {"base-1-w0", "base-1-w1", "base-1-ps"}
    assert res.dispatches["base-1-ps"]["shard"] == 0
    assert res.member["epoch"] == 4
    assert res.rejoins == 1
    dur2.close()


def test_sched_journal_dispatch_superseded_by_rejoin(tmp_path):
    """A rejoin re-dispatch under the same job id supersedes the original
    record — adoption must hello the REPLACEMENT peer."""
    dur = _seed_journal(tmp_path)
    dur.note_dispatch("base-1-r0", "w9", "l9", "train", batch_size=2)
    dur.close()
    dur2 = DurableScheduler.open(tmp_path)
    assert dur2.resume.dispatches["base-1-r0"]["peer"] == "w9"
    dur2.close()


def test_sched_journal_torn_tail_parses_as_end(tmp_path):
    dur = _seed_journal(tmp_path)
    dur.note_round(3)
    dur.close()
    path = tmp_path / "sched-journal.cbor"
    data = path.read_bytes()
    # Tear mid-record: chop the last record's body short.
    path.write_bytes(data[:-3])
    dur2 = DurableScheduler.open(tmp_path)
    assert dur2.resume is not None
    assert dur2.resume.base_id == "base-1"
    # The torn round record is gone; everything before it survived.
    assert dur2.resume.round in (0, 3)
    dur2.close()


def test_sched_journal_garbage_resumes_nothing(tmp_path):
    """An unreadable journal (arbitrary corruption) parses as an empty log
    — resume is None and the orchestrator falls back to the fresh-run /
    re-auction path instead of wedging."""
    path = tmp_path / "sched-journal.cbor"
    path.write_bytes(struct.pack("<I", 1 << 30) + b"\xde\xad\xbe\xef" * 16)
    assert DurableScheduler.has_state(tmp_path)
    dur = DurableScheduler.open(tmp_path)
    assert dur.resume is None
    assert dur.generation == 1
    dur.close()


def test_sched_journal_compaction_stays_bounded(tmp_path):
    dur = _seed_journal(tmp_path)
    for r in range(1, 100):
        dur.note_round(r)
    size = (tmp_path / "sched-journal.cbor").stat().st_size
    records = RoundJournal.read_all(tmp_path / "sched-journal.cbor")
    # Compaction every 8 rounds: gen + plan + 3 dispatches + <= 8 rounds.
    assert len(records) <= 16, records
    assert size < 4096
    dur.close()
    dur2 = DurableScheduler.open(tmp_path)
    assert dur2.resume.round == 99
    assert set(dur2.resume.dispatches) == {
        "base-1-w0", "base-1-w1", "base-1-ps"
    }
    dur2.close()


def test_sched_journal_generation_monotonic_and_complete_wipes(tmp_path):
    gens = []
    for _ in range(3):
        dur = DurableScheduler.open(tmp_path)
        gens.append(dur.generation)
        if dur.resume is None:
            dur.note_plan({"base_id": "b", "workers": {}, "ps_peers": ["p"]})
        dur.close()
    assert gens == [1, 2, 3]
    dur = DurableScheduler.open(tmp_path)
    dur.complete()
    assert not DurableScheduler.has_state(tmp_path)
    # A completed job's next open starts a fresh generation line.
    dur2 = DurableScheduler.open(tmp_path)
    assert dur2.generation == 1 and dur2.resume is None
    dur2.close()


# --------------------------------------------------------------------------
# generation stamping + idempotency
# --------------------------------------------------------------------------


def _scheduler(generation=None, epochs=4, target=4):
    tracker = ProgressTracker(
        parameter_server="psw", update_target=target, update_epochs=epochs
    )
    tracker.add_worker("w0", 2)
    tracker.add_worker("w1", 2)
    return BatchScheduler(tracker, generation=generation), tracker


def test_unstamped_responses_are_byte_identical_singletons():
    """Generation off-path (a job that never restarts its scheduler): the
    shared frozen response singletons survive and the wire carries no
    generation/round keys — byte-identical to today's."""
    sched, _ = _scheduler(generation=None, target=100)
    r1 = sched.on_progress(
        "w0", Progress(kind=ProgressKind.STATUS, batch_size=2)
    )
    r2 = sched.on_progress(
        "w0", Progress(kind=ProgressKind.STATUS, batch_size=2)
    )
    assert r1 is r2  # the shared frozen singleton survives
    for resp in (r1, r2):
        enc = messages.encode(resp)
        assert b"generation" not in enc
        assert b"round" not in enc
    assert b"scheduler_generation" not in messages.encode(
        Progress(kind=ProgressKind.STATUS, batch_size=2)
    )


def test_restarted_scheduler_stamps_generation_and_round():
    sched, tracker = _scheduler(generation=2)
    resp = sched.on_progress(
        "w0", Progress(kind=ProgressKind.STATUS, batch_size=2)
    )
    assert resp.generation == 2
    assert resp.round == tracker.round
    enc = messages.encode(resp)
    assert b"generation" in enc and b"round" in enc


def test_zombie_scheduler_drops_newer_generation_traffic():
    """An UPDATED stamped for generation 3 arriving at a generation-2
    scheduler: WE are the zombie — refuse instead of advancing the round."""
    sched, tracker = _scheduler(generation=2)
    before = FT_METRICS.stale_generation_dropped.value()
    resp = sched.on_progress(
        "psw",
        Progress(
            kind=ProgressKind.UPDATED, round=0, scheduler_generation=3
        ),
    )
    assert resp.kind == ProgressResponseKind.ERROR
    assert tracker.round == 0
    assert FT_METRICS.stale_generation_dropped.value() == before + 1


def test_generation_one_zombie_drops_newer_generation_traffic():
    """The most common zombie is the UNSTAMPED generation-1 predecessor
    (it never restarted, so it stamps nothing): stamped traffic from a
    fleet that adopted its successor must still be refused — senders only
    stamp after adopting generation >= 2, so an unstamped scheduler
    receiving stamped traffic is by construction the one that died."""
    sched, tracker = _scheduler(generation=None)
    resp = sched.on_progress(
        "psw",
        Progress(kind=ProgressKind.UPDATED, round=0, scheduler_generation=2),
    )
    assert resp.kind == ProgressResponseKind.ERROR
    assert tracker.round == 0


def test_old_generation_updated_still_processed():
    """A parked Updated from the pre-crash era (stamped gen 2 at a gen-3
    scheduler) is REAL round progress — round idempotency handles
    duplicates; generation gating must not wedge the round."""
    sched, tracker = _scheduler(generation=3)
    resp = sched.on_progress(
        "psw",
        Progress(kind=ProgressKind.UPDATED, round=0, scheduler_generation=2),
    )
    assert resp.kind == ProgressResponseKind.OK
    assert tracker.round == 1


def test_duplicate_schedule_update_is_idempotent():
    """A restarted scheduler re-issues ScheduleUpdate to a worker already
    counting down: the countdown in progress stands."""
    first = ProgressResponse(
        kind=ProgressResponseKind.SCHEDULE_UPDATE, counter=5
    )
    dup = ProgressResponse(
        kind=ProgressResponseKind.SCHEDULE_UPDATE, counter=9, generation=2
    )
    countdown = adopt_schedule(first, None)
    assert countdown == 5
    countdown -= 1
    assert adopt_schedule(dup, countdown) == 4  # duplicate ignored
    # Round boundary (countdown back to None): the next issue is adopted.
    assert adopt_schedule(dup, None) == 9
    # Non-schedule responses never touch the countdown.
    cont = ProgressResponse(kind=ProgressResponseKind.CONTINUE)
    assert adopt_schedule(cont, 3) == 3


def test_stale_generation_continue_dropped():
    """The worker-side gate: a Continue stamped with an OLDER generation
    than one already adopted is a zombie's control decision — dropped."""
    gen = None
    gen, stale = stale_scheduler_response(
        ProgressResponse(kind=ProgressResponseKind.CONTINUE, generation=2), gen
    )
    assert (gen, stale) == (2, False)
    gen, stale = stale_scheduler_response(
        ProgressResponse(kind=ProgressResponseKind.CONTINUE, generation=1), gen
    )
    assert stale and gen == 2
    # Unstamped responses (the off path) pass through untouched.
    gen, stale = stale_scheduler_response(
        ProgressResponse(kind=ProgressResponseKind.CONTINUE), gen
    )
    assert (gen, stale) == (2, False)


def test_adopt_round_fast_forwards_from_acks():
    """The fleet's truth wins: a PS whose AdoptAck reports round 3 carries
    rounds the journal never saw — the scheduler fast-forwards, never
    rewinds, and an already-quorate round is never re-run."""
    sched, tracker = _scheduler(generation=2, epochs=6)
    adopted = sched.adopt_round(1, {0: 3})
    assert adopted == 3 and tracker.round == 3
    # Fast-forward only: a lower report never rewinds.
    assert sched.adopt_round(1, {0: 2}) == 3
    # The PS's parked re-notify of round 2 is now idempotent.
    resp = sched.on_progress(
        "psw", Progress(kind=ProgressKind.UPDATED, round=2)
    )
    assert resp.kind == ProgressResponseKind.OK
    assert tracker.round == 3


# --------------------------------------------------------------------------
# straggler controller: post-restart warmup (satellite regression)
# --------------------------------------------------------------------------


def test_controller_reset_mid_job_does_not_punish_healthy_peers():
    """A rebuilt StragglerController must start in WARMUP: no published
    assignments, no drop penalty, no EWMA feed from the outage-spanning
    round — until one full measured round completes (mirrors the PR 8
    recovered-PS re-notify guard)."""
    clock = {"t": 0.0}
    ctrl = StragglerController(
        base_steps=8, alpha=1.0, clock=lambda: clock["t"]
    )
    # Rounds 0-2: w1 is a real 4x straggler.
    for rnd in range(3):
        ctrl.start_round(rnd, ["w0", "w1"])
        ctrl.note_round_closed(rnd, {"w0": 1.0, "w1": 4.0})
    snap = ctrl.snapshot()
    assert snap["per_step"]["w1"] > snap["per_step"]["w0"]
    assert ctrl.steps_for("w1") < 8  # the live controller throttles w1

    # Scheduler crash: a REBUILT controller adopts the snapshot in warmup.
    ctrl2 = StragglerController(
        base_steps=8, alpha=1.0, clock=lambda: clock["t"]
    )
    ctrl2.resume_warmup(3, snap)
    # Warmup: base assignment for everyone, NOTHING published.
    assert ctrl2.steps_for("w1") == 8
    assert ctrl2.steps_for("w0") == 8
    assert ctrl2.assignments() == {}
    w1_before = ctrl2.snapshot()["per_step"]["w1"]
    # The outage-spanning round closes WITHOUT w0 (its arrival died with
    # the old scheduler) and with a grotesque parked-upload lag for w1:
    # neither may move the estimates or trigger the drop penalty.
    ctrl2.note_round_closed(3, {"w1": 400.0})
    ctrl2.start_round(4, ["w0", "w1"])
    after = ctrl2.snapshot()["per_step"]
    assert after["w1"] == pytest.approx(w1_before)  # no feed, no penalty
    assert "w0" not in after or after["w0"] == pytest.approx(
        snap["per_step"]["w0"]
    )
    # One full measured round later, normal adaptation resumes (from the
    # seeded history: w1 is throttled again without re-learning from
    # scratch).
    ctrl2.note_round_closed(4, {"w0": 1.0, "w1": 4.0})
    ctrl2.start_round(5, ["w0", "w1"])
    assert ctrl2.steps_for("w1") < 8
    assert ctrl2.assignments() != {}


def test_link_table_snapshot_restore_roundtrip():
    lt = LinkTable(base_codec="none", hi_mbps=100.0, lo_mbps=10.0)
    lt.observe("w0", 10_000_000, 1.0)  # 80 Mbit/s -> int8 tier
    snap = lt.snapshot()
    lt2 = LinkTable(base_codec="none", hi_mbps=100.0, lo_mbps=10.0)
    lt2.restore(snap)
    assert lt2.measured("w0")
    assert lt2.bandwidth_bps("w0") == pytest.approx(lt.bandwidth_bps("w0"))
    assert lt2.codec_for("w0") == lt.codec_for("w0")


# --------------------------------------------------------------------------
# off-path wire goldens (a job that never restarts its scheduler)
# --------------------------------------------------------------------------


def test_generation_off_path_ships_todays_wire():
    enc = messages.encode(
        TrainExecutorConfig(
            model={}, data=messages.Fetch(Reference.from_uri("file:///d")),
            updates=Send(Reference.from_peers(["p"], "u")),
            results=Receive(Reference.from_peers(["p"], "r")),
            optimizer=messages.Adam(), batch_size=2,
        )
    )
    assert b"adopt_grace_s" not in enc
    enc = messages.encode(
        AggregateExecutorConfig(
            updates=Receive(Reference.from_peers(["p"], "u")),
            results=Send(Reference.from_peers(["p"], "r")),
            optimizer=Nesterov(),
        )
    )
    assert b"adopt_grace_s" not in enc
    assert b"scheduler_adopt" not in messages.encode(FTConfig())
    rm = RoundMembership(epoch=1, active=["a"])
    assert messages.decode(messages.encode(rm)) == rm


# --------------------------------------------------------------------------
# adoption deadline (fake clock) + the handshake against a real arbiter
# --------------------------------------------------------------------------


class _FakeNode:
    """request() scripted per peer; never dials anything."""

    def __init__(self, answers=None):
        self.answers = answers or {}
        self.calls: list[tuple[str, object]] = []
        self.peer_id = "sched"

    async def request(self, peer, protocol, msg, timeout=None):
        self.calls.append((peer, msg))
        answer = self.answers.get(peer)
        if answer is None:
            raise RequestError(f"no route to {peer}")
        if callable(answer):
            return answer(msg)
        return answer


def _mini_orchestrator(node):
    from hypha_tpu.scheduler.orchestrator import Orchestrator

    orch = Orchestrator.__new__(Orchestrator)
    orch.node = node
    return orch


def test_adoption_deadline_fake_clock_no_real_waiting():
    """Executions that never ack fall out at the adoption deadline — the
    fallback to the re-auction path — with the deadline driven by an
    injected clock, not wall time."""
    from hypha_tpu.scheduler.orchestrator import _RunContext

    node = _FakeNode(
        answers={
            "w0": lambda msg: AdoptAck(
                job_id=msg.job_id, round=2, state="running",
                generation=msg.generation,
            )
        }
    )
    orch = _mini_orchestrator(node)
    ctx = _RunContext()
    ctx.dur = type(
        "D", (), {"generation": 2}
    )()
    clock = {"t": 0.0}

    def fake_clock():
        clock["t"] += 4.0  # each check burns 4 fake seconds
        return clock["t"]

    t0 = time.monotonic()
    acks = run(
        orch._adopt_executions(
            ctx,
            {"j-w0": {"peer": "w0"}, "j-w1": {"peer": "w1"}},
            round_hint=1,
            deadline_s=20.0,
            clock=fake_clock,
        ),
        timeout=30,
    )
    assert time.monotonic() - t0 < 10.0  # fake deadline, not 20 real s
    assert set(acks) == {"j-w0"}
    assert acks["j-w0"].round == 2
    hello = next(m for p, m in node.calls if p == "w0")
    assert isinstance(hello, SchedulerHello)
    assert hello.generation == 2 and hello.round == 1


def _arbiter_env():
    from hypha_tpu.resources import Resources
    from hypha_tpu.worker.arbiter import Arbiter
    from hypha_tpu.worker.job_manager import Execution, JobManager, _ActiveJob
    from hypha_tpu.worker.lease_manager import LeaseManager
    from hypha_tpu.worker.resources_mgr import StaticResourceManager

    lm = LeaseManager(StaticResourceManager(Resources(cpu=8, memory=100)))
    jm = JobManager(node=None, executors={})
    arb = Arbiter(node=None, lease_manager=lm, job_manager=jm)
    lease = lm.request("sched", Resources(cpu=1, memory=1), 10.0)
    execution = Execution("job-1")
    execution.round = 3
    execution.epoch = 2
    execution.adopt_grace_s = 30.0
    jm._active["job-1"] = _ActiveJob(execution=execution, lease_id=lease.id)
    return arb, lm, jm, lease, execution


def test_hello_adopts_running_execution_and_rearms_lease():
    async def main():
        arb, lm, jm, lease, execution = _arbiter_env()
        lease.timeout = time.time() + 0.5  # nearly lapsed during the outage
        ack = await arb._on_hello(
            "sched", SchedulerHello(generation=2, job_id="job-1", round=1)
        )
        assert ack.ok and ack.state == "running"
        assert ack.round == 3 and ack.epoch == 2
        assert execution.scheduler_generation == 2
        assert lm.get(lease.id).remaining() > 5.0  # renewed by the adoption

    run(main())


def test_hello_from_stale_generation_refused():
    async def main():
        arb, _, _, _, execution = _arbiter_env()
        execution.scheduler_generation = 3
        ack = await arb._on_hello(
            "sched", SchedulerHello(generation=2, job_id="job-1", round=1)
        )
        assert not ack.ok and ack.state == "stale"
        assert ack.generation == 3
        assert execution.scheduler_generation == 3  # unchanged

    run(main())


def test_hello_for_unknown_job_is_gone():
    async def main():
        arb, _, _, _, _ = _arbiter_env()
        ack = await arb._on_hello(
            "sched", SchedulerHello(generation=2, job_id="nope", round=0)
        )
        assert not ack.ok and ack.state == "gone"

    run(main())


def test_adoption_grace_defers_lease_prune_then_cancels(tmp_path):
    """The worker-side half of re-adoption: an adoptable job's lease
    outlives expiry by the grace (the execution keeps running), and only
    past the grace does the normal expiry cancellation fire."""
    from hypha_tpu.worker.arbiter import Arbiter

    async def main():
        arb, lm, jm, lease, execution = _arbiter_env()
        execution.adopt_grace_s = 0.8
        cancelled = []
        execution.cancel = lambda: cancelled.append(True) or _noop()

        async def _noop():
            return None

        async def cancel():
            cancelled.append(True)

        execution.cancel = cancel
        lease.timeout = time.time() + 0.2
        prune = asyncio.create_task(arb._prune_loop())
        try:
            await asyncio.sleep(0.6)
            # Expired 0.4 s ago — inside the grace: lease + job survive.
            assert not cancelled
            assert lm.ledger.try_get(lease.id) is not None
            await asyncio.sleep(0.8)
            # Past expiry + grace: pruned and cancelled.
            assert cancelled
            assert lm.ledger.try_get(lease.id) is None
        finally:
            prune.cancel()
            await asyncio.gather(prune, return_exceptions=True)

    run(main(), timeout=20)


# --------------------------------------------------------------------------
# quorate round closes without the scheduler
# --------------------------------------------------------------------------


def test_parked_notify_broadcasts_first_on_outage():
    """The acceptance pin: with the scheduler down, the PS's Updated
    notify parks — and the round's BROADCAST fires on the second
    consecutive failed attempt (one transient blip against a live
    scheduler must not reorder notify-before-broadcast), so a round that
    is already quorate closes (workers merge) without any scheduler
    intervention."""
    from hypha_tpu.worker.job_manager import Execution
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    class _Node:
        peer_id = "psw"

        def __init__(self):
            self.fail_left = 2
            self.requests = 0

        async def request(self, peer, protocol, msg, timeout=None):
            self.requests += 1
            if self.fail_left > 0:
                self.fail_left -= 1
                raise RequestError("scheduler down")
            return ProgressResponse(
                kind=ProgressResponseKind.OK, generation=2, round=1
            )

    node = _Node()
    ps = ParameterServerExecutor.__new__(ParameterServerExecutor)
    ps.node = node
    order: list[str] = []

    async def bcast():
        order.append("broadcast")

    async def parked():
        execution = Execution("job-1")
        resp = await ps._notify_updated_resilient(
            "sched", "job-1", 1, execution=execution, park_s=30.0,
            on_first_failure=bcast,
        )
        return execution, resp

    execution, resp = run(parked())
    order.append("notified")
    assert order == ["broadcast", "notified"]
    assert resp.kind == ProgressResponseKind.OK
    assert node.requests == 3  # two parked failures, then the answer
    assert execution.scheduler_generation == 2  # adopted from the stamp

    # park_s=0 (recovery off): single attempt, no broadcast hook, today's
    # fail-fast behavior.
    node2 = _Node()
    ps.node = node2
    with pytest.raises(RequestError):
        run(
            ps._notify_updated_resilient(
                "sched", "job-1", 1, park_s=0.0, on_first_failure=bcast
            )
        )
    assert node2.requests == 1


def test_stale_generation_updated_reply_is_retried():
    """A zombie scheduler's reply to an Updated must not drive the round
    machinery: the resilient notify drops it and re-sends until the live
    generation answers."""
    from hypha_tpu.worker.job_manager import Execution
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    class _Node:
        peer_id = "psw"

        def __init__(self):
            self.gens = [1, 1, 3]  # zombie, zombie, live successor

        async def request(self, peer, protocol, msg, timeout=None):
            return ProgressResponse(
                kind=ProgressResponseKind.DONE,
                generation=self.gens.pop(0), round=2,
            )

    ps = ParameterServerExecutor.__new__(ParameterServerExecutor)
    ps.node = _Node()

    async def parked():
        execution = Execution("job-1")
        execution.scheduler_generation = 2  # adopted via SchedulerHello
        resp = await ps._notify_updated_resilient(
            "sched", "job-1", 2, execution=execution, park_s=30.0
        )
        return execution, resp

    execution, resp = run(parked())
    assert resp.generation == 3
    assert execution.scheduler_generation == 3


# --------------------------------------------------------------------------
# orchestrator fallback: no adoptable journal -> fresh run path
# --------------------------------------------------------------------------


def test_resume_without_plan_raises_adoption_failed(tmp_path):
    from hypha_tpu.scheduler.job_config import DiLoCoJob
    from hypha_tpu.scheduler.orchestrator import AdoptionFailed

    job = DiLoCoJob(
        model={}, dataset="toy",
        checkpoint_dir=str(tmp_path),
        ft=FTConfig(),
        scheduler_recovery=True,
    )
    # Garbage journal: parses as empty, resume None.
    root = tmp_path / "scheduler"
    root.mkdir()
    (root / "sched-journal.cbor").write_bytes(b"\xff" * 64)
    orch = _mini_orchestrator(_FakeNode())
    with pytest.raises(AdoptionFailed):
        run(orch._resume_once(job))


# --------------------------------------------------------------------------
# full-cluster e2e: orchestrated DiLoCo job survives a scheduler kill
# --------------------------------------------------------------------------


@pytest.mark.fault
def test_kill_scheduler_e2e_bit_equal(tmp_path):
    """The acceptance scenario end to end (``tests/harness/ft_chaos.py``):
    3 workers + durable PS + durable scheduler,
    scheduler node killed mid-round and restarted under the same peer id.
    All rounds complete with zero full restarts, the restarted generation
    re-adopts every live execution, and the final weights are BIT-equal
    to a no-kill run of the identical blocking-f32 job."""
    from harness.ft_chaos import run_chaos_scenario

    line = run_chaos_scenario("kill-scheduler:2", rounds=3)
    assert line["rounds_completed"] == 3
    assert line["baseline_rounds"] == 3
    assert line["full_restarts"] == 0
    assert line["weights_bit_equal"] is True
    assert line["scheduler_recoveries"] >= 1
    assert line["adopted_executions"] >= 4  # 3 workers + the PS
    assert line["recovery_wall_s"] is None or line["recovery_wall_s"] < 30.0
