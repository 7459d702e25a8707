"""Unit tests for the fault-tolerance subsystem (hypha_tpu.ft).

Covers the φ-accrual math (monotonicity, re-heal), membership epochs,
quorum + deadline aggregation on the parameter server (k-of-n deltas →
correct sample-weighted mean), stale-delta rejection, early-delta parking,
the rejoin catch-up buffer, and the chaos controller's deterministic
triggers — all with fakes/injected clocks, no network.
"""

from __future__ import annotations

import asyncio
from pathlib import Path

import numpy as np
import pytest
from safetensors.numpy import load_file, save_file

from hypha_tpu.ft import (
    CatchupBuffer,
    ChaosAction,
    ChaosController,
    MembershipUpdate,
    MembershipView,
    PhiAccrualDetector,
    RoundMembership,
    await_catchup,
    parse_chaos_spec,
    quorum_size,
)
from hypha_tpu.messages import (
    AggregateExecutorConfig,
    Nesterov,
    Receive,
    Reference,
    Send,
    decode,
    encode,
)
from hypha_tpu.telemetry.ft_metrics import FT_METRICS
from hypha_tpu.worker.ps_executor import (
    ParameterServerExecutor,
    _ElasticState,
    _OuterMomentum,
)


# --------------------------------------------------------------------------
# φ-accrual detector
# --------------------------------------------------------------------------


def make_detector(threshold=8.0):
    t = [0.0]
    d = PhiAccrualDetector(threshold=threshold, clock=lambda: t[0])
    return d, t


def test_phi_unknown_peer_is_not_suspected():
    d, _ = make_detector()
    assert d.phi("ghost") == 0.0
    assert not d.suspected("ghost")


def test_phi_monotonically_grows_with_silence():
    d, t = make_detector()
    for i in range(20):
        t[0] = i * 0.1
        d.heartbeat("w")
    last_beat = t[0]
    phis = []
    for silence in (0.05, 0.2, 0.5, 1.0, 2.0, 5.0):
        t[0] = last_beat + silence
        phis.append(d.phi("w"))
    assert all(b >= a for a, b in zip(phis, phis[1:])), phis
    assert phis[0] < 1.0  # within one expected interval: not suspicious
    assert phis[-1] > 8.0  # 50 intervals of silence: very suspicious


def test_phi_threshold_crossing_and_reheal_on_heartbeat():
    d, t = make_detector(threshold=8.0)
    for i in range(10):
        t[0] = i * 0.1
        d.heartbeat("w")
    t[0] = 0.9 + 5.0
    assert d.suspected("w")
    d.heartbeat("w")  # the peer speaks again
    t[0] += 0.05
    assert not d.suspected("w")
    assert d.phi("w") < 1.0


def test_phi_irregular_heartbeats_widen_tolerance():
    """A naturally jittery peer needs longer silence to look dead."""
    regular, tr = make_detector()
    jittery, tj = make_detector()
    beats_r = [i * 1.0 for i in range(10)]
    beats_j = [0, 0.2, 2.8, 3.0, 5.9, 6.0, 8.9, 9.1, 11.8, 12.2]
    for ts in beats_r:
        tr[0] = ts
        regular.heartbeat("w")
    for ts in beats_j:
        tj[0] = ts
        jittery.heartbeat("w")
    silence = 3.0
    tr[0] = beats_r[-1] + silence
    tj[0] = beats_j[-1] + silence
    assert regular.phi("w") > jittery.phi("w")


def test_detector_remove_and_levels():
    d, t = make_detector()
    d.heartbeat("a")
    d.heartbeat("b")
    assert set(d.suspicion_levels()) == {"a", "b"}
    d.remove("a")
    assert d.peers() == ["b"]


# --------------------------------------------------------------------------
# membership + wire
# --------------------------------------------------------------------------


def test_quorum_size_math():
    assert quorum_size(0.75, 4) == 3
    assert quorum_size(0.75, 3) == 3
    assert quorum_size(0.5, 4) == 2
    assert quorum_size(0.5, 1) == 1
    assert quorum_size(0.0, 4) == 1  # floor: never zero
    assert quorum_size(1.0, 4) == 4
    assert quorum_size(0.75, 0) == 1


def test_membership_view_epoch_bumps():
    view = MembershipView(["a", "b", "c"])
    assert view.epoch == 0
    assert view.suspect("b") and view.epoch == 1
    assert not view.suspect("b")  # idempotent: no bump
    assert view.epoch == 1
    assert view.reinstate("b") and view.epoch == 2
    assert view.depart("c") and view.epoch == 3
    assert view.join("d") and view.epoch == 4
    snap = view.snapshot()
    assert snap.active == ["a", "b", "d"]
    assert snap.departed == ["c"]
    assert snap.expected() == {"a", "b", "d"}


def test_membership_update_wire_roundtrip():
    msg = MembershipUpdate(
        job_id="job-1",
        membership=RoundMembership(
            epoch=7, active=["a", "b"], suspected=["b"], departed=["c"]
        ),
        joined=["d"],
    )
    back = decode(encode(msg))
    assert back.job_id == "job-1"
    assert back.membership.epoch == 7
    assert back.membership.suspected == ["b"]
    assert back.joined == ["d"]


# --------------------------------------------------------------------------
# quorum aggregation on the parameter server
# --------------------------------------------------------------------------


class FakePush:
    def __init__(self, peer: str, resource: dict, tree: dict):
        self.peer = peer
        self.resource = resource
        self._tree = tree
        self.drained = False

    async def save_to(self, dest, hasher=None):
        save_file(self._tree, str(dest))
        if hasher is not None:
            hasher.update(Path(dest).read_bytes())
        return 1

    async def read_all(self):
        self.drained = True
        return b""

    def finish(self):
        pass


class FakeConsumer:
    def __init__(self, pushes: list[FakePush]):
        self._pushes = list(pushes)

    async def next(self, timeout=None):
        if self._pushes:
            return self._pushes.pop(0)
        await asyncio.sleep(min(timeout or 0.01, 0.01))
        raise asyncio.TimeoutError

    def close(self):
        pass


def elastic_cfg(peers, quorum_fraction=0.75, round_deadline_s=0.4):
    return AggregateExecutorConfig(
        updates=Receive(Reference.from_peers(list(peers), "u")),
        results=Send(Reference.from_peers(list(peers), "r")),
        optimizer=Nesterov(lr=0.7, momentum=0.9),
        num_workers=len(peers),
        quorum_fraction=quorum_fraction,
        round_deadline_s=round_deadline_s,
    )


def delta_push(peer, round_num, value, samples):
    return FakePush(
        peer,
        {"resource": "u", "name": f"d-{peer}", "round": round_num,
         "num_samples": samples},
        {"w": np.full((3,), value, np.float32)},
    )


def run(coro, timeout=15):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def test_quorum_aggregation_closes_at_deadline_with_3_of_4(tmp_path):
    peers = ["w0", "w1", "w2", "w3"]
    cfg = elastic_cfg(peers)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    before = FT_METRICS.degraded_rounds.value()
    consumer = FakeConsumer(
        [delta_push(p, 0, v, s) for p, v, s in
         [("w0", 1.0, 10.0), ("w1", 2.0, 20.0), ("w2", 3.0, 10.0)]]
    )  # w3 never reports
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0)
    )
    assert set(received) == {"w0", "w1", "w2"}
    assert FT_METRICS.degraded_rounds.value() == before + 1

    # k-of-n sample-weighted mean over the deltas that DID arrive:
    # weights 10,20,10 → ḡ = (1·10 + 2·20 + 3·10)/40 = 2.0; zero momentum
    # Nesterov: m=ḡ, update = lr·(μ·ḡ + ḡ) = 0.7·1.9·2.0 = 2.66.
    out = ps._outer_step(
        received, _OuterMomentum(tmp_path / "momentum.safetensors", save=False),
        0.7, 0.9, tmp_path, 0,
    )
    update = load_file(str(out.ensure_file()))["w"]
    np.testing.assert_allclose(update, np.full((3,), 0.7 * 1.9 * 2.0), rtol=1e-6)


def test_all_active_reported_closes_before_deadline(tmp_path):
    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=30.0)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    consumer = FakeConsumer(
        [delta_push("w0", 0, 1.0, 1.0), delta_push("w1", 0, 2.0, 1.0)]
    )
    # Would hang for 30 s if the all-reported close condition were broken.
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0),
        timeout=5,
    )
    assert set(received) == {"w0", "w1"}


def test_stale_delta_rejected_and_counted(tmp_path):
    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=0.3)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    before = FT_METRICS.stale_deltas_dropped.value()
    stale = delta_push("w0", 0, 9.0, 1.0)  # for round 0 — but we collect 1
    fresh = delta_push("w1", 1, 2.0, 1.0)
    consumer = FakeConsumer([stale, fresh])
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 1)
    )
    assert set(received) == {"w1"}
    assert stale.drained  # stream released, file never written
    assert FT_METRICS.stale_deltas_dropped.value() == before + 1


def test_early_delta_parked_and_credited_to_its_round(tmp_path):
    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=0.3)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    early = delta_push("w0", 1, 5.0, 1.0)  # already at round 1
    now = delta_push("w1", 0, 2.0, 1.0)
    received0 = run(
        ps._collect_round_elastic(FakeConsumer([early, now]), "job", st, cfg, tmp_path, 0)
    )
    assert set(received0) == {"w1"}
    assert 1 in st.early and "w0" in st.early[1]
    received1 = run(
        ps._collect_round_elastic(
            FakeConsumer([delta_push("w1", 1, 1.0, 1.0)]), "job", st, cfg, tmp_path, 1
        )
    )
    assert set(received1) == {"w0", "w1"}  # parked delta pre-credited


def test_elastic_duplicate_resend_replaces_cleanly(tmp_path):
    """A re-sent delta lands on the SAME deterministic path as the first
    (delta-{round}-{sha(peer)}), so the replace must retire the old entry
    BEFORE saving — the un-fold/unlink-after-save ordering crashed the PS
    on the very double-send the guard exists to tolerate (review r6)."""
    from hypha_tpu.worker.ps_executor import _RoundAccum

    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=0.3)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    accum = _RoundAccum()
    consumer = FakeConsumer(
        [
            delta_push("w0", 0, 1.0, 10.0),  # superseded
            delta_push("w0", 0, 5.0, 10.0),  # the re-send that must win
            delta_push("w1", 0, 3.0, 10.0),
        ]
    )
    received = run(
        ps._collect_round_elastic(
            consumer, "job", st, cfg, tmp_path, 0, accum=accum
        )
    )
    assert set(received) == {"w0", "w1"}
    assert received["w0"][0].is_file()  # the replacement survived on disk
    assert accum.folds == 2
    # Fold accounting: (5·10 + 3·10)/20 = 4.0, no trace of the first send.
    np.testing.assert_allclose(accum.mean()["w"], np.full(3, 4.0), rtol=1e-6)
    out = ps._outer_step(
        received, _OuterMomentum(tmp_path / "m.st", save=False),
        0.7, 0.9, tmp_path, 0, accum,
    )
    np.testing.assert_allclose(
        load_file(str(out.ensure_file()))["w"], np.full(3, 0.7 * 1.9 * 4.0), rtol=1e-6
    )


def test_elastic_duplicate_early_delta_parks_latest(tmp_path):
    """Same path-collision hazard for the early-park bucket: a double-sent
    future-round delta must leave a live file parked, not a dangling path."""
    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=0.3)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    received0 = run(
        ps._collect_round_elastic(
            FakeConsumer(
                [
                    delta_push("w0", 1, 1.0, 1.0),  # early, superseded
                    delta_push("w0", 1, 7.0, 1.0),  # early re-send wins
                    delta_push("w1", 0, 2.0, 1.0),
                ]
            ),
            "job", st, cfg, tmp_path, 0,
        )
    )
    assert set(received0) == {"w1"}
    parked = st.early[1]["w0"]
    assert parked[0].is_file()
    np.testing.assert_allclose(load_file(str(parked[0]))["w"], np.full(3, 7.0))


def test_non_member_push_dropped(tmp_path):
    peers = ["w0", "w1"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=0.3)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    intruder = delta_push("evil", 0, 100.0, 1.0)
    ok = delta_push("w0", 0, 1.0, 1.0)
    received = run(
        ps._collect_round_elastic(FakeConsumer([intruder, ok]), "job", st, cfg, tmp_path, 0)
    )
    assert set(received) == {"w0"}
    assert intruder.drained


def test_membership_shrink_closes_round_without_deadline(tmp_path):
    """Adopting a departed-peer membership closes the round at the next poll
    tick — no need to sit out the full deadline."""
    peers = ["w0", "w1", "w2"]
    cfg = elastic_cfg(peers, quorum_fraction=0.5, round_deadline_s=30.0)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)

    async def scenario():
        consumer = FakeConsumer(
            [delta_push("w0", 0, 1.0, 1.0), delta_push("w1", 0, 2.0, 1.0)]
        )
        collect = asyncio.create_task(
            ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0)
        )
        await asyncio.sleep(0.2)
        assert not collect.done()  # still waiting for w2
        st.adopt(
            MembershipUpdate(
                job_id="job",
                membership=RoundMembership(
                    epoch=1, active=["w0", "w1"], departed=["w2"]
                ),
            )
        )
        return await asyncio.wait_for(collect, timeout=5)

    received = run(scenario())
    assert set(received) == {"w0", "w1"}


# --------------------------------------------------------------------------
# rejoin catch-up
# --------------------------------------------------------------------------


def test_catchup_buffer_accumulates_updates(tmp_path):
    u1 = tmp_path / "u1.safetensors"
    u2 = tmp_path / "u2.safetensors"
    save_file({"w": np.array([1.0, 2.0], np.float32)}, str(u1))
    save_file({"w": np.array([0.5, -1.0], np.float32)}, str(u2))
    buf = CatchupBuffer()
    assert buf.is_empty()
    buf.accumulate(u1)
    buf.accumulate(u2)
    assert buf.rounds == 2
    out = buf.write(tmp_path / "cum.safetensors")
    cum = load_file(str(out))
    np.testing.assert_allclose(cum["w"], [1.5, 1.0])


def test_catchup_buffer_empty_write_is_valid(tmp_path):
    buf = CatchupBuffer()
    out = buf.write(tmp_path / "cum.safetensors")
    assert load_file(str(out)) == {}


def test_await_catchup_skips_regular_updates():
    events = iter(
        [
            {"path": "a", "meta": {"round": 3}},
            {"path": "b", "meta": None},
            {"path": "c", "meta": {"round": 4, "catchup": True, "epoch": 2}},
        ]
    )
    skipped = []
    got = await_catchup(events, on_skip=skipped.append)
    assert got["path"] == "c"
    assert [e["path"] for e in skipped] == ["a", "b"]


def test_await_catchup_raises_on_stream_end():
    with pytest.raises(RuntimeError, match="catch-up"):
        await_catchup(iter([{"path": "a", "meta": {}}]))


# --------------------------------------------------------------------------
# chaos controller
# --------------------------------------------------------------------------


class FakeWorker:
    def __init__(self):
        self.stopped = False
        self.node = type("N", (), {})()

    async def stop(self):
        self.stopped = True


def test_chaos_kill_fires_at_round_trigger():
    async def scenario():
        w = FakeWorker()
        ctl = ChaosController(
            [ChaosAction(kind="kill", target="w1", at_round=2)], {"w1": w}
        )
        hook = ctl.metrics_hook()
        hook("w1", 0, {})  # round 0 done -> round 1 running: no fire
        await asyncio.sleep(0)
        assert not w.stopped and not ctl.fired
        hook("w1", 1, {})  # round 1 done -> round 2 running: FIRE
        await ctl.drain()
        assert w.stopped
        assert ctl.fired_at("w1") is not None

    run(scenario())


def test_chaos_fires_once_and_chains_inner_hook():
    async def scenario():
        w = FakeWorker()
        seen = []
        ctl = ChaosController(
            [ChaosAction(kind="kill", target="w1", at_round=1)], {"w1": w}
        )
        hook = ctl.metrics_hook(lambda p, r, m: seen.append((p, r)))
        hook("w1", 0, {})
        hook("w1", 1, {})
        await ctl.drain()
        assert len(ctl.fired) == 1
        assert seen == [("w1", 0), ("w1", 1)]

    run(scenario())


def test_parse_chaos_spec():
    a = parse_chaos_spec("kill-worker:2", "wX")
    assert (a.kind, a.target, a.at_round) == ("kill", "wX", 2)
    d = parse_chaos_spec("delay-worker:1:0.25", "wY")
    assert (d.kind, d.at_round, d.delay_s) == ("delay", 1, 0.25)
    k = parse_chaos_spec("kill-ps:2", "psw")
    assert (k.kind, k.target, k.at_round) == ("kill-ps", "psw", 2)
    p = parse_chaos_spec("partition-ps:1:2.5", "psw")
    assert (p.kind, p.at_round, p.delay_s) == ("partition-ps", 1, 2.5)
    with pytest.raises(ValueError):
        parse_chaos_spec("explode:1", "w")


def test_chaos_partition_ps_severs_and_heals():
    """partition-ps drops pushes between the PS and the workers for the
    configured duration, both directions, then restores the originals."""
    from hypha_tpu.network.node import RequestError

    async def scenario():
        class Node_:
            def __init__(self):
                self.sent = []

            async def push(self, peer_id, resource, source):
                self.sent.append(peer_id)
                return 1

        class W:
            def __init__(self):
                self.node = Node_()

        ps, w1 = W(), W()
        ctl = ChaosController(
            [ChaosAction(kind="partition-ps", target="psw", at_round=0,
                         delay_s=0.2)],
            {"psw": ps, "w1": w1},
        )
        async def probe_once(node, target):
            # Deliberate single-attempt probe: the assertion IS whether
            # this exact push lands under the chaos schedule.
            await node.push(target, {}, b"")

        with pytest.raises(RequestError):
            await w1.node.push("psw", {}, b"")  # worker -> PS dropped
        with pytest.raises(RequestError):
            await ps.node.push("w1", {}, b"")  # PS broadcast dropped
        await probe_once(w1.node, "other")  # unrelated peers unaffected
        await asyncio.sleep(0.4)
        await ctl.drain()
        await probe_once(w1.node, "psw")  # healed
        assert w1.node.sent == ["other", "psw"]

    run(scenario())


# --------------------------------------------------------------------------
# durable-PS telemetry (ft.durable satellites)
# --------------------------------------------------------------------------


def test_ft_metrics_snapshot_carries_durable_counters():
    FT_METRICS.reset()
    FT_METRICS.retry_attempts.add(3)
    FT_METRICS.ps_journal_bytes.add(512)
    FT_METRICS.ps_recoveries.add(1)
    snap = FT_METRICS.snapshot()
    assert snap["retry_attempts"] == 3
    assert snap["ps_journal_bytes"] == 512
    assert snap["ps_recoveries"] == 1
    FT_METRICS.reset()


def test_register_on_exports_durable_counters():
    from hypha_tpu.telemetry.ft_metrics import FTMetrics, register_on

    class SpyMeter:  # duck-typed: register_on only needs observable_gauge
        def __init__(self):
            self.gauges = {}

        def observable_gauge(self, name, callback, unit=""):
            self.gauges[name] = callback

    metrics = FTMetrics()
    metrics.retry_attempts.add(2)
    metrics.ps_journal_bytes.add(64)
    metrics.ps_recoveries.add(1)
    meter = SpyMeter()
    register_on(meter, metrics)
    assert meter.gauges["hypha.ft.retry_attempts"]() == 2
    assert meter.gauges["hypha.ps.journal_bytes"]() == 64
    assert meter.gauges["hypha.ps.recoveries"]() == 1
