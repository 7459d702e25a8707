"""WAN-adaptive outer rounds (hypha_tpu.ft.adaptive + chaos degrade modes).

Coverage map (ISSUE 9 satellites):

  * EWMA straggler controller under a deterministic fake clock — the
    4x-slower worker's assignment shrinks toward ~k/4 while the median
    peers keep the base count, quorum-dropped peers keep shrinking;
  * per-peer codec roundtrip with DISJOINT error-feedback residuals —
    two links on different codecs each track the true f32 sum to within
    their own final residual (the EF invariant), from one PS-side
    per-link broadcast;
  * adaptive-off bit-exactness — the new knobs default to wire-invisible
    (no new encoded fields, no new header keys, collectors byte-identical
    to the PR 8 call shape);
  * chaos degrade determinism — multi-spec parsing, bandwidth caps the
    RECEIVER can measure mid-stream, slow-CPU factor stretching the
    Status round-trip;
  * quorum-drop-vs-adapt at the parameter-server collector (tier-1) and
    a full orchestrated 4-worker e2e under a 4x slow + bandwidth-capped
    pool (slow-marked; the pool is ``tests/harness/hetbench.py``).
"""

from __future__ import annotations

import asyncio
import time
from pathlib import Path

import numpy as np
import pytest
from safetensors.numpy import save_file

from hypha_tpu import compress, messages
from hypha_tpu.ft import LinkTable, StragglerController, parse_chaos_specs
from hypha_tpu.ft.adaptive import Ewma
from hypha_tpu.ft.chaos import ChaosController, parse_chaos_spec
from hypha_tpu.ft.membership import RoundMembership
from hypha_tpu.messages import (
    CODEC_KEY,
    AggregateExecutorConfig,
    Nesterov,
    Progress,
    ProgressKind,
    ProgressResponseKind,
    Receive,
    Reference,
    Send,
)
from hypha_tpu.scheduler.batch_scheduler import BatchScheduler
from hypha_tpu.scheduler.trackers import ProgressTracker
from hypha_tpu.telemetry.ft_metrics import HET_METRICS, register_on
from hypha_tpu.worker.ps_executor import (
    ParameterServerExecutor,
    _ElasticState,
    _OuterMomentum,
)


def run(coro, timeout=20):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# --------------------------------------------------------------------------
# EWMA + straggler controller (deterministic fake clock)
# --------------------------------------------------------------------------


def test_ewma_update_and_scale():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(1.0) == 1.0
    assert e.update(3.0) == pytest.approx(2.0)
    e.scale(2.0)
    assert e.value == pytest.approx(4.0)


def test_controller_assigns_base_without_history():
    clk = {"t": 0.0}
    ctrl = StragglerController(base_steps=8, clock=lambda: clk["t"])
    assert ctrl.counter_for("w0") == 8
    ctrl.note_batch("w0")
    # One batch already run: the remaining countdown shrinks by one.
    assert ctrl.counter_for("w0") == 7


def test_controller_scales_slow_worker_to_quarter():
    """A 4x slower worker lands at ~base/4 next round; the median peers
    keep the base count (cadence tracks the MEDIAN, not the slowest)."""
    clk = {"t": 0.0}
    ctrl = StragglerController(
        base_steps=8, warmup_rounds=0, clock=lambda: clk["t"]
    )
    peers = ["w0", "w1", "w2", "w3"]
    for p in peers:
        ctrl.counter_for(p)  # freeze round 0 assignments at base
    # Round 0 closed: three peers at 0.1 s/step, w3 at 0.4 s/step
    # (arrival lag = steps * per-step cost: 8*0.1 vs 8*0.4).
    ctrl.note_round_closed(0, {"w0": 0.8, "w1": 0.8, "w2": 0.8, "w3": 3.2})
    ctrl.start_round(1, peers)
    a = ctrl.assignments()
    assert a["w0"] == a["w1"] == a["w2"] == 8
    assert a["w3"] == 2  # round(8 * median(0.1) / 0.4)
    # Countdown accounting composes with batches already run.
    ctrl.note_batch("w3")
    assert ctrl.counter_for("w3") == 1


def test_controller_penalizes_dropped_worker():
    """An assigned peer whose delta never arrived gets its estimate scaled
    by drop_penalty, so its assignment keeps shrinking until it lands."""
    clk = {"t": 0.0}
    ctrl = StragglerController(
        base_steps=8, warmup_rounds=0, clock=lambda: clk["t"],
        drop_penalty=2.0,
    )
    peers = ["w0", "w1", "w2", "w3"]
    for p in peers:
        ctrl.counter_for(p)
    ctrl.note_round_closed(0, {"w0": 0.8, "w1": 0.8, "w2": 0.8, "w3": 3.2})
    ctrl.start_round(1, peers)
    first = ctrl.assignments()["w3"]
    # Round 1 closes WITHOUT w3 (dropped): estimate doubles -> steps halve.
    ctrl.note_round_closed(1, {"w0": 0.8, "w1": 0.8, "w2": 0.8})
    ctrl.start_round(2, peers)
    second = ctrl.assignments()["w3"]
    assert second < first
    # Stale re-notifies (a recovered PS re-sending an old round) are inert.
    before = ctrl.assignments()
    ctrl.note_round_closed(0, {"w3": 0.01})
    assert ctrl.assignments() == before


def test_controller_warmup_skips_compile_poisoned_round():
    """Round 0's arrival lags are dominated by one-time jit compile; the
    default warmup skips them so everyone doesn't look equally slow."""
    clk = {"t": 0.0}
    ctrl = StragglerController(base_steps=8, clock=lambda: clk["t"])
    for p in ("a", "b"):
        ctrl.counter_for(p)
    ctrl.note_round_closed(0, {"a": 16.0, "b": 16.1})  # compile noise
    ctrl.start_round(1, ["a", "b"])
    assert ctrl.assignments() == {"a": 8, "b": 8}
    assert ctrl._estimate("a") is None  # nothing was fed


def test_controller_cadence_floor_defeats_headstart_masking():
    """A worker that starts its round during the previous broadcast can
    land with ~zero arrival lag no matter how slow its CPU is; the
    scheduler-observed batch cadence is the floor that cannot be masked."""
    clk = {"t": 0.0}
    ctrl = StragglerController(
        base_steps=8, warmup_rounds=0, clock=lambda: clk["t"]
    )
    # Batch cadence: three peers at 0.05 s/batch, one 4x slower at 0.2.
    cadences = {"f0": 0.05, "f1": 0.05, "f2": 0.05, "slow": 0.2}
    for peer, dt in cadences.items():
        clk["t"] = 0.0
        ctrl.note_batch(peer)
        for _ in range(4):
            clk["t"] += dt
            ctrl.note_batch(peer)
    # Arrival lags near zero for EVERYONE (head-start masking).
    ctrl.note_round_closed(0, {p: 0.01 for p in cadences})
    ctrl.start_round(1, list(cadences))
    a = ctrl.assignments()
    assert a["f0"] == a["f1"] == a["f2"] == 8
    assert a["slow"] == 2  # 8 * median(0.05) / 0.2


def test_controller_never_assigns_below_min_steps():
    clk = {"t": 0.0}
    ctrl = StragglerController(
        base_steps=4, min_steps=1, warmup_rounds=0, clock=lambda: clk["t"]
    )
    for p in ("a", "b", "c"):
        ctrl.counter_for(p)
    ctrl.note_round_closed(0, {"a": 0.4, "b": 0.4, "c": 400.0})
    ctrl.start_round(1, ["a", "b", "c"])
    assert ctrl.assignments()["c"] == 1


# --------------------------------------------------------------------------
# batch scheduler integration
# --------------------------------------------------------------------------


def _status(peer_batch: int = 4) -> Progress:
    return Progress(kind=ProgressKind.STATUS, job_id="j", batch_size=peer_batch)


def test_batch_scheduler_adaptive_schedules_immediately():
    clk = {"t": 0.0}
    tracker = ProgressTracker(
        parameter_server="ps", update_target=32, update_epochs=2,
        clock=lambda: clk["t"],
    )
    tracker.add_worker("w0", 4)
    tracker.add_worker("w1", 4)
    ctrl = StragglerController(
        base_steps=4, warmup_rounds=0, clock=lambda: clk["t"]
    )
    sched = BatchScheduler(tracker, adaptive=ctrl)
    resp = sched.on_progress("w0", _status())
    assert resp.kind == ProgressResponseKind.SCHEDULE_UPDATE
    assert resp.counter == 3  # 4 assigned, 1 batch already reported
    # The PS's Updated carries per-peer arrival lags; the round advances
    # and the next round's assignments reflect the 4x straggler.
    updated = Progress(
        kind=ProgressKind.UPDATED, job_id="j", round=0,
        metrics={"arrival_s": {"w0": 0.4, "w1": 1.6}},
    )
    resp = sched.on_progress("ps", updated)
    assert resp.kind == ProgressResponseKind.OK
    assert tracker.round == 1
    assert ctrl.round == 1
    a = {p: ctrl.steps_for(p) for p in ("w0", "w1")}
    assert a["w1"] < a["w0"]


def test_batch_scheduler_without_adaptive_unchanged():
    """adaptive=None keeps the reference projection path: no stats yet ->
    CONTINUE, never an immediate SCHEDULE_UPDATE."""
    tracker = ProgressTracker(
        parameter_server="ps", update_target=32, update_epochs=2
    )
    tracker.add_worker("w0", 4)
    sched = BatchScheduler(tracker)
    resp = sched.on_progress("w0", _status())
    assert resp.kind == ProgressResponseKind.CONTINUE


# --------------------------------------------------------------------------
# link table + per-peer codec roundtrip (disjoint EF residuals)
# --------------------------------------------------------------------------


def test_codec_for_bandwidth_ladder():
    assert compress.codec_for_bandwidth(200e6, "bf16", 100e6, 10e6) == "bf16"
    assert compress.codec_for_bandwidth(50e6, "bf16", 100e6, 10e6) == "int8"
    assert compress.codec_for_bandwidth(1e6, "bf16", 100e6, 10e6) == "int4"
    # Never upgrades past the base codec's bit width.
    assert compress.codec_for_bandwidth(50e6, "int4", 100e6, 10e6) == "int4"
    assert compress.codec_for_bandwidth(200e6, "int8", 100e6, 10e6) == "int8"


def test_link_table_measures_and_selects():
    HET_METRICS.reset()
    table = LinkTable(base_codec="bf16", hi_mbps=100.0, lo_mbps=10.0)
    assert not table.measured("w0")
    assert table.codec_for("w0") == "bf16"  # unmeasured: benefit of doubt
    # 1 MB in 10 ms = 800 Mbit/s -> fast link keeps the base codec.
    table.observe("w0", 1_000_000, 0.010)
    assert table.measured("w0")
    assert table.codec_for("w0") == "bf16"
    # 100 KB in 1 s = 0.8 Mbit/s -> slowest tier.
    table.observe("w1", 100_000, 1.0)
    assert table.codec_for("w1") == "int4"
    snap = HET_METRICS.snapshot()
    assert snap["bandwidth_bps"]["w0"] > snap["bandwidth_bps"]["w1"]
    assert snap["peer_codecs"] == {"w0": "bf16", "w1": "int4"}


class SpyNode:
    """Captures PS broadcast pushes: (peer, header, payload bytes)."""

    def __init__(self) -> None:
        self.pushes: list[tuple[str, dict, bytes]] = []

    async def push(self, peer: str, header: dict, source) -> int:
        data = Path(source).read_bytes()
        self.pushes.append((peer, dict(header), data))
        return len(data)


def _plain_cfg(peers):
    return AggregateExecutorConfig(
        updates=Receive(Reference.from_peers(list(peers), "u")),
        results=Send(Reference.from_peers(list(peers), "r")),
        optimizer=Nesterov(lr=0.7, momentum=0.9),
        num_workers=len(peers),
    )


def test_per_peer_codec_roundtrip_disjoint_ef(tmp_path):
    """One adaptive broadcast per round, three rounds: the fast link ships
    the base codec, the slow link int4 with its OWN residual — each link's
    cumulative decoded sum equals the true f32 sum minus that link's final
    residual (the EF invariant), and the residuals are disjoint objects."""
    HET_METRICS.reset()
    node = SpyNode()
    ps = ParameterServerExecutor(node=node, work_root=tmp_path)
    cfg = _plain_cfg(["fast", "slow"])
    table = LinkTable(base_codec="none", hi_mbps=100.0, lo_mbps=10.0)
    table.observe("fast", 1_000_000, 0.010)  # 800 Mbit/s
    table.observe("slow", 100_000, 1.0)  # 0.8 Mbit/s
    peer_efs: dict = {}
    rng = np.random.default_rng(7)
    true_sum = np.zeros((64,), np.float32)
    decoded_sums = {"fast": np.zeros((64,), np.float32),
                    "slow": np.zeros((64,), np.float32)}
    for rnd in range(3):
        update = rng.standard_normal(64).astype(np.float32)
        true_sum += update
        path = tmp_path / f"update-{rnd}.safetensors"
        save_file({"w": update}, str(path))
        node.pushes.clear()
        run(
            ps._broadcast_adaptive(
                cfg, path, rnd, None, table, peer_efs, tmp_path
            )
        )
        assert len(node.pushes) == 2
        for peer, header, payload in node.pushes:
            expect = "none" if peer == "fast" else "int4"
            assert header[CODEC_KEY] == expect
            assert header["round"] == rnd
            wire = tmp_path / f"got-{peer}.bin"
            wire.write_bytes(payload)
            decoded_sums[peer] += compress.read_delta(wire)["w"]
    # Fast link is uncompressed: exact.
    np.testing.assert_array_equal(decoded_sums["fast"], true_sum)
    # Slow link: Σ decoded = Σ true − final residual, to f32 rounding.
    assert set(peer_efs) == {"slow"}  # only the quantized link holds one
    residual = peer_efs["slow"].state()["w"]
    np.testing.assert_allclose(
        decoded_sums["slow"] + residual, true_sum, rtol=1e-5, atol=1e-5
    )
    assert HET_METRICS.snapshot()["codec_counts"]["int4"] >= 3


# --------------------------------------------------------------------------
# adaptive-off bit-exactness (the PR 8 wire and call shape)
# --------------------------------------------------------------------------


def test_adaptive_off_ships_todays_wire():
    """Static configs encode with NO new fields and membership snapshots
    with NO inner_steps key — `adaptive_steps: off` is byte-compatible."""
    enc = messages.encode(RoundMembership(epoch=3, active=["a", "b"]))
    assert b"inner_steps" not in enc
    cfg = _plain_cfg(["a"])
    enc_cfg = messages.encode(cfg)
    for key in (
        b"adaptive_steps", b"adaptive_codec",
        b"codec_bw_hi_mbps", b"codec_bw_lo_mbps",
    ):
        assert key not in enc_cfg
    # A non-adaptive PS's Updated progress carries no arrival report.
    updated = Progress(kind=ProgressKind.UPDATED, job_id="j", round=1)
    assert b"arrival_s" not in messages.encode(updated)
    # And round-trips still hold with the fields populated.
    rm = RoundMembership(epoch=4, active=["a"], inner_steps={"a": 3})
    assert messages.decode(messages.encode(rm)) == rm


def test_collector_defaults_bit_exact_with_explicit_none(tmp_path):
    """The new link/arrivals collector params default to the exact PR 8
    behavior: same pushes, same update bytes, with or without them."""
    from tests.test_ft import FakeConsumer, delta_push, elastic_cfg

    outs = []
    for explicit in (False, True):
        sub = tmp_path / ("b" if explicit else "a")
        sub.mkdir()
        cfg = elastic_cfg(["w0", "w1"], quorum_fraction=0.5,
                          round_deadline_s=5.0)
        st = _ElasticState(cfg, "sched")
        ps = ParameterServerExecutor(node=None, work_root=sub)
        consumer = FakeConsumer(
            [delta_push("w0", 0, 1.5, 10.0), delta_push("w1", 0, 0.5, 30.0)]
        )
        kwargs = {"link": None, "arrivals": None} if explicit else {}
        received = run(
            ps._collect_round_elastic(
                consumer, "job", st, cfg, sub, 0, **kwargs
            )
        )
        out = ps._outer_step(
            received, _OuterMomentum(sub / "momentum.safetensors", save=False),
            0.7, 0.9, sub, 0,
        )
        outs.append(out.ensure_file().read_bytes())
    assert outs[0] == outs[1]


# --------------------------------------------------------------------------
# chaos degrade modes
# --------------------------------------------------------------------------


def test_parse_chaos_specs_composes_and_is_deterministic():
    specs = "kill-worker:2,bw-cap:w1:10,slow-worker:4,jitter:w2:0.5"
    a = parse_chaos_specs(specs, "w9")
    b = parse_chaos_specs(specs, "w9")
    assert [(x.kind, x.target, x.at_round) for x in a] == [
        ("kill", "w9", 2),
        ("bw-cap", "w1", 0),
        ("slow", "w9", 0),
        ("jitter", "w2", 0),
    ]
    assert [(x.kind, x.target) for x in a] == [(x.kind, x.target) for x in b]
    assert a[1].rate_bps == pytest.approx(10e6)
    assert a[2].factor == pytest.approx(4.0)
    assert a[3].delay_s == pytest.approx(0.5)
    # Inline peer form for slow-worker; single-spec parse still works.
    s = parse_chaos_spec("slow-worker:w5:2.5", "w0")
    assert (s.kind, s.target, s.factor) == ("slow", "w5", 2.5)
    with pytest.raises(ValueError):
        parse_chaos_spec("bw-cap:10", "w0")  # a cap must name its peer
    with pytest.raises(ValueError):
        parse_chaos_specs(" , ", "w0")


class _CapNode:
    """Receiver-side view of a push: drains the source, timing it."""

    def __init__(self) -> None:
        self.transfers: list[tuple[str, int, float]] = []

    async def push(self, peer_id: str, resource, source) -> int:
        t0 = time.monotonic()
        total = 0
        if isinstance(source, (bytes, bytearray)):
            total = len(source)
        elif hasattr(source, "__aiter__"):
            async for chunk in source:
                total += len(chunk)
        else:  # un-throttled file path (the pass-through case)
            total = Path(source).stat().st_size
        self.transfers.append((peer_id, total, time.monotonic() - t0))
        return total


class _FakeWorker:
    def __init__(self, node) -> None:
        self.node = node

    async def stop(self) -> None:  # pragma: no cover - not killed here
        pass


def test_bw_cap_throttles_mid_stream(tmp_path):
    """The cap is visible DURING the transfer (the receiver's drain takes
    ~bytes/rate) — the property the PS LinkTable measurement rests on."""
    payload = tmp_path / "delta.bin"
    payload.write_bytes(b"x" * 65536)  # 64 KiB = 0.524 Mbit

    async def main():
        node = _CapNode()
        workers = {"w1": _FakeWorker(node)}
        actions = parse_chaos_specs("bw-cap:w1:1", "w1")  # 1 Mbit/s
        ChaosController(actions, workers)
        async def timed_push_once():
            # Single timed attempt — the bw-cap drain IS the measurement.
            return await node.push("ps", {"resource": "u"}, payload)

        t0 = time.monotonic()
        n = await timed_push_once()
        elapsed = time.monotonic() - t0
        assert n == 65536
        # 0.524 Mbit at 1 Mbit/s ≥ ~0.5 s, and the drain itself saw it.
        assert elapsed >= 0.4
        assert node.transfers[0][2] >= 0.4

    run(main())


def test_bw_cap_is_bidirectional(tmp_path):
    """Pushes TOWARD the capped peer (the PS broadcast direction) are
    throttled too."""
    payload = tmp_path / "update.bin"
    payload.write_bytes(b"y" * 32768)  # 32 KiB = 0.262 Mbit

    async def main():
        capped = _CapNode()
        other = _CapNode()
        workers = {"w1": _FakeWorker(capped), "psw": _FakeWorker(other)}
        ChaosController(parse_chaos_specs("bw-cap:w1:1", "w1"), workers)
        t0 = time.monotonic()
        await other.push("w1", {"resource": "r"}, payload)
        toward_capped = time.monotonic() - t0
        t0 = time.monotonic()
        await other.push("w2", {"resource": "r"}, payload)
        toward_free = time.monotonic() - t0
        assert toward_capped >= 0.2
        assert toward_free < 0.1

    run(main())


def test_slow_worker_stretches_status_cadence():
    """slow-worker:<x> makes the per-batch Status round-trip ~x× the
    natural compute gap — the genuine slow-CPU signal every observer
    (scheduler timing stats, round deadline) keys on."""
    from hypha_tpu.messages import PROTOCOL_PROGRESS

    class _ReqNode:
        def __init__(self) -> None:
            self.times: list[float] = []

        async def request(self, peer_id, protocol, msg, **kw):
            self.times.append(time.monotonic())
            return "ok"

    async def main():
        node = _ReqNode()
        workers = {"w2": _FakeWorker(node)}
        ChaosController(parse_chaos_specs("slow-worker:w2:3", "w2"), workers)
        compute = 0.05
        t0 = time.monotonic()
        for _ in range(3):
            await asyncio.sleep(compute)  # "the inner batch"
            await node.request("sched", PROTOCOL_PROGRESS, _status())
        elapsed = time.monotonic() - t0
        # First status has no baseline; the next two stretch ~3x: total
        # >= compute + 2 * 3*compute (with generous slack for CI jitter).
        assert elapsed >= compute * (1 + 2 * 2.0)
        # Non-status requests pass through untouched.
        t0 = time.monotonic()
        await node.request("sched", "/other", object())
        assert time.monotonic() - t0 < 0.05

    run(main())


def test_jitter_is_deterministic_per_seed():
    import random

    a = random.Random("hypha-chaos-jitter:w1:0.5")
    b = random.Random("hypha-chaos-jitter:w1:0.5")
    assert [a.uniform(0, 0.5) for _ in range(8)] == [
        b.uniform(0, 0.5) for _ in range(8)
    ]


# --------------------------------------------------------------------------
# quorum-drop vs adapt at the parameter-server collector
# --------------------------------------------------------------------------


class TimedConsumer:
    """Pushes delivered at scheduled offsets from the first next() call."""

    def __init__(self, schedule):
        self._sched = sorted(schedule, key=lambda x: x[0])
        self._t0 = None

    async def next(self, timeout=None):
        loop = asyncio.get_running_loop()
        if self._t0 is None:
            self._t0 = loop.time()
        if not self._sched:
            await asyncio.sleep(min(timeout or 0.05, 0.05))
            raise asyncio.TimeoutError
        due, push = self._sched[0]
        now = loop.time()
        remaining = self._t0 + due - now
        if timeout is not None and remaining > timeout:
            await asyncio.sleep(timeout)
            raise asyncio.TimeoutError
        if remaining > 0:
            await asyncio.sleep(remaining)
        self._sched.pop(0)
        return push

    def close(self):
        pass


def _timed_round(schedule):
    from tests.test_ft import delta_push

    return [(at, delta_push(p, 0, v, s)) for at, (p, v, s) in schedule]


def test_static_deadline_drops_the_slow_uploader(tmp_path):
    """Static elastic close: the capped peer's delta misses the deadline,
    the round closes at quorum, and the drop is counted."""
    from tests.test_ft import elastic_cfg

    HET_METRICS.reset()
    cfg = elastic_cfg(["w0", "w1", "w2", "w3"], quorum_fraction=0.75,
                      round_deadline_s=0.4)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    consumer = TimedConsumer(_timed_round([
        (0.02, ("w0", 1.0, 8.0)),
        (0.03, ("w1", 1.0, 8.0)),
        (0.05, ("w2", 1.0, 8.0)),
        (1.5, ("w3", 1.0, 8.0)),  # the bandwidth-capped straggler
    ]))
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0)
    )
    assert set(received) == {"w0", "w1", "w2"}
    snap = HET_METRICS.snapshot()
    assert snap["quorum_drops"] == 1
    assert snap["quorum_drops_by_round"] == {0: 1}


def test_deadline_bounds_the_drain_not_just_the_header(tmp_path):
    """A push is queued at HEADER arrival; its payload may stream for
    seconds on a capped link. The deadline must bound the drain too —
    otherwise one slow in-progress transfer holds every round open past
    the close (the original elastic loop only re-checked the close
    condition between accepts)."""
    from tests.test_ft import elastic_cfg

    class SlowDrainPush:
        def __init__(self, peer, round_num, drain_s):
            self.peer = peer
            self.resource = {"resource": "u", "name": f"d-{peer}",
                            "round": round_num, "num_samples": 8.0}
            self.drain_s = drain_s
            self.finished = False

        async def save_to(self, dest, hasher=None):
            await asyncio.sleep(self.drain_s)
            save_file({"w": np.ones((3,), np.float32)}, str(dest))
            return 1

        async def read_all(self):
            return b""

        def finish(self):
            self.finished = True

    HET_METRICS.reset()
    cfg = elastic_cfg(["w0", "w1", "w2", "w3"], quorum_fraction=0.75,
                      round_deadline_s=0.5)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    slow = SlowDrainPush("w3", 0, drain_s=5.0)
    consumer = TimedConsumer(
        _timed_round([
            (0.02, ("w0", 1.0, 8.0)),
            (0.03, ("w1", 1.0, 8.0)),
            (0.05, ("w2", 1.0, 8.0)),
        ])
        + [(0.10, slow)]  # header arrives early, payload streams forever
    )
    t0 = time.monotonic()
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0),
        timeout=10,
    )
    elapsed = time.monotonic() - t0
    assert set(received) == {"w0", "w1", "w2"}
    assert elapsed < 3.0  # NOT the 5 s drain: the deadline cut it off
    assert slow.finished  # the stream slot was released
    assert HET_METRICS.snapshot()["quorum_drops"] == 1


def test_drain_unbounded_while_quorum_still_needs_it(tmp_path):
    """The drain bound applies only once the round is already quorate:
    a quorum-REQUIRED delta must drain to completion however slow its
    link — abandoning it would starve the round of the very delta its
    close is waiting for (and every retry would get a smaller budget)."""
    from tests.test_ft import elastic_cfg

    class SlowDrainPush:
        def __init__(self, peer, round_num, drain_s):
            self.peer = peer
            self.resource = {"resource": "u", "name": f"d-{peer}",
                            "round": round_num, "num_samples": 8.0}
            self.drain_s = drain_s

        async def save_to(self, dest, hasher=None):
            await asyncio.sleep(self.drain_s)
            save_file({"w": np.ones((3,), np.float32)}, str(dest))
            return 1

        async def read_all(self):
            return b""

        def finish(self):
            pass

    HET_METRICS.reset()
    cfg = elastic_cfg(["w0", "w1"], quorum_fraction=1.0,
                      round_deadline_s=0.4)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    consumer = TimedConsumer(
        _timed_round([(0.02, ("w0", 1.0, 8.0))])
        + [(0.05, SlowDrainPush("w1", 0, drain_s=1.5))]
    )
    received = run(
        ps._collect_round_elastic(consumer, "job", st, cfg, tmp_path, 0),
        timeout=10,
    )
    assert set(received) == {"w0", "w1"}  # the needed drain completed
    assert HET_METRICS.snapshot()["quorum_drops"] == 0


def test_adaptive_grace_waits_for_the_unmeasured_peer(tmp_path):
    """Same timings, adaptive: the first-round grace extends the deadline
    for the never-measured peer, its delta lands, zero quorum drops —
    and from then on the LinkTable has the measurement the codec ladder
    (and the next rounds' normal deadline) keys on."""
    from tests.test_ft import elastic_cfg

    HET_METRICS.reset()
    cfg = elastic_cfg(["w0", "w1", "w2", "w3"], quorum_fraction=0.75,
                      round_deadline_s=0.4)
    st = _ElasticState(cfg, "sched")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    link = LinkTable(base_codec="none", first_round_grace=6.0)
    arrivals: dict = {}
    consumer = TimedConsumer(_timed_round([
        (0.02, ("w0", 1.0, 8.0)),
        (0.03, ("w1", 1.0, 8.0)),
        (0.05, ("w2", 1.0, 8.0)),
        (1.5, ("w3", 1.0, 8.0)),
    ]))
    received = run(
        ps._collect_round_elastic(
            consumer, "job", st, cfg, tmp_path, 0,
            link=link, arrivals=arrivals,
        )
    )
    assert set(received) == {"w0", "w1", "w2", "w3"}
    assert HET_METRICS.snapshot()["quorum_drops"] == 0
    assert link.measured("w3")
    # The arrival report the straggler controller consumes: w3's lag
    # dominates, and every accepted peer is present.
    assert set(arrivals) == {"w0", "w1", "w2", "w3"}
    assert arrivals["w3"] > arrivals["w0"]


# --------------------------------------------------------------------------
# telemetry surface
# --------------------------------------------------------------------------


def test_het_metrics_snapshot_and_register_on():
    HET_METRICS.reset()
    HET_METRICS.note_bandwidth("w0", 5e6)
    HET_METRICS.note_assigned("w0", 6)
    HET_METRICS.note_codec("w0", "int8")
    HET_METRICS.note_quorum_drop(2, ["w1"])
    HET_METRICS.codec_switches.add(1)
    snap = HET_METRICS.snapshot()
    assert snap["bandwidth_bps"] == {"w0": 5e6}
    assert snap["assigned_steps"] == {"w0": 6}
    assert snap["codec_counts"] == {"int8": 1}
    assert snap["quorum_drops"] == 1
    assert snap["quorum_drops_by_round"] == {2: 1}
    assert snap["codec_switches"] == 1

    class SpyMeter:
        def __init__(self):
            self.gauges = {}

        def observable_gauge(self, name, fn):
            self.gauges[name] = fn

    meter = SpyMeter()
    register_on(meter)
    assert meter.gauges["hypha.het.quorum_drops"]() == 1
    assert meter.gauges["hypha.het.codec_switches"]() == 1
    assert meter.gauges["hypha.het.bandwidth_bps.w0"]() == 5e6
    assert meter.gauges["hypha.het.assigned_steps.w0"]() == 6
    assert meter.gauges["hypha.het.codec.int8"]() == 1
    # Peers first seen AFTER registration attach lazily.
    HET_METRICS.note_bandwidth("w9", 1e6)
    assert meter.gauges["hypha.het.bandwidth_bps.w9"]() == 1e6


# --------------------------------------------------------------------------
# orchestrated e2e (slow)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_quorum_drop_vs_adapt_e2e():
    """4-worker pool, one 4x slow-CPU + one bandwidth-capped peer: the
    static run quorum-drops the capped peer; the adaptive run lands every
    delta."""
    from harness.hetbench import run_het_scenario

    static = run_het_scenario(adaptive=False, rounds=2)
    assert static["quorum_drops"] >= 1
    adaptive = run_het_scenario(adaptive=True, rounds=2)
    assert adaptive["quorum_drops"] == 0
    assert adaptive["assigned_steps"], "controller published no assignments"
