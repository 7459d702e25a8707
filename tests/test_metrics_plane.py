"""Live metrics plane tests (ISSUE 13): series/rollup math with error
bounds, the registry sampler's delta semantics, reporter -> collector over
the memory fabric, SLO rule parsing + edge-triggered breaches, the
``telemetry.top`` renderer, exporters, the off-path wire goldens, the
flight recorder's spill-on-demand, and the metrics_snapshot JSON-safety
property test.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import signal

import numpy as np
import pytest

from hypha_tpu import codec, messages
from hypha_tpu.messages import (
    Adam,
    AggregateExecutorConfig,
    Fetch,
    InferExecutorConfig,
    Nesterov,
    Progress,
    ProgressKind,
    ProgressResponse,
    ProgressResponseKind,
    Receive,
    Reference,
    Send,
    TrainExecutorConfig,
)
from hypha_tpu.network import MemoryTransport, Node
from hypha_tpu.telemetry import metrics_snapshot
from hypha_tpu.telemetry.flight import FlightRecorder
from hypha_tpu.telemetry.ft_metrics import (
    DATA_METRICS,
    FT_METRICS,
    HET_METRICS,
    SERVE_METRICS,
    SHARD_METRICS,
    STREAM_METRICS,
)
from hypha_tpu.telemetry.metrics_plane import (
    PROTOCOL_METRICS,
    MetricsCollector,
    MetricsPage,
    MetricsQuery,
    MetricsReport,
    MetricsReporter,
    RegistrySampler,
)
from hypha_tpu.telemetry.series import (
    TimeSeriesStore,
    merge_summaries,
    prometheus_text,
    summarize,
    to_otlp_metrics,
)
from hypha_tpu.telemetry.slo import (
    SLOWatchdog,
    parse_slo_rule,
    parse_slo_rules,
)
from hypha_tpu.telemetry import top


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=30))


@pytest.fixture(autouse=True)
def _fresh_bundles():
    """The sampler reads the process-global bundles; isolate per test."""
    for b in (FT_METRICS, STREAM_METRICS, SHARD_METRICS, SERVE_METRICS,
              HET_METRICS):
        b.reset()
    yield
    for b in (FT_METRICS, STREAM_METRICS, SHARD_METRICS, SERVE_METRICS,
              HET_METRICS):
        b.reset()


# ---------------------------------------------------------------------------
# summaries + quantile merge (satellite: documented error bounds)
# ---------------------------------------------------------------------------


def test_summarize_shape():
    s = summarize([5.0, 1.0, 3.0, 2.0, 4.0])
    assert s["count"] == 5 and s["sum"] == 15.0
    assert s["min"] == 1.0 and s["max"] == 5.0
    assert s["p50"] == 3.0


def test_merge_single_summary_reads_back_its_own_knots():
    """Self-consistency: merging ONE summary returns its own quantiles
    exactly (the CDF inversion lands back on the knots)."""
    s = summarize(list(np.random.default_rng(3).normal(50, 10, 500)))
    merged = merge_summaries([s])
    for k in ("p50", "p95", "p99", "min", "max"):
        assert merged[k] == pytest.approx(s[k], rel=1e-9)


def test_merge_identical_distributions_is_near_exact():
    """Identical per-peer distributions merge to (nearly) the per-peer
    quantiles — only per-peer sampling error and the piecewise-linear
    tail interpolation remain (documented bounds: <= 5% for p50/p95,
    <= 10% for p99 whose mass sits between sparse knots)."""
    rng = np.random.default_rng(0)
    peers = [rng.lognormal(0.0, 1.0, 2000) for _ in range(4)]
    pooled = np.concatenate(peers)
    merged = merge_summaries([summarize(p) for p in peers])
    for q, bound in ((50, 0.05), (95, 0.05), (99, 0.10)):
        true = float(np.percentile(pooled, q))
        assert abs(merged[f"p{q}"] - true) / true < bound, (q, merged)


def test_merge_mixed_distributions_within_bounds():
    """Adversarially different per-peer distributions: the documented
    bounds are <= 15% relative error at the TAIL quantiles (p95/p99,
    where knots are dense), exact count/sum/min/max, and the
    bracketing-knot envelope for the mid-rank p50 (which legitimately
    drifts inside a peer's p50–p95 knot gap under disjoint mixtures)."""
    rng = np.random.default_rng(7)
    peers = [
        rng.lognormal(0.0, 1.0, 3000),
        rng.uniform(5.0, 10.0, 1500),
        rng.normal(20.0, 1.0, 500).clip(min=0.1),
    ]
    pooled = np.concatenate(peers)
    summaries = [summarize(p) for p in peers]
    merged = merge_summaries(summaries)
    assert merged["count"] == pooled.size
    assert merged["sum"] == pytest.approx(float(pooled.sum()), rel=1e-9)
    assert merged["min"] == pytest.approx(float(pooled.min()))
    assert merged["max"] == pytest.approx(float(pooled.max()))
    for q in (95, 99):
        true = float(np.percentile(pooled, q))
        rel = abs(merged[f"p{q}"] - true) / true
        assert rel <= 0.15, f"p{q}: merged {merged[f'p{q}']} vs true {true}"
    for q in (50, 95, 99):
        assert merged["min"] <= merged[f"p{q}"] <= merged["max"]
    # p50 envelope: between the smallest per-peer knot below the pooled
    # rank and the largest per-peer knot above it.
    true_p50 = float(np.percentile(pooled, 50))
    lo = min(s["min"] for s in summaries)
    hi = max(s["p95"] for s in summaries)
    assert lo <= merged["p50"] <= hi
    assert lo <= true_p50 <= hi


def test_merge_empty_and_singleton():
    assert merge_summaries([])["count"] == 0
    one = summarize([1.0, 2.0, 3.0])
    merged = merge_summaries([one, {"count": 0}])
    assert merged["count"] == 3 and merged["p50"] == one["p50"]


# ---------------------------------------------------------------------------
# TimeSeriesStore
# ---------------------------------------------------------------------------


def test_store_rings_are_bounded():
    store = TimeSeriesStore(capacity=8)
    for i in range(100):
        store.record_gauge("w0", "g", float(i), t=float(i))
    pts = store.series("w0", "g")
    assert len(pts) == 8 and pts[-1][1] == 99.0


def test_store_rollups_and_outlier():
    store = TimeSeriesStore()
    store.record_gauge("w0", "bw", 100.0)
    store.record_gauge("w1", "bw", 2.0)
    store.record_gauge("w2", "bw", 110.0)
    assert store.fleet_sum("bw") == pytest.approx(212.0)
    assert store.fleet_max("bw") == 110.0
    peer, value = store.outlier("bw")
    assert peer == "w1" and value == 2.0
    # No outlier when the fleet is homogeneous.
    uniform = TimeSeriesStore()
    for p in ("a", "b", "c"):
        uniform.record_gauge(p, "bw", 10.0)
    assert uniform.outlier("bw") is None


def test_store_counter_deltas_and_rates():
    store = TimeSeriesStore()
    store.record_delta("w0", "bytes", 1000.0, interval_s=2.0, t=0.0)
    store.record_delta("w0", "bytes", 3000.0, interval_s=2.0, t=2.0)
    assert store.cumulative("w0", "bytes") == 4000.0
    assert store.latest("w0", "bytes") == 1500.0  # rate of the last window
    assert store.average_rate("w0", "bytes") == pytest.approx(2000.0)
    assert store.fleet_peak("bytes") == {"w0": 1500.0}


def test_store_quality_series_and_round_walls():
    store = TimeSeriesStore()
    for r, v in ((0, 3.5), (1, 3.3), (2, 3.1)):
        store.record_quality("w0", "loss", r, v)
        store.record_quality("w1", "loss", r, v + 0.1)
        store.note_round(r, t=float(r) * 2.0)
    curves = store.quality_rounds("loss")
    assert sorted(curves) == [0, 1, 2]
    assert curves[1]["w1"] == pytest.approx(3.4)
    walls = store.round_walls()
    assert walls[0] == pytest.approx(2.0) and walls[1] == pytest.approx(2.0)


def test_store_silent_for():
    store = TimeSeriesStore()
    store.note_peer("w0", t=100.0)
    assert store.silent_for("w0", now=115.0) == pytest.approx(15.0)
    assert math.isinf(store.silent_for("ghost", now=115.0))


def test_fleet_quantile_merge_from_store():
    store = TimeSeriesStore()
    store.record_summary("w0", "lat", summarize([10.0] * 50 + [100.0]))
    store.record_summary("w1", "lat", summarize([20.0] * 50))
    merged = store.fleet_quantiles("lat")
    assert merged["count"] == 101
    assert 10.0 <= merged["p50"] <= 20.0 + 1e-6
    assert merged["max"] == 100.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_text_shapes():
    store = TimeSeriesStore()
    store.record_gauge("w0", "hypha.serve.queue_depth", 3.0)
    store.record_summary("w0", "hypha.serve.request_latency_ms",
                         summarize([1.0, 2.0, 3.0]))
    store.record_quality("w0", "loss", 2, 3.25)
    text = prometheus_text(store)
    assert '# TYPE hypha_serve_queue_depth gauge' in text
    assert 'hypha_serve_queue_depth{peer="w0"} 3' in text
    assert '# TYPE hypha_serve_request_latency_ms summary' in text
    assert 'quantile="0.5"' in text
    assert 'hypha_serve_request_latency_ms_count{peer="w0"} 3' in text
    assert 'quality_loss{peer="w0",round="2"} 3.25' in text


def test_otlp_metrics_export_shape():
    store = TimeSeriesStore()
    store.record_gauge("w0", "bw", 5.0)
    store.record_quality("w0", "loss", 1, 3.0)
    payload = to_otlp_metrics(store)
    rm = payload["resourceMetrics"][0]
    names = {m["name"] for m in rm["scopeMetrics"][0]["metrics"]}
    assert names == {"bw", "hypha.quality.loss"}
    point = rm["scopeMetrics"][0]["metrics"][0]["gauge"]["dataPoints"][0]
    assert point["asDouble"] == 5.0
    assert {"key": "peer", "value": {"stringValue": "w0"}} in point["attributes"]
    json.dumps(payload)  # JSON-serializable end to end


# ---------------------------------------------------------------------------
# SLO rules
# ---------------------------------------------------------------------------


def test_parse_slo_rules():
    r = parse_slo_rule("hypha.serve.request_latency_ms.p99 <= 250")
    assert (r.metric, r.agg, r.op, r.threshold) == (
        "hypha.serve.request_latency_ms", "p99", "<=", 250.0
    )
    assert parse_slo_rule("round_wall_s <= 30").scope == "fleet"
    assert parse_slo_rule("silent_s <= 15").scope == "peer"
    assert parse_slo_rule("node.bandwidth_out_mbps >= 0.5 @peer").scope == "peer"
    assert parse_slo_rule("hypha.het.quorum_drops == 0").op == "=="
    with pytest.raises(ValueError):
        parse_slo_rule("no operator here")
    with pytest.raises(ValueError):
        parse_slo_rule("metric <= notanumber")
    assert parse_slo_rules(["a <= 1", "  "]) and len(parse_slo_rules([])) == 0


def test_slo_breach_is_edge_triggered_with_recovery():
    store = TimeSeriesStore()
    advisories = []
    dog = SLOWatchdog(
        parse_slo_rules(["queue <= 5 @peer"]), store,
        job_id="j", on_advisory=advisories.append,
    )
    store.record_gauge("w0", "queue", 3.0)
    assert dog.check() == []
    store.record_gauge("w0", "queue", 9.0)
    first = dog.check()
    assert len(first) == 1 and first[0].breached and first[0].peer == "w0"
    assert dog.check() == []  # still breached: no re-fire
    store.record_gauge("w0", "queue", 2.0)
    rec = dog.check()
    assert len(rec) == 1 and not rec[0].breached
    assert dog.breaches == 1
    assert [a.breached for a in advisories] == [True, False]


def test_slo_silence_rule_fires_flight_event():
    from hypha_tpu.telemetry.flight import FLIGHT

    FLIGHT.clear()
    store = TimeSeriesStore()
    store.note_peer("w0", t=0.0)
    dog = SLOWatchdog(parse_slo_rules(["silent_s <= 10"]), store, job_id="j")
    assert dog.check(now=5.0) == []
    breaches = dog.check(now=50.0)
    assert len(breaches) == 1 and breaches[0].peer == "w0"
    events = [e for e in FLIGHT.snapshot() if e["event"] == "slo.breach"]
    assert events and events[-1]["attrs"]["peer"] == "w0"
    FLIGHT.clear()


def test_slo_counter_equality_reads_cumulative():
    store = TimeSeriesStore()
    dog = SLOWatchdog(
        parse_slo_rules(["hypha.het.quorum_drops == 0"]), store
    )
    store.record_delta("sched", "hypha.het.quorum_drops", 0.0, 1.0)
    assert dog.check() == []
    store.record_delta("sched", "hypha.het.quorum_drops", 2.0, 1.0)
    assert len(dog.check()) == 1  # cumulative 2 != 0 even if rate settles
    store.record_delta("sched", "hypha.het.quorum_drops", 0.0, 1.0)
    assert dog.check() == []  # cumulative still 2 -> still breached, no edge


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


def test_sampler_ships_counter_deltas_not_totals():
    sampler = RegistrySampler()
    FT_METRICS.rejoins.add(3)
    counters, _gauges, _ = sampler.sample()
    assert counters["hypha.ft.rejoins"] == 3.0
    counters, _gauges, _ = sampler.sample()
    assert "hypha.ft.rejoins" not in counters  # no change -> no key
    FT_METRICS.rejoins.add(2)
    counters, _gauges, _ = sampler.sample()
    assert counters["hypha.ft.rejoins"] == 2.0  # the delta, not 5


def test_sampler_covers_lazy_counter_dicts_and_gauges():
    HET_METRICS.note_codec("w0", "int8")
    HET_METRICS.note_bandwidth("w0", 1e6)
    SERVE_METRICS.pool_state(free_blocks=7, queue_depth=2)
    sampler = RegistrySampler()
    counters, gauges, _ = sampler.sample()
    assert counters["hypha.het.codec.int8"] == 1.0
    assert gauges["hypha.het.bandwidth_bps.w0"] == 1e6
    assert gauges["hypha.serve.free_blocks"] == 7.0
    assert gauges["hypha.serve.queue_depth"] == 2.0


def test_sampler_reservoir_summary():
    for v in (10.0, 20.0, 30.0):
        SERVE_METRICS.request_finished(v)
    sampler = RegistrySampler()
    _c, _g, summaries = sampler.sample()
    s = summaries["hypha.serve.request_latency_ms"]
    assert s["count"] == 3 and s["max"] == 30.0 and "p99" in s
    _c, _g, summaries = sampler.sample()
    assert not summaries  # unchanged reservoir is not re-shipped


# ---------------------------------------------------------------------------
# reporter -> collector over the memory fabric
# ---------------------------------------------------------------------------


async def _two_nodes():
    hub = MemoryTransport()
    sched = Node(hub.shared(), peer_id="sched")
    worker = Node(hub.shared(), peer_id="w0")
    await sched.start()
    await worker.start()
    peer = await worker.dial(sched.listen_addrs[0])
    assert peer == "sched"
    sched.add_peer_addr("w0", worker.listen_addrs[0])
    return sched, worker


def test_reporter_collector_end_to_end(tmp_path):
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(
            sched, "job-1", journal_dir=tmp_path,
            slo_rules=["hypha.ft.rejoins == 0"],
        ).start()
        reporter = MetricsReporter(
            worker, "sched", "job-1-w0", interval_s=0.05,
            round_fn=lambda: 2,
        ).start()
        FT_METRICS.rejoins.add(1)
        for _ in range(100):
            if collector.reports >= 2:
                break
            await asyncio.sleep(0.05)
        assert collector.reports >= 2, "collector ingested no reports"
        await reporter.stop()
        # Quality via the progress channel (the orchestrator's hook).
        collector.ingest_quality("w0", 2, {"loss": 3.25, "bogus": "skip"})
        store = collector.store
        assert "w0" in store.peers()
        assert store.cumulative("w0", "hypha.ft.rejoins") >= 1.0
        assert store.quality_rounds("loss")[2]["w0"] == pytest.approx(3.25)
        # The SLO rule on the counter breached (rejoins == 0 violated).
        assert collector.watchdog.breaches >= 1
        # Query path (telemetry.top's addr mode).
        page = await worker.request(
            "sched", PROTOCOL_METRICS, MetricsQuery(job_id="job-1")
        )
        assert isinstance(page, MetricsPage)
        assert "w0" in page.snapshot["gauges"] or "w0" in page.snapshot["last_seen"]
        await collector.close()
        await sched.stop()
        await worker.stop()
        journals = list(tmp_path.glob("metrics-*.jsonl"))
        assert journals, "no metrics journal written"
        recs = [json.loads(ln) for ln in journals[0].read_text().splitlines()]
        kinds = {r["type"] for r in recs}
        assert "report" in kinds and "quality" in kinds and "slo" in kinds

    run(main())


def test_collector_derives_bandwidth_and_prefix_match(tmp_path):
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(sched, "base").start()
        report = MetricsReport(
            job_id="base-w7", peer="w7", round=1, seq=0, interval_s=2.0,
            counters={"node.bytes_out": 2_000_000.0},
        )
        ack = await worker.request("sched", PROTOCOL_METRICS, report)
        assert ack.ok
        # 2 MB over 2 s = 8 Mbit/s derived gauge.
        assert collector.store.latest(
            "w7", "node.bandwidth_out_mbps"
        ) == pytest.approx(8.0)
        # A foreign job's report is refused (prefix mismatch).
        foreign = MetricsReport(job_id="otherjob-w0", peer="x")
        from hypha_tpu.network import RequestError

        with pytest.raises(RequestError):
            await worker.request("sched", PROTOCOL_METRICS, foreign)
        await collector.close()
        await sched.stop()
        await worker.stop()

    run(main())


def test_reporter_survives_dead_collector():
    async def main():
        hub = MemoryTransport()
        worker = Node(hub.shared(), peer_id="w0")
        await worker.start()
        reporter = MetricsReporter(
            worker, "nowhere", "job", interval_s=0.02
        ).start()
        await asyncio.sleep(0.2)
        await reporter.stop(flush=False)
        assert reporter.dropped >= 1 and reporter.sent == 0
        await worker.stop()

    run(main())


# ---------------------------------------------------------------------------
# telemetry.top
# ---------------------------------------------------------------------------


def test_top_renders_from_journal_dir(tmp_path):
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(sched, "job-1", journal_dir=tmp_path).start()
        report = MetricsReport(
            job_id="job-1-w0", peer="w0", round=1, interval_s=1.0,
            counters={"node.bytes_out": 1_000_000.0},
            gauges={"hypha.serve.queue_depth": 4.0},
        )
        await worker.request("sched", PROTOCOL_METRICS, report)
        collector.ingest_quality("w0", 1, {"loss": 3.5, "tokens_per_s": 120.0})
        await asyncio.sleep(0.1)  # quality journal write is spawned
        await collector.close()
        await sched.stop()
        await worker.stop()

    run(main())
    snap = top.snapshot_from_dir(tmp_path)
    assert "w0" in snap["gauges"]
    frame = top.render(snap)
    assert "w0" in frame and "SLO" in frame
    assert "3.5" in frame  # the loss column
    # --once --json main() path over the dir.
    rc = top.main([str(tmp_path), "--once", "--json"])
    assert rc == 0


def test_top_render_empty_snapshot():
    assert "0 peers" in top.render({})


# ---------------------------------------------------------------------------
# off = byte-identical wire (golden-pinned)
# ---------------------------------------------------------------------------


def test_executor_configs_off_omit_report_fields():
    train = TrainExecutorConfig(
        model={"x": 1},
        data=Fetch(Reference.from_uri("file:///d")),
        updates=Send(Reference.from_peers(["ps"], "updates")),
        results=Receive(Reference.from_peers(["ps"], "results")),
        optimizer=Adam(),
        batch_size=4,
    )
    agg = AggregateExecutorConfig(
        updates=Receive(Reference.from_peers(["w0"], "updates")),
        results=Send(Reference.from_peers(["w0"], "results")),
        optimizer=Nesterov(),
    )
    infer = InferExecutorConfig(model={"x": 1}, serve_name="svc")
    for cfg in (train, agg, infer):
        plain = messages.to_json_dict(cfg)
        assert "report_metrics_s" not in plain
        assert "metrics_peer" not in plain
        # And the round trip drops nothing.
        assert messages.decode(messages.encode(cfg)) == cfg


def test_progress_off_wire_bytes_unchanged_by_metrics_plane():
    """The exact golden from tests/test_trace.py still holds: a
    non-reporting job's Progress carries no quality keys and encodes to
    its pre-metrics bytes."""
    p = Progress(kind=ProgressKind.UPDATED, job_id="job-1", round=3)
    golden = codec.dumps(
        {
            "_t": "Progress",
            "kind": {"_e": "ProgressKind", "v": "updated"},
            "job_id": "job-1",
            "batch_size": 0,
            "round": 3,
            "metrics": {},
            "shard": 0,
        }
    )
    assert messages.encode(p) == golden


def test_progress_response_off_wire_bytes_unchanged():
    r = ProgressResponse(kind=ProgressResponseKind.CONTINUE)
    golden = codec.dumps(
        {
            "_t": "ProgressResponse",
            "kind": {"_e": "ProgressResponseKind", "v": "continue"},
            "counter": 0,
            "message": "",
        }
    )
    assert messages.encode(r) == golden


def test_metrics_report_roundtrip_and_protocol():
    report = MetricsReport(
        job_id="j", peer="w0", round=2, seq=5, interval_s=0.5,
        counters={"a": 1.0}, gauges={"b": 2.0},
        summaries={"c": {"count": 1.0, "p50": 3.0}},
    )
    assert messages.decode(messages.encode(report)) == report
    # generation None is omitted (durable-control-plane discipline).
    assert "generation" not in messages.to_json_dict(report)
    assert "MetricsReport" in messages.PROTOCOL_MESSAGES[PROTOCOL_METRICS]


# ---------------------------------------------------------------------------
# satellite: flight recorder spill-on-demand
# ---------------------------------------------------------------------------


def test_flight_dump_is_read_only_snapshot(tmp_path):
    rec = FlightRecorder(node="wedged")
    rec.configure(spill_dir=tmp_path)
    rec.record("round.stall", round=3, peer="w1")
    rec.record("retry", attempt=2)
    path = rec.dump()
    assert path is not None and path.name == "events-wedged-dump.jsonl"
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["round.stall", "retry"]
    # Read-only: the ring was NOT drained (unlike spill).
    assert len(rec.snapshot()) == 2
    # A second dump overwrites with the full current ring.
    rec.record("more")
    lines2 = rec.dump().read_text().splitlines()
    assert len(lines2) == 3


def test_flight_dump_explicit_path_without_spill_dir(tmp_path):
    rec = FlightRecorder(node="n")
    rec.record("e1")
    out = rec.dump(tmp_path / "sub" / "ring.jsonl")
    assert out.is_file() and "e1" in out.read_text()


@pytest.mark.skipif(
    not hasattr(signal, "SIGUSR2"), reason="platform without SIGUSR2"
)
def test_flight_sigusr2_dumps_ring(tmp_path):
    rec = FlightRecorder(node="sig")
    rec.configure(spill_dir=tmp_path)
    assert rec.arm_signal() is True
    rec.record("wedged.evidence", round=9)
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        # The handler runs between bytecodes in the main thread.
        for _ in range(100):
            if (tmp_path / "events-sig-dump.jsonl").is_file():
                break
        dumped = (tmp_path / "events-sig-dump.jsonl").read_text()
        assert "wedged.evidence" in dumped
        # The ring is intact: the node can keep recording after a capture.
        assert len(rec.snapshot()) == 1
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# satellite: metrics_snapshot JSON-safety property test
# ---------------------------------------------------------------------------


def _walk_leaves(obj, path=""):
    if isinstance(obj, dict):
        for k, v in obj.items():
            assert isinstance(k, (str, int)), f"non-JSON key at {path}: {k!r}"
            yield from _walk_leaves(v, f"{path}/{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _walk_leaves(v, f"{path}[{i}]")
    else:
        yield path, obj


def test_metrics_snapshot_is_json_safe_under_numpy_scalars():
    """Property: after feeding numpy/jax-flavored scalars into EVERY
    registered instrument of the five shared bundles, metrics_snapshot()
    still serializes to JSON and every leaf is a plain Python scalar —
    no np.float32 leakage (each would crash json.dumps downstream, e.g.
    the bench artifact writers)."""
    from hypha_tpu.telemetry import Counter, Histogram

    def feed(bundle):
        for value in vars(bundle).values():
            if isinstance(value, Counter):
                value.add(np.float32(1.5))
                value.add(np.int64(2))
            elif isinstance(value, Histogram):
                value.record(np.float32(12.5))
            elif isinstance(value, dict):
                for v in value.values():
                    if isinstance(v, Counter):
                        v.add(np.float32(1))

    for bundle in (FT_METRICS, STREAM_METRICS, SHARD_METRICS,
                   SERVE_METRICS, HET_METRICS, DATA_METRICS):
        feed(bundle)
    # The special recorders that historically bypassed Counter/Histogram.
    STREAM_METRICS.flight_started(np.float32(1024.0))
    STREAM_METRICS.flight_landed(np.float32(512.0))
    STREAM_METRICS.flight_finished(np.float64(1.5), np.float32(1.0))
    STREAM_METRICS.fragment_closed(np.int64(0))
    HET_METRICS.note_bandwidth("w0", np.float32(1e6))
    HET_METRICS.note_assigned("w0", np.int64(16))
    HET_METRICS.note_codec("w0", "int8")
    HET_METRICS.note_quorum_drop(np.int64(3), ["w1"])
    SERVE_METRICS.pool_state(np.int64(10), np.float32(2))
    SERVE_METRICS.cache_state(np.float32(5), np.int32(1))
    SERVE_METRICS.request_finished(np.float32(25.0))
    FT_METRICS.rejoin_latency_ms.record(np.float32(100.0))
    DATA_METRICS.note_input_wait(np.float32(0.5))
    DATA_METRICS.note_boundary_wait(np.float64(0.25))
    DATA_METRICS.note_fetch(np.float32(0.1))
    DATA_METRICS.note_queue_depth(np.int64(2))

    snap = metrics_snapshot()
    json.dumps(snap)  # must not raise
    for path, leaf in _walk_leaves(snap):
        assert leaf is None or type(leaf) in (int, float, str, bool), (
            f"non-plain scalar at {path}: {type(leaf).__name__} = {leaf!r}"
        )


# ---------------------------------------------------------------------------
# orchestrated end to end (slow): full in-process DiLoCo run, metrics on
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_metrics_plane_end_to_end_orchestrated(tmp_path):
    from harness.ft_chaos import run_chaos_scenario

    line = run_chaos_scenario(
        spec=None, num_workers=2, rounds=2,
        quorum_fraction=0.0, round_deadline_s=0.0,
        metrics_plane=True, metrics_dir=str(tmp_path),
        slo_rules=["silent_s <= 60"],
    )
    assert line["rounds_completed"] == 2
    mp = line["metrics_plane"]
    assert mp["reports"] > 0
    # Loss curve: both workers, both rounds, no gaps.
    loss = {int(r): peers for r, peers in mp["loss_rounds"].items()}
    assert sorted(loss) == [0, 1]
    for r in (0, 1):
        assert set(loss[r]) == {"w0", "w1"}
    # Per-node bandwidth gauges reached the store.
    assert set(mp["bandwidth_out_mbps"]) >= {"w0", "w1", "psw"}
    assert mp["slo"]["breaches"] == 0
    # Journal on disk, consumable by telemetry.top offline.
    journals = list(tmp_path.glob("metrics-*.jsonl"))
    assert journals
    snap = top.snapshot_from_dir(tmp_path)
    frame = top.render(snap)
    assert "w0" in frame and "w1" in frame


# ---------------------------------------------------------------------------
# serving supervisor relay
# ---------------------------------------------------------------------------


def test_supervisor_relays_serve_load_into_store():
    """The routed supervisor's ServeLoad handler feeds the collector's
    store (per-backend queue depth / KV headroom), and its dispatched
    InferExecutorConfig carries the report fields only when asked."""
    import types

    from hypha_tpu.messages import ServeLoad, ServeLoadAck
    from hypha_tpu.scheduler.serving import ServingSupervisor, _Deployment

    async def main():
        hub = MemoryTransport()
        node = Node(hub.shared(), peer_id="sched")
        await node.start()
        store = TimeSeriesStore()
        sink = types.SimpleNamespace(
            ingest_serve_load=lambda backend, q, fb: (
                store.record_gauge(backend, "hypha.serve.queue_depth", q),
                store.record_gauge(backend, "hypha.serve.free_blocks", fb),
            )
        )
        sup = ServingSupervisor(
            node, {"model_type": "x"}, "llm", num_workers=2,
            report_metrics_s=0.5, metrics=sink,
        )
        assert sup._config.report_metrics_s == 0.5
        assert sup._config.metrics_peer == "sched"
        dep = _Deployment(
            slot=0,
            handle=types.SimpleNamespace(peer_id="wrk"),
            task=None, job_id="j0", backend_name="llm@0",
        )
        sup._deployments[0] = dep
        load = ServeLoad(
            job_id="j0", serve_name="llm@0", queue_depth=5, free_blocks=11
        )
        ack = await sup._on_load("wrk", load)
        assert isinstance(ack, ServeLoadAck) and ack.ok
        assert store.latest("llm@0", "hypha.serve.queue_depth") == 5.0
        assert store.latest("llm@0", "hypha.serve.free_blocks") == 11.0
        # Off: no report fields on the dispatched config.
        off = ServingSupervisor(node, {"model_type": "x"}, "llm2")
        plain = messages.to_json_dict(off._config)
        assert "report_metrics_s" not in plain and "metrics_peer" not in plain
        await node.stop()

    run(main())


# ---------------------------------------------------------------------------
# review regressions
# ---------------------------------------------------------------------------


def test_sampler_reships_summary_after_reservoir_trims():
    """The re-ship trigger is the histogram's MONOTONE count, not the
    reservoir length: once the bounded reservoir saturates (trimmed to a
    window), new traffic must still refresh the shipped quantiles."""
    sampler = RegistrySampler()
    for v in (10.0, 20.0, 30.0):
        SERVE_METRICS.request_finished(v)
    _c, _g, summaries = sampler.sample()
    assert summaries
    # Two more requests land and the reservoir trims back to 3 entries —
    # same length as before, but the count moved.
    SERVE_METRICS.request_finished(500.0)
    SERVE_METRICS.request_finished(600.0)
    with SERVE_METRICS._lock:
        del SERVE_METRICS._latencies[:2]
    _c, _g, summaries = sampler.sample()
    assert summaries, "saturated reservoir froze the shipped summary"
    assert summaries["hypha.serve.request_latency_ms"]["max"] == 600.0


def test_slo_round_wall_sees_a_hung_round():
    """A round that never completes must still breach round_wall_s: the
    open round's AGE counts, not just completed round gaps."""
    store = TimeSeriesStore()
    dog = SLOWatchdog(parse_slo_rules(["round_wall_s <= 10"]), store)
    store.note_round(0, t=0.0)
    store.note_round(1, t=2.0)  # round 0 completed in 2 s
    assert dog.check(now=5.0) == []  # round 1 is 3 s old: healthy
    breaches = dog.check(now=60.0)  # round 1 wedged for 58 s
    assert len(breaches) == 1 and breaches[0].breached
    # The wedged round finally closes (wall 59 s — still a violation, the
    # breach stays latched), then a HEALTHY round completes: recovery.
    store.note_round(2, t=61.0)
    assert dog.check(now=62.0) == []
    store.note_round(3, t=63.0)  # round 2's wall was 2 s
    rec = dog.check(now=64.0)
    assert len(rec) == 1 and not rec[0].breached  # progress resumed


def test_top_dir_mode_reconstructs_rates_from_journal_interval(tmp_path):
    """Journaled reports carry interval_s; the offline reader derives the
    same per-interval rates and bandwidth gauges as the live store."""
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(
            sched, "job-1", journal_dir=tmp_path
        ).start()
        report = MetricsReport(
            job_id="job-1-w0", peer="w0", round=1, interval_s=2.0,
            counters={"node.bytes_out": 2_000_000.0},
        )
        await worker.request("sched", PROTOCOL_METRICS, report)
        await collector.close()
        await sched.stop()
        await worker.stop()

    run(main())
    snap = top.snapshot_from_dir(tmp_path)
    # 2 MB over the journaled 2 s window = 8 Mbit/s, matching the live
    # collector's derivation (not a hardcoded 1 s guess = 16 Mbit/s).
    assert snap["gauges"]["w0"]["node.bandwidth_out_mbps"] == pytest.approx(8.0)
    assert "8" in top.render(snap)


def test_quality_edge_slo_breach_reaches_the_journal(tmp_path):
    """An SLO edge fired from ingest_quality (not a report) must land in
    the journal's 'slo' records, or offline state diverges from live."""
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(
            sched, "job-1", journal_dir=tmp_path,
            slo_rules=["loss_breaches_nothing == 0"],
        ).start()
        # Manufacture a breach visible only via quality ingest: a counter
        # family fed through the store directly, then the quality hook.
        collector.store.record_delta(
            "w0", "loss_breaches_nothing", 2.0, 1.0
        )
        collector.ingest_quality("w0", 1, {"loss": 3.0})
        await asyncio.sleep(0.1)
        await collector.close()
        await sched.stop()
        await worker.stop()

    run(main())
    journals = list(tmp_path.glob("metrics-*.jsonl"))
    assert journals
    recs = [json.loads(ln) for ln in journals[0].read_text().splitlines()]
    slo_recs = [r for r in recs if r["type"] == "slo"]
    assert slo_recs and slo_recs[0]["breached"]


def test_flight_dump_is_lockfree_under_held_lock(tmp_path):
    """The SIGUSR2 body must never block on the recorder lock — the
    interrupted frame may HOLD it (record() on a hot path). dump() with
    the lock held by another frame must complete, not deadlock."""
    rec = FlightRecorder(node="held")
    rec.configure(spill_dir=tmp_path)
    rec.record("before")
    with rec._lock:  # simulate the interrupted frame holding the lock
        path = rec.dump()
    assert path is not None and "before" in path.read_text()


def test_sampler_always_ships_node_byte_deltas():
    """Idle intervals ship a ZERO byte delta: the derived bandwidth gauge
    must decay to 0 instead of freezing at the last burst rate."""
    import types

    node = types.SimpleNamespace(bytes_in=0, bytes_out=1000)
    sampler = RegistrySampler(node)
    counters, _g, _s = sampler.sample()
    assert counters["node.bytes_out"] == 1000.0
    counters, _g, _s = sampler.sample()  # idle interval
    assert counters["node.bytes_out"] == 0.0
    assert counters["node.bytes_in"] == 0.0


def test_top_render_merges_fleet_latency():
    """The serve-latency line pools EVERY peer's summary — a slow
    backend must not hide behind whichever peer iterates last."""
    snap = {
        "gauges": {}, "quality": {}, "last_seen": {"a": 0.0, "b": 0.0},
        "summaries": {
            "a": {"hypha.serve.request_latency_ms": summarize([800.0] * 50)},
            "b": {"hypha.serve.request_latency_ms": summarize([40.0] * 50)},
        },
    }
    frame = top.render(snap, now=1.0)
    assert "serve latency ms" in frame
    # Fleet p99 must reflect the slow backend's 800 ms tail.
    assert "800" in frame


def test_sweep_journals_silence_breach(tmp_path):
    """A breach whose edge lands on the periodic sweep (all reporters
    dead — silence's primary case) must reach the journal."""
    async def main():
        sched, worker = await _two_nodes()
        collector = MetricsCollector(
            sched, "job-1", journal_dir=tmp_path,
            slo_rules=["silent_s <= 0.5"],
        ).start()
        report = MetricsReport(job_id="job-1-w0", peer="w0", interval_s=0.1)
        await worker.request("sched", PROTOCOL_METRICS, report)
        # No further reports: the sweep's clock must trip the rule.
        for _ in range(60):
            if collector.watchdog.breaches:
                break
            await asyncio.sleep(0.1)
        assert collector.watchdog.breaches >= 1
        await asyncio.sleep(0.1)
        await collector.close()
        await sched.stop()
        await worker.stop()

    run(main())
    recs = [
        json.loads(ln)
        for j in tmp_path.glob("metrics-*.jsonl")
        for ln in j.read_text().splitlines()
    ]
    slo_recs = [r for r in recs if r["type"] == "slo" and r["breached"]]
    assert slo_recs, "sweep-edge breach never reached the journal"
