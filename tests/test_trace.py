"""End-to-end round tracing tests: the wire-bit-equality guarantee
(tracing off ships today's exact bytes), the traceparent format, the
per-node span recorder, the flight recorder ring, and the scheduler's
per-round root-span propagation."""

from __future__ import annotations

import argparse
import json
import random

import pytest

from hypha_tpu import codec, messages
from hypha_tpu.messages import (
    TRACEPARENT_KEY,
    GenerateRequest,
    Progress,
    ProgressKind,
    ProgressResponse,
    ProgressResponseKind,
)
from hypha_tpu.scheduler.batch_scheduler import BatchScheduler
from hypha_tpu.scheduler.trackers import ProgressTracker
from hypha_tpu.telemetry import trace
from hypha_tpu.telemetry.flight import FlightRecorder


@pytest.fixture
def tracing_off():
    """Guarantee tracing is globally OFF and reset state afterwards."""
    trace.disable()
    yield
    trace.disable()


@pytest.fixture
def tracing_on(tmp_path):
    trace.disable()
    t = trace.enable(tmp_path, node="testnode")
    yield t
    trace.disable()


# -------------------------------------------------- wire-bit equality


def test_progress_off_wire_bytes_are_pre_tracing_exact(tracing_off):
    """The traceparent field is omitted entirely at None: byte-for-byte the
    pre-tracing wire (the PR-8 additive-field discipline)."""
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
    assert "traceparent" not in messages.to_json_dict(p)


def test_progress_response_off_wire_bytes_exact(tracing_off):
    r = ProgressResponse(
        kind=ProgressResponseKind.SCHEDULE_UPDATE, counter=7
    )
    golden = codec.dumps(
        {
            "_t": "ProgressResponse",
            "kind": {"_e": "ProgressResponseKind", "v": "schedule-update"},
            "counter": 7,
            "message": "",
        }
    )
    assert messages.encode(r) == golden


def test_generate_request_off_wire_bytes_exact(tracing_off):
    req = GenerateRequest(serve_name="llm", prompts=[[1, 2]], seed=4)
    golden = codec.dumps(
        {
            "_t": "GenerateRequest",
            "serve_name": "llm",
            "prompts": [[1, 2]],
            "max_new_tokens": 64,
            "seed": 4,
        }
    )
    assert messages.encode(req) == golden


def test_push_header_gains_no_key_when_off(tracing_off):
    header = {"round": 2, "num_samples": 8.0}
    before = codec.dumps(header)
    out = trace.inject(header, None)
    assert out is header
    assert codec.dumps(out) == before
    assert TRACEPARENT_KEY not in out


def test_traceparent_round_trips_when_set():
    tp = "ab" * 16 + "-" + "cd" * 8
    p = Progress(kind=ProgressKind.UPDATE, job_id="j", traceparent=tp)
    back = messages.decode(messages.encode(p))
    assert back.traceparent == tp
    header = trace.inject({"round": 1}, tp)
    assert header[TRACEPARENT_KEY] == tp


# ----------------------------------------------------- traceparent fmt


def test_parse_traceparent():
    tp = "ab" * 16 + "-" + "cd" * 8
    assert trace.parse_traceparent(tp) == ("ab" * 16, "cd" * 8)
    for bad in (None, 7, "", "xx", "ab-cd", "g" * 32 + "-" + "cd" * 8,
                "ab" * 16 + "cd" * 8, "ab" * 16 + "-" + "cd" * 7):
        assert trace.parse_traceparent(bad) is None


def test_ids_use_urandom_not_seeded_global_rng():
    """Seeded deterministic chaos runs seed the GLOBAL rng; trace/span ids
    must not become deterministic (they would collide across nodes in one
    merged timeline). Regression for telemetry._rand_id too."""
    from hypha_tpu.telemetry import _rand_id

    random.seed(1234)
    a = (_rand_id(16), trace._rand_hex(16))
    random.seed(1234)
    b = (_rand_id(16), trace._rand_hex(16))
    assert a[0] != b[0] and a[1] != b[1]
    assert len(a[0]) == 32 and len(a[1]) == 32


# ----------------------------------------------------- span recorder


def test_node_tracing_writes_per_node_jsonl(tmp_path, tracing_on):
    t = tracing_on
    root = t.begin("round", attrs={"round": 0}, node="scheduler")
    child = t.begin("upload", parent=root.traceparent, attrs={"peer": "w0"})
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    t.finish(child)
    t.finish(root)
    with t.span("merge", parent=root, attrs={"round": 0}) as s:
        assert s.trace_id == root.trace_id
    sched = [
        json.loads(line)
        for line in (tmp_path / "spans-scheduler.jsonl").read_text().splitlines()
    ]
    local = [
        json.loads(line)
        for line in (tmp_path / "spans-testnode.jsonl").read_text().splitlines()
    ]
    assert [s["name"] for s in sched] == ["round"]
    assert [s["name"] for s in local] == ["upload", "merge"]
    up = local[0]
    assert up["trace_id"] == root.trace_id
    assert up["end_ns"] >= up["start_ns"]
    assert up["attrs"] == {"peer": "w0"}


def test_module_helpers_noop_when_off(tracing_off):
    assert trace.active() is None
    assert trace.begin("x") is None
    trace.finish(None)  # must not raise
    with trace.span("y") as s:
        assert s is None
    assert trace.traceparent_of(None) is None


ROLES = ("gateway", "scheduler", "worker", "data")


def _role_config(role: str, *sets: str):
    """What ``hypha-tpu <role> run --set ...`` builds, environment included."""
    from hypha_tpu import cli

    if role == "data":  # a data node refuses to build without a dataset
        sets += ("datasets.toy=/nonexistent",)
    return cli._load_config(
        role, argparse.Namespace(config=None, set=list(sets), name="n1")
    )


@pytest.mark.parametrize("role", ROLES)
def test_config_key_enables_tracing(role, tmp_path, monkeypatch, tracing_off):
    """The one switch: ``telemetry.trace_dir``, here from the environment,
    through the call every role's runner makes."""
    from hypha_tpu import cli

    monkeypatch.setenv("HYPHA_TELEMETRY__TRACE_DIR", str(tmp_path))
    conf = _role_config(role)
    assert conf.telemetry.trace_dir == str(tmp_path)
    telemetry = cli._telemetry_for(conf)
    try:
        t = trace.active()
        assert t is not None and t.node == "n1" and t.trace_dir == tmp_path
    finally:
        telemetry.shutdown()


@pytest.mark.parametrize("role", ROLES)
def test_set_flag_carries_the_trace_dir_and_default_is_off(role, tmp_path, tracing_off):
    from hypha_tpu import cli

    conf = _role_config(role, f"telemetry.trace_dir={tmp_path}")
    assert conf.telemetry.trace_dir == str(tmp_path)
    off = _role_config(role)
    assert off.telemetry.trace_dir == ""
    cli._telemetry_for(off).shutdown()
    assert trace.active() is None and not list(tmp_path.iterdir())


@pytest.mark.parametrize("role", ROLES)
def test_the_old_environment_switch_is_refused_by_name(role, tmp_path, monkeypatch):
    """``HYPHA_TRACE_DIR`` was never a config key: every role reads
    ``HYPHA_*`` as configuration and says which key it does not know."""
    from hypha_tpu.config import ConfigError

    monkeypatch.setenv("HYPHA_TRACE_DIR", str(tmp_path))
    with pytest.raises(ConfigError, match="unknown config key 'trace_dir'"):
        _role_config(role)


def test_nothing_in_the_environment_enables_tracing(tmp_path, monkeypatch, tracing_off):
    monkeypatch.setenv("HYPHA_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HYPHA_TELEMETRY__TRACE_DIR", str(tmp_path))
    assert trace.active() is None and trace.begin("x") is None


def test_child_executor_is_handed_the_trace_dir_on_its_command_line(tmp_path, tracing_off):
    from hypha_tpu.executor.training import build_parser

    args = build_parser().parse_args(
        ["--socket", "s", "--work-dir", "w", "--job", "{}",
         "--trace-dir", str(tmp_path), "--trace-node", "w7"]
    )
    assert (args.trace_dir, args.trace_node) == (str(tmp_path), "w7")
    bare = build_parser().parse_args(["--socket", "s", "--work-dir", "w", "--job", "{}"])
    assert bare.trace_dir == ""


# ------------------------------------------------------------- phases


def test_phase_times_into_the_dict_with_tracing_off(tracing_off):
    times: dict = {}
    with trace.phase("outer_step.mean", into=times, key="mean_s") as ph:
        ph.set("bytes", 1)
    with trace.phase("outer_step.mean", into=times, key="mean_s"):
        pass
    assert ph.span is None and times["mean_s"] >= ph.seconds > 0


def test_phase_span_and_dict_are_one_timing(tmp_path, tracing_on):
    times: dict = {}
    root = tracing_on.begin("outer_step", attrs={"round": 4}, node="ps")
    with trace.phase("outer_step.mean", parent=root, into=times, key="mean_s") as ph:
        ph.set("bytes", 12)
    trace.finish(root)
    child, parent = [
        json.loads(x) for x in (tmp_path / "spans-ps.jsonl").read_text().splitlines()
    ]
    assert child["name"] == "outer_step.mean" and child["parent_id"] == parent["span_id"]
    assert child["node"] == "ps" and child["attrs"] == {"round": 4, "bytes": 12}
    assert times["mean_s"] == (child["mono_end_ns"] - child["mono_start_ns"]) / 1e9


def test_phase_min_s_drops_the_span_not_the_seconds(tmp_path, tracing_on):
    times: dict = {}
    with trace.phase("cleanup", into=times, min_s=60.0):
        pass
    assert times["cleanup"] > 0
    assert not (tmp_path / "spans-testnode.jsonl").exists()


def test_deferred_phase_is_written_later_with_the_end_it_had(tmp_path, tracing_on):
    with trace.phase("step", defer=True) as ph:
        pass
    assert not (tmp_path / "spans-testnode.jsonl").exists()
    ph.set("status_s", 0.25)
    ph.write()
    (rec,) = [json.loads(x) for x in (tmp_path / "spans-testnode.jsonl").read_text().splitlines()]
    assert rec["attrs"] == {"status_s": 0.25}
    assert (rec["mono_end_ns"] - rec["mono_start_ns"]) / 1e9 == ph.seconds


def test_phase_enters_and_leaves_its_annotation(tracing_off):
    seen = []

    class Annotation:
        def __enter__(self):
            seen.append("in")

        def __exit__(self, *exc):
            seen.append("out")

    with pytest.raises(KeyError):
        with trace.phase("encode", annotation=Annotation()):
            raise KeyError("x")
    assert seen == ["in", "out"]


def test_reparent_binds_only_parentless_spans(tracing_on):
    t = tracing_on
    orphan = t.begin("quorum_wait")
    tp = "ab" * 16 + "-" + "cd" * 8
    trace.reparent(orphan, tp)
    assert orphan.trace_id == "ab" * 16 and orphan.parent_id == "cd" * 8
    child = t.begin("fold", parent=orphan)
    trace.reparent(child, "ef" * 16 + "-" + "12" * 8)  # keeps its parent
    assert child.parent_id == orphan.span_id


# --------------------------------------------------- flight recorder


def test_flight_recorder_ring_and_spill(tmp_path):
    fr = FlightRecorder(capacity=4, node="psw")
    for i in range(7):
        fr.record("retry", attempt=i)
    events = fr.snapshot()
    assert len(events) == 4  # bounded ring keeps the newest
    assert [e["attrs"]["attempt"] for e in events] == [3, 4, 5, 6]
    fr.record("chaos.kill", node="w1", target="w1")
    paths = fr.spill(tmp_path)
    assert sorted(p.name for p in paths) == [
        "events-psw.jsonl", "events-w1.jsonl",
    ]
    w1 = [
        json.loads(line)
        for line in (tmp_path / "events-w1.jsonl").read_text().splitlines()
    ]
    assert w1[0]["event"] == "chaos.kill"
    assert "t_mono_ns" in w1[0] and "t_wall_ns" in w1[0]
    # Spill DRAINS: a second spill (the atexit hook) writes no duplicates.
    assert fr.snapshot() == []
    assert fr.spill(tmp_path) == []
    # No spill dir configured and none passed: no-op.
    assert FlightRecorder().spill() == []


def test_flight_recorder_sanitizes_attrs(tmp_path):
    fr = FlightRecorder(node="n")
    fr.record("x", peers={"w1", "w0"}, err=ValueError("boom"))
    (rec,) = fr.snapshot()
    json.dumps(rec)  # JSON-clean
    assert sorted(rec["attrs"]["peers"]) == ["w0", "w1"]
    assert rec["attrs"]["err"] == "boom"


# ------------------------------------- scheduler round-span propagation


def _drive_round(bs, now):
    from hypha_tpu.messages import Progress as P

    def status(peer, t_ms):
        now[0] = t_ms / 1000.0
        return bs.on_progress(
            peer, P(kind=ProgressKind.STATUS, batch_size=10)
        )

    return status


def test_scheduler_hands_down_round_context_when_on(tmp_path, tracing_on):
    now = [0.0]
    tracker = ProgressTracker(
        "ps", update_target=60, update_epochs=2, clock=lambda: now[0]
    )
    tracker.add_worker("w0", 10)
    tracker.add_worker("w1", 10)
    bs = BatchScheduler(tracker)
    status = _drive_round(bs, now)
    status("w0", 100)
    scheduled = []
    for t_ms in range(200, 1200, 100):
        for w in ("w0", "w1"):
            r = status(w, t_ms)
            if r.kind is ProgressResponseKind.SCHEDULE_UPDATE:
                scheduled.append(r)
        if len(scheduled) >= 2:
            break
    assert scheduled, "no SCHEDULE_UPDATE produced"
    tp0 = scheduled[0].traceparent
    assert trace.parse_traceparent(tp0) is not None
    assert all(s.traceparent == tp0 for s in scheduled)
    for w in ("w0", "w1"):
        bs.on_progress(w, Progress(kind=ProgressKind.UPDATE))
    r = bs.on_progress("ps", Progress(kind=ProgressKind.UPDATED, round=0))
    # The Updated reply hands the PS the NEXT round's context.
    tp1 = r.traceparent
    assert tp1 is not None and tp1 != tp0
    # Workers' Continue also carries round 1's context.
    r = bs.on_progress("w0", Progress(kind=ProgressKind.UPDATE_RECEIVED))
    assert r.kind is ProgressResponseKind.CONTINUE
    assert r.traceparent == tp1
    # Round 0's root span was written at rotation, attributed round=0.
    spans = [
        json.loads(line)
        for line in (tmp_path / "spans-scheduler.jsonl").read_text().splitlines()
    ]
    assert [(s["name"], s["attrs"]["round"]) for s in spans] == [("round", 0)]
    assert f"{spans[0]['trace_id']}-{spans[0]['span_id']}" == tp0


def test_scheduler_responses_untouched_when_off(tracing_off):
    now = [0.0]
    tracker = ProgressTracker(
        "ps", update_target=60, update_epochs=1, clock=lambda: now[0]
    )
    tracker.add_worker("w0", 10)
    bs = BatchScheduler(tracker)
    status = _drive_round(bs, now)
    resp = None
    for t_ms in range(100, 1200, 100):
        r = status("w0", t_ms)
        if r.kind is ProgressResponseKind.SCHEDULE_UPDATE:
            resp = r
            break
    assert resp is not None and resp.traceparent is None
    r = bs.on_progress("ps", Progress(kind=ProgressKind.UPDATED, round=0))
    assert r.traceparent is None
