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


# ---------------------------------------------------------------------------
# PR 42: a span at each end of a push, what a phase cost the process, a loop
# that says when it was held, one clock with the profiler's trace
# ---------------------------------------------------------------------------


def _read_spans(trace_dir) -> list[dict]:
    out = []
    for path in sorted(trace_dir.glob("spans-*.jsonl")):
        out += [json.loads(x) for x in path.read_text().splitlines()]
    return out


async def _tcp_pair():
    from hypha_tpu.network import Node, TcpTransport

    a, b = Node(TcpTransport(), peer_id="a"), Node(TcpTransport(), peer_id="b")
    await a.start(["127.0.0.1:0"])
    await b.start(["127.0.0.1:0"])
    a.add_peer_addr("b", b.listen_addrs[0])
    return a, b


def _push_through_the_connectors(tmp_path, meta: dict, nbytes: int = 9_000_001, spy=None):
    """A file from node ``a`` to node ``b`` by ``Connector.send`` and
    ``Connector.receive``, as a worker's bridge and a PS's broadcast's
    receiver use them. Returns what ``b`` received."""
    import asyncio

    from hypha_tpu.messages import Receive, Reference, Send
    from hypha_tpu.worker.connectors import Connector

    payload = random.Random(7).randbytes(nbytes)
    src = tmp_path / "delta-3.safetensors"
    src.write_bytes(payload)

    async def main():
        a, b = await _tcp_pair()
        if spy is not None:
            spy(a)
        try:
            incoming = Connector(b).receive(
                Receive(Reference.from_peers(["a"], "updates")), tmp_path / "incoming"
            )
            landed = asyncio.ensure_future(anext(incoming))
            await Connector(a).send(
                Send(Reference.from_peers(["b"], "updates")), src, "updates", meta
            )
            got = await asyncio.wait_for(landed, 30)
            await incoming.aclose()
            assert got.path.read_bytes() == payload and got.size == nbytes
            return got, {n.peer_id: {t.get_name() for t in n._tasks} for n in (a, b)}
        finally:
            await a.stop()
            await b.stop()

    return asyncio.run(main())


@pytest.mark.parametrize("path", ["loop", "thread"])
def test_a_push_leaves_a_span_at_each_end_of_one_trace(tmp_path, tracing_on, path):
    pages = "fresh"
    if path == "thread":
        # What the stream's last push left once its consumer unlinked it: a
        # push that finds a spare is the one the drain thread takes.
        from hypha_tpu.worker.connectors import _safe_name

        spare = tmp_path / "incoming" / "spare" / f"{_safe_name('a-updates')}.bin"
        spare.parent.mkdir(parents=True)
        spare.write_bytes(b"\xee" * 10_000_000)
        pages = "recycled"
    root = tracing_on.begin("round", attrs={"round": 3}, node="sched")
    meta = trace.inject({"num_samples": 8.0, "round": 3}, root)
    got, _ = _push_through_the_connectors(tmp_path, meta)
    assert got.meta["round"] == 3
    spans = _read_spans(tmp_path)
    (send,) = [s for s in spans if s["name"] == "send"]
    (receive,) = [s for s in spans if s["name"] == "receive"]
    assert (send["node"], receive["node"]) == ("a", "b")
    for s in (send, receive):
        assert s["trace_id"] == root.trace_id and s["parent_id"] == root.span_id
        assert s["ok"] is True and s["attrs"]["round"] == 3 and s["attrs"]["bytes"] == 9_000_001
        assert {"cpu_user_s", "cpu_sys_s", "minflt", "maxrss_kb"} <= set(s["attrs"])
    assert (send["attrs"]["peer"], send["attrs"]["attempt"]) == ("b", 1)
    a = receive["attrs"]
    assert (a["peer"], a["resource"], a["pages"], a["path"]) == ("a", "updates", pages, path)
    took = (receive["mono_end_ns"] - receive["mono_start_ns"]) / 1e9
    assert a["read_s"] >= 0 and a["write_s"] > 0
    if path == "thread":  # there the two part socket from file, and nothing overlaps them
        assert a["read_s"] + a["write_s"] <= took
    # the header arrives after the sender began, the payload is on disk after it was sent
    assert send["mono_start_ns"] <= receive["mono_start_ns"]


def test_a_send_that_fails_and_a_receive_that_is_cut_are_spans_that_say_so(tmp_path, tracing_on, monkeypatch):
    import asyncio

    from hypha_tpu.messages import Receive, Reference, Send
    from hypha_tpu.network.node import PushStream
    from hypha_tpu.worker.connectors import Connector

    monkeypatch.setenv("HYPHA_PUSH_RETRY_DEADLINE", "0.3")
    src = tmp_path / "delta-0.safetensors"
    src.write_bytes(b"x" * 1000)

    async def gone(self, path, **_):
        raise ConnectionError("the sender went away")

    async def main():
        a, b = await _tcp_pair()
        try:
            with pytest.raises(Exception):  # nobody listens at the address of "c"
                a.add_peer_addr("c", "127.0.0.1:9")
                await Connector(a).send(
                    Send(Reference.from_peers(["c"], "updates")), src, "updates", {"round": 0}
                )
            monkeypatch.setattr(PushStream, "save_to", gone)
            incoming = Connector(b).receive(
                Receive(Reference.from_peers(["a"], "updates")), tmp_path / "incoming"
            )
            landed = asyncio.ensure_future(anext(incoming))
            pushing = asyncio.ensure_future(a.push("b", {"resource": "updates", "round": 1}, b"y" * 10))
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(landed, 30)
            await asyncio.gather(pushing, return_exceptions=True)
        finally:
            await a.stop()
            await b.stop()

    asyncio.run(main())
    spans = _read_spans(tmp_path)
    sends = [s for s in spans if s["name"] == "send"]
    assert sends and all(s["ok"] is False and s["attrs"]["peer"] == "c" for s in sends)
    assert [s["attrs"]["attempt"] for s in sends] == list(range(1, len(sends) + 1))
    (receive,) = [s for s in spans if s["name"] == "receive"]
    assert receive["ok"] is False and receive["attrs"]["round"] == 1 and "bytes" not in receive["attrs"]


def test_the_broadcasts_timing_is_left_on_what_the_caller_hands_push(tmp_path):
    import asyncio

    async def main():
        a, b = await _tcp_pair()
        try:
            timing: dict = {}
            taken = asyncio.ensure_future(b.next_push())
            n = await a.push("b", {"resource": "results"}, b"z" * 100_000, timing=timing)
            push = await asyncio.wait_for(taken, 30)
            assert len(await push.read_all()) == n == 100_000
            return timing
        finally:
            await a.stop()
            await b.stop()

    timing = asyncio.run(main())
    assert set(timing) == {"connect_s", "send_s", "close_s"} and all(v >= 0 for v in timing.values())


def _burn(seconds: float) -> None:
    import time as _time

    end = _time.process_time() + seconds
    while _time.process_time() < end:
        sum(range(1000))


@pytest.mark.parametrize("how", ["begin", "span", "phase"])
def test_a_usage_span_carries_what_its_interval_cost_the_process(tmp_path, tracing_on, how):
    import numpy as np

    def busy_and_first_touch():
        _burn(0.05)
        fresh = np.empty(64 << 20, np.uint8)  # untouched anonymous memory
        fresh[:: 4096] = 1  # a fault a page: 16 384 of 4 KiB, 32 where the kernel hands out 2 MiB
        return fresh

    if how == "begin":
        s = trace.begin("encode", attrs={"round": 0}, usage=True)
        keep = busy_and_first_touch()
        trace.finish(s)
    elif how == "span":
        with trace.span("encode", attrs={"round": 0}, usage=True):
            keep = busy_and_first_touch()
    else:
        with trace.phase("encode", attrs={"round": 0}, usage=True):
            keep = busy_and_first_touch()
    with trace.span("encode.plain", attrs={"round": 0}):
        _burn(0.01)
    del keep
    with_usage, plain = _read_spans(tmp_path)
    a = with_usage["attrs"]
    assert a["cpu_user_s"] + a["cpu_sys_s"] >= 0.04
    assert a["minflt"] >= 16 and a["maxrss_kb"] > 64 * 1024
    took = (with_usage["mono_end_ns"] - with_usage["mono_start_ns"]) / 1e9
    assert a["cpu_user_s"] + a["cpu_sys_s"] <= took * (1 + 8)  # the process's, every thread's
    assert not {"cpu_user_s", "cpu_sys_s", "minflt", "maxrss_kb"} & set(plain["attrs"])


def test_a_deferred_usage_phase_reads_the_counters_at_its_end_not_at_its_writing(tmp_path, tracing_on):
    with trace.phase("step", usage=True, defer=True) as ph:
        pass
    _burn(0.05)  # after the interval: not the span's
    ph.write()
    (s,) = _read_spans(tmp_path)
    assert s["attrs"]["cpu_user_s"] + s["attrs"]["cpu_sys_s"] < 0.04


def _hold_the_loop(node_name: str, switch) -> list[dict]:
    """A node's loop held 200 ms by a blocking call, after a span of round 5
    was written for the node."""
    import asyncio
    import time as _time

    from hypha_tpu.network import MemoryTransport, Node
    from hypha_tpu.network.node import LOOP_WATCH_TASK

    async def main():
        node = Node(MemoryTransport().shared(), peer_id=node_name)
        switch(node)
        await node.start()
        try:
            assert [t.get_name() for t in node._tasks].count(LOOP_WATCH_TASK) == 1
            await asyncio.sleep(0.12)  # the watch is asleep, on time so far
            with trace.span("merge", attrs={"round": 5}, node=node_name):
                pass
            _time.sleep(0.2)  # the loop's thread stands still
            await asyncio.sleep(0.12)
        finally:
            await node.stop()
        assert not node._tasks

    asyncio.run(main())


@pytest.mark.parametrize("way", ["enable_before_main", "config_key"])
def test_a_held_loop_writes_one_loop_stall_with_the_nodes_last_round(tmp_path, tracing_off, way):
    from hypha_tpu import cli

    telemetry = []

    def switch(node):
        if way == "enable_before_main":  # perfbench/traced_entry.py
            trace.enable(tmp_path, node="w0")
        else:  # telemetry.trace_dir through the call every role's runner makes
            conf = _role_config("worker", f"telemetry.trace_dir={tmp_path}")
            telemetry.append(cli._telemetry_for(conf, node))

    try:
        _hold_the_loop("w0", switch)
    finally:
        for t in telemetry:
            t.shutdown()
    stalls = [s for s in _read_spans(tmp_path) if s["name"] == "loop_stall"]
    assert len(stalls) == 1, stalls
    (s,) = stalls
    assert s["node"] == "w0" and s["attrs"]["round"] == 5
    assert 0.15 <= s["attrs"]["lag_s"] < 1.0
    assert s["mono_start_ns"] == s["mono_end_ns"] and s["start_ns"] == s["end_ns"]
    # the loop stood still from when the watch was due to the record's own time
    assert s["mono_end_ns"] - s["attrs"]["due_mono_ns"] == pytest.approx(s["attrs"]["lag_s"] * 1e9, abs=5e6)


def test_an_instant_record_keeps_a_round_it_names_and_has_none_before_any_span(tmp_path, tracing_on):
    trace.instant("loop_stall", attrs={"lag_s": 0.1}, node="w9")
    with trace.span("merge", attrs={"round": 2}, node="w9"):
        pass
    trace.instant("clock_mark", attrs={"round": 7}, node="w9")
    trace.instant("loop_stall", attrs={"lag_s": 0.1}, node="w9")
    first, _, named, last = _read_spans(tmp_path)
    assert "round" not in first["attrs"] and named["attrs"]["round"] == 7
    assert last["attrs"]["round"] == 7  # the last record written for the node


def test_hypha_clock_is_in_a_profiler_sessions_trace_with_both_clocks(tmp_path, tracing_on):
    import time as _time

    import jax
    from jax.profiler import ProfileData

    from hypha_tpu.executor.training import _RoundTrace

    rtrace = _RoundTrace("w0")
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        before = _time.time_ns(), _time.monotonic_ns()
        rtrace.clock_mark(3)
        after = _time.time_ns(), _time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    (mark,) = [s for s in _read_spans(tmp_path) if s["name"] == "clock_mark"]
    assert mark["node"] == "w0" and mark["attrs"]["round"] == 3
    assert mark["mono_start_ns"] == mark["mono_end_ns"]
    wall_ns, mono_ns = mark["attrs"]["wall_ns"], mark["attrs"]["mono_ns"]
    assert before[0] <= wall_ns <= after[0] and before[1] <= mono_ns <= after[1]
    (xplane,) = (tmp_path / "profile").glob("plugins/profile/*/*.xplane.pb")
    names = [
        ev.name
        for plane in ProfileData.from_file(str(xplane)).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("hypha_clock")
    ]
    assert names == [f"hypha_clock wall_ns={wall_ns} mono_ns={mono_ns}"]


def test_off_nothing_is_read_started_or_sent(tmp_path, tracing_off, monkeypatch):
    """Tracing off: no ``getrusage``, no watch task, no clock mark, and the
    push header's bytes are the ones a program from before these spans sent."""
    import resource

    from hypha_tpu.executor.training import _RoundTrace
    from hypha_tpu.network.node import LOOP_WATCH_TASK

    def refused(*_):
        raise AssertionError("getrusage read with tracing off")

    monkeypatch.setattr(resource, "getrusage", refused)
    headers = []

    def spy(node):
        push = node.push

        async def push_spy(peer, header, source, **kw):
            assert not kw  # no timing is asked of an untraced push
            headers.append(messages.encode(header))
            return await push(peer, header, source)

        node.push = push_spy

    got, tasks = _push_through_the_connectors(
        tmp_path, {"num_samples": 8.0, "round": 2}, nbytes=100_000, spy=spy
    )
    assert headers == [codec.dumps(
        {"num_samples": 8.0, "round": 2, "resource": "updates", "name": "delta-3.safetensors"}
    )]
    assert got.meta == {"num_samples": 8.0, "round": 2, "resource": "updates",
                        "name": "delta-3.safetensors"}
    assert all(LOOP_WATCH_TASK not in names for names in tasks.values())
    for opened in (
        trace.begin("encode", usage=True), trace.phase("encode", usage=True).__enter__().span
    ):
        assert opened is None
    with trace.span("merge", usage=True) as s, trace.phase("merge.read", usage=True) as ph:
        assert s is None and ph.span is None
    _RoundTrace("w0").clock_mark(0)
    trace.instant("loop_stall", attrs={"lag_s": 1.0}, node="w0")
    assert not list(tmp_path.glob("spans-*.jsonl"))
