"""The split of a blocking DiLoCo round from inside the program.

One worker and one parameter server run a two-round job in-process on the
memory fabric, once with the round trace on and once with it off. On: every
phase span of docs/observability.md's table is there, under its parent, in
its parent's interval, tagged with its round, and each round has one ``step``
span an inner step whose median is the line's ``median_step_s``. Off: the
same numbers are on the lines the roles log, as ``key=value``. The momentum
file's two spans exist only where the file is read or written: a third pair
of jobs runs under a ``checkpoint_dir``, the second of them warm-started.
"""

from __future__ import annotations

import json
import logging
import re
import statistics
from collections import defaultdict

import pytest

from hypha_tpu.data_node import DataNode
from hypha_tpu.gateway import Gateway
from hypha_tpu.network import MemoryTransport, Node
from hypha_tpu.resources import Resources
from hypha_tpu.scheduler.orchestrator import Orchestrator
from hypha_tpu.telemetry import trace
from hypha_tpu.worker import arbiter
from hypha_tpu.worker.arbiter import OfferConfig
from hypha_tpu.worker.runtime import WorkerNode
from pathlib import Path

from perfbench import logs
from test_e2e import diloco_job, make_dataset, run

REPO = Path(__file__).resolve().parent.parent
ROUNDS = 2
# parent span name -> its phase spans (docs/observability.md)
CHILDREN = {
    "outer_step": ["outer_step.nesterov", "outer_step.save_update"],
    "fold": ["fold.read", "fold.accumulate"],
    "encode": ["encode.extract", "encode.write"],
    "merge": ["merge.read", "merge.apply"],
    "inner_steps": ["step"],
}
ROUND_LEVEL = ["notify", "await_update"]  # children of the round itself
MOVES_DATA = [
    "outer_step.nesterov", "outer_step.save_update", "fold.read",
    "encode.extract", "encode.write", "merge.read",
]
# Emitted only when the momentum file is read or written: under a
# checkpoint_dir, and the read only where a file was there before round 0.
MOMENTUM_FILE = ["outer_step.load_momentum", "outer_step.save_momentum"]
NEW_OUTER_FIELDS = ["threads", "momentum_resident", "momentum_saved"]
ROUND_FIELDS = {"steps_sum_s", "max_step_s", "status_s", "input_wait_s",
                "median_step_s", "first_step_s", "wall_s", "steps", "tokens"}
SYNC_FIELDS = {"round", "encode_s", "upload_s", "wait_s", "merge_s", "cleanup_s",
               "bytes_up", "bytes_down"}
OUTER_FIELDS = {"round", "wall_s", "mean_s", "load_s", "nesterov_s",
                "save_update_s", "save_momentum_s", "bytes", "native_kernels"}
LOGGERS = ("hypha.executor.training", "hypha.worker.ps", "hypha.worker.arbiter",
           "hypha.scheduler.worker")
# The first job of the process lasts some ten seconds, most of them set-up
# and compiling: a lease this short is renewed inside it, and a loop that
# stands still for a second keeps it. The second job (untraced) can be over
# before its first renewal; test_lease_margin.py has the lines with tracing off.
LEASE_S = 3.0


class _Lines(logging.Handler):
    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.lines: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(record.getMessage())


async def _job(tmp_path, checkpoint_dir=None):
    hub = MemoryTransport()
    gw = Gateway(hub.shared(), peer_id="gw")
    await gw.start()
    boot = [gw.node.listen_addrs[0]]
    data = DataNode(
        hub.shared(), {"toy": make_dataset(tmp_path)}, peer_id="data", bootstrap=boot
    )
    await data.start()
    workers = []
    for name, res in (("w0", Resources(tpu=1.0, cpu=8, memory=1000)),
                      ("ps", Resources(cpu=2, memory=200))):
        w = WorkerNode(
            hub.shared(), resources=res, peer_id=name, bootstrap=boot,
            offer=OfferConfig(price=1.0, strategy="whole"),
            work_root=tmp_path / name,
        )
        await w.start()
        workers.append(w)
    sched = Node(hub.shared(), peer_id="sched", bootstrap=boot)
    await sched.start()
    await sched.wait_for_bootstrap()
    job = diloco_job(rounds=ROUNDS)
    job.resources.num_workers = 1
    job.checkpoint_dir = checkpoint_dir
    try:
        return await Orchestrator(sched).run(job, auction_timeout=1.5)
    finally:
        for w in workers:
            await w.stop()
        await data.stop()
        await sched.stop()
        await gw.stop()


def _run_job(tmp_path, checkpoint_dir=None, patch=None) -> list[str]:
    """The job's log lines (worker, parameter server, arbiters, scheduler).
    ``patch(mp)`` sets what a test wants replaced while the job runs."""
    handler = _Lines()
    loggers = [logging.getLogger(n) for n in LOGGERS]
    levels = [lg.level for lg in loggers]
    for lg in loggers:
        lg.addHandler(handler)
        lg.setLevel(logging.INFO)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(arbiter, "LEASE_TIMEOUT_S", LEASE_S)
            if patch is not None:
                patch(mp)
            result = run(_job(tmp_path, checkpoint_dir))
    finally:
        for lg, level in zip(loggers, levels):
            lg.removeHandler(handler)
            lg.setLevel(level)
    assert result.rounds == ROUNDS
    return handler.lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traced")
    trace.enable(tmp / "spans", node="sched")
    try:
        lines = _run_job(tmp)
    finally:
        trace.disable()
    return _spans(tmp), lines


def _spans(tmp) -> list[dict]:
    return [json.loads(x) for path in sorted((tmp / "spans").glob("spans-*.jsonl"))
            for x in path.read_text().splitlines()]


@pytest.fixture(scope="module")
def checkpointed(tmp_path_factory):
    """Two traced jobs, one after the other, under one ``checkpoint_dir``:
    the second starts from the momentum the first left there. Returns the
    spans and lines of each."""
    tmp = tmp_path_factory.mktemp("checkpointed")
    jobs = []
    for n in ("first", "warm"):
        (tmp / n).mkdir()
        trace.enable(tmp / n / "spans", node="sched")
        try:
            lines = _run_job(tmp / n, checkpoint_dir=str(tmp / "ckpt"))
        finally:
            trace.disable()
        jobs.append((_spans(tmp / n), lines))
    return jobs


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("untraced")
    assert trace.active() is None
    lines = _run_job(tmp)
    assert not list(tmp.rglob("spans-*.jsonl"))
    return lines


def _named(spans, name, rnd=None):
    return [s for s in spans if s["name"] == name
            and (rnd is None or s["attrs"].get("round") == rnd)]


def _fields(lines, pattern):
    return [logs.parse_fields(m.group(0)) for x in lines
            for m in [re.search(pattern, x)] if m]


@pytest.mark.parametrize("name", sorted({c for cs in CHILDREN.values() for c in cs}
                                        | set(ROUND_LEVEL)))
def test_every_round_has_the_phase_span_with_a_parent_that_exists(traced, name):
    spans, _ = traced
    ids = {s["span_id"] for s in spans}
    for rnd in range(ROUNDS):
        found = _named(spans, name, rnd)
        assert found, f"no {name} span in round {rnd}"
        for s in found:
            assert s["ok"] and s["mono_end_ns"] >= s["mono_start_ns"]
            # Round 0's root context reaches the PS only with the first
            # delta, so its round-level spans may be roots there.
            assert s["parent_id"] in ids or (rnd == 0 and s["parent_id"] is None), s


@pytest.mark.parametrize("parent,children", sorted(CHILDREN.items()))
def test_children_lie_inside_their_parent_and_share_its_node(traced, parent, children):
    spans, _ = traced
    by_id = {s["span_id"]: s for s in spans}
    seen = set()
    for child in (s for s in spans if s["name"] in children):
        p = by_id[child["parent_id"]]
        assert p["name"] == parent and p["node"] == child["node"]
        assert p["trace_id"] == child["trace_id"]
        assert p["attrs"]["round"] == child["attrs"]["round"]
        assert p["mono_start_ns"] <= child["mono_start_ns"]
        assert child["mono_end_ns"] <= p["mono_end_ns"]
        seen.add(child["name"])
    assert seen == set(children)


@pytest.mark.parametrize("name", MOVES_DATA)
def test_a_span_that_moves_data_says_how_much(traced, name):
    spans, _ = traced
    for rnd in range(ROUNDS):
        found = _named(spans, name, rnd)
        assert found
        for s in found:
            assert s["attrs"]["bytes"] > 0 and s["attrs"].get("leaves", 1) > 0, s


@pytest.mark.parametrize("name", sorted({c for cs in CHILDREN.values() for c in cs}
                                        | set(ROUND_LEVEL) | set(MOMENTUM_FILE)
                                        | {"cleanup", "input_wait"}))
def test_the_docs_table_names_the_span(name):
    table = (REPO / "docs" / "observability.md").read_text()
    assert re.search(rf"^\| `{re.escape(name)}` \|", table, re.M), name


def test_every_emitted_span_name_is_in_the_docs(traced):
    spans, _ = traced
    table = (REPO / "docs" / "observability.md").read_text()
    for name in {s["name"] for s in spans}:
        assert f"`{name}`" in table, name


def test_existing_spans_keep_names_and_attributes(traced):
    spans, _ = traced
    for name, node, keys in (
        ("inner_steps", "w0", {"round"}), ("encode", "w0", {"round", "codec"}),
        ("upload", "w0", {"round", "codec", "bytes"}), ("merge", "w0", {"round"}),
        ("upload", "ps", {"round", "peer", "bytes"}), ("fold", "ps", {"round", "peer"}),
        ("quorum_wait", "ps", {"round"}), ("outer_step", "ps", {"round"}),
        ("broadcast", "ps", {"round"}),
    ):
        found = [s for s in _named(spans, name) if s["node"] == node]
        assert len(found) == ROUNDS, (name, node)
        for s in found:
            assert keys <= set(s["attrs"]), s


@pytest.mark.parametrize("which", ["traced", "untraced"])
@pytest.mark.parametrize("field", ["direct", "resident", "leaves", "read_s", "accumulate_s"])
def test_the_fold_says_whether_it_went_in_one_pass_into_resident_buffers(request, which, field):
    """The job sends f32 SafeTensors deltas: every leaf goes from the file
    into the sum's buffer in one pass (``direct``), and from round 1 on
    that buffer is the one round 0 left (``resident``). On the ``ps fold:``
    line with tracing on and off, and on the ``fold`` span."""
    got = request.getfixturevalue(which)
    spans, lines = got if which == "traced" else ([], got)
    folds = _fields(lines, r"ps fold: .*")
    assert [f["round"] for f in folds] == list(range(ROUNDS))
    assert all(f["peer"] == "w0" and f["sign"] == 1 and f["bytes"] > 0 for f in folds)
    leaves = folds[0]["leaves"]
    assert leaves > 0
    want = {"direct": [leaves] * ROUNDS, "resident": [0, leaves], "leaves": [leaves] * ROUNDS}
    for rnd, f in enumerate(folds):
        if field in want:
            assert f[field] == want[field][rnd], f
            for s in _named(spans, "fold", rnd):
                assert s["attrs"][field] == want[field][rnd], s
        else:
            assert f[field] >= 0
            name = {"read_s": "fold.read", "accumulate_s": "fold.accumulate"}[field]
            found = _named(spans, name, rnd)
            if spans:
                assert len(found) == leaves  # one a leaf on the one-pass path
                secs = sum((s["mono_end_ns"] - s["mono_start_ns"]) / 1e9 for s in found)
                assert abs(secs - f[field]) < 1e-3, (name, f)


@pytest.mark.parametrize("which", ["traced", "untraced"])
@pytest.mark.parametrize("field", ["direct", "resident", "leaves", "bytes"])
def test_the_merge_read_says_whether_it_went_into_buffers_the_job_kept(request, which, field):
    """The PS broadcasts f32 SafeTensors: every leaf goes from the file
    into a host buffer of the job in one pass (``direct``), and from round 1
    on that buffer is the one round 0 made (``resident``). On the worker's
    ``sync done:`` line with tracing on and off, and on ``merge.read``."""
    got = request.getfixturevalue(which)
    spans, lines = got if which == "traced" else ([], got)
    syncs = _fields(lines, r"sync done: .*")
    assert [s["round"] for s in syncs] == list(range(ROUNDS))
    leaves = syncs[0]["leaves"]
    assert leaves > 0
    want = {"direct": [leaves] * ROUNDS, "resident": [0, leaves],
            "leaves": [leaves] * ROUNDS,
            "bytes": [s["bytes_down"] for s in syncs]}
    for rnd, s in enumerate(syncs):
        if field != "bytes":
            assert s[field] == want[field][rnd], s
        found = _named(spans, "merge.read", rnd)
        assert len(found) == (1 if spans else 0)
        for span in found:
            assert span["attrs"][field] == want[field][rnd], span


@pytest.fixture(scope="module")
def merges(tmp_path_factory):
    """One more job, with every merge of the worker on record: the
    parameters that went in, the tree ``merge_update`` was given (the job's
    own host buffers), what came out (a copy made on the device as the
    round's anchor is, dispatched and not waited for: the next step donates
    the merged arrays themselves), and what ``read_delta`` makes of the
    same broadcast file, fresh, read before the job's own reader runs."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu import compress
    from hypha_tpu.executor import training

    record = {"fresh": [], "merge": []}
    read_into, merge_update = compress.read_delta_into, training.merge_update

    def spy_read(path, lease):
        record["fresh"].append(compress.read_delta(path))
        return read_into(path, lease)

    def spy_merge(params, update):
        out = merge_update(params, update)
        record["merge"].append((params, update, jax.tree.map(jnp.copy, out)))
        return out

    def patch(mp):
        mp.setattr(compress, "read_delta_into", spy_read)
        mp.setattr(training, "merge_update", spy_merge)

    _run_job(tmp_path_factory.mktemp("merges"), patch=patch)
    assert len(record["fresh"]) == len(record["merge"]) == ROUNDS
    return record


@pytest.mark.parametrize("rnd", range(ROUNDS))
def test_the_merged_parameters_are_what_a_merge_through_read_delta_gives(merges, rnd):
    """Bit for bit, in every round, and read only now, after the job: round
    0's merged parameters have outlived round 1's read, which wrote round
    1's update over the buffers round 0's merge was dispatched with."""
    import jax
    import numpy as np

    from hypha_tpu.executor.diloco import merge_update
    from hypha_tpu.executor.serialization import flatten_tree, unflatten_like

    params, _, out = merges["merge"][rnd]
    want = merge_update(params, unflatten_like(merges["fresh"][rnd], params))
    got, want = flatten_tree(jax.device_get(out)), flatten_tree(jax.device_get(want))
    assert list(got) == list(want)
    for key in want:
        assert got[key].dtype == want[key].dtype and got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes(), key
    assert any(np.any(want[k] != v) for k, v in flatten_tree(jax.device_get(params)).items())


def test_every_round_merges_from_the_same_host_buffers(merges):
    """The tree ``merge_update`` is given is the job's buffers, the same
    arrays in every round, and by the job's end they hold the last round's
    update and no longer round 0's."""
    import jax
    import numpy as np

    flat = [jax.tree.leaves(update) for _, update, _ in merges["merge"]]
    assert all(type(leaf) is np.ndarray and leaf.dtype == np.float32 for leaf in flat[0])
    for later in flat[1:]:
        assert len(later) == len(flat[0])
        assert all(a is b for a, b in zip(flat[0], later))
    held = {leaf.tobytes() for leaf in flat[0]}
    assert held == {v.tobytes() for v in merges["fresh"][-1].values()}
    assert held != {v.tobytes() for v in merges["fresh"][0].values()}


def test_merge_apply_says_that_it_ends_at_dispatch(traced):
    spans, _ = traced
    assert all(s["attrs"]["ends_at"] == "dispatch" for s in _named(spans, "merge.apply"))
    assert all(isinstance(s["attrs"]["native"], bool)
               for s in _named(spans, "outer_step.nesterov"))


@pytest.mark.parametrize("attr,want", [("threads", int), ("fused_mean", True),
                                       ("in_place", True)])
def test_the_fused_pass_says_what_it_is(traced, attr, want):
    spans, _ = traced
    found = _named(spans, "outer_step.nesterov")
    assert len(found) == ROUNDS
    for s in found:
        got = s["attrs"][attr]
        if want is int:
            # The PS node was given two cores (and a tiny model's leaves
            # run on the caller alone).
            assert type(got) is int and 1 <= got <= 2, s
        else:
            assert got is want, s


@pytest.mark.parametrize("name", ["outer_step.mean"] + MOMENTUM_FILE)
def test_a_phase_that_did_not_run_has_no_span_and_reads_zero(traced, name):
    """No checkpoint_dir: the momentum file has no reader, so it is neither
    read nor written, and the mean is inside the fused pass. A zero-length
    span would say work was done."""
    spans, lines = traced
    assert not _named(spans, name)
    key = {"outer_step.mean": "mean_s", "outer_step.load_momentum": "load_s",
           "outer_step.save_momentum": "save_momentum_s"}[name]
    assert [o[key] for o in logs.outer_steps("\n".join(lines))] == [0.0] * ROUNDS


@pytest.mark.parametrize("field", NEW_OUTER_FIELDS)
def test_the_outer_line_says_where_the_momentum_is(traced, untraced, checkpointed, field):
    """``threads``, ``momentum_resident`` (0 in the round that created or
    loaded it) and ``momentum_saved`` (the job has a checkpoint_dir), with
    tracing on and off."""
    plain = [logs.outer_steps("\n".join(x)) for x in (traced[1], untraced)]
    saved = [logs.outer_steps("\n".join(lines)) for _, lines in checkpointed]
    for outers in plain + saved:
        assert len(outers) == ROUNDS
        want = {"threads": [1] * ROUNDS, "momentum_resident": [0, 1],
                "momentum_saved": [int(outers in saved)] * ROUNDS}[field]
        assert [o[field] for o in outers] == want


@pytest.mark.parametrize("which", ["traced", "untraced", "checkpointed"])
def test_the_update_is_broadcast_from_memory_unless_a_reader_needs_a_file(request, which):
    """A plain job: ``update_in_memory=1`` on the ``ps outer step:`` line with
    tracing on and off, ``in_memory`` on ``outer_step.save_update`` (which
    now times the framing), ``source=memory`` on ``broadcast``, whose
    ``bytes`` are what the worker's ``sync done:`` line counts as received.
    Under a checkpoint_dir the durable commit hard-links the update's file,
    so there is one and it is what is pushed."""
    got = request.getfixturevalue(which)
    jobs = got if which == "checkpointed" else [got if which == "traced" else ([], got)]
    in_memory = which != "checkpointed"
    for spans, lines in jobs:
        outers = logs.outer_steps("\n".join(lines))
        assert [o["update_in_memory"] for o in outers] == [int(in_memory)] * ROUNDS
        syncs = _fields(lines, r"sync done: .*")
        for rnd in range(ROUNDS if spans else 0):
            (saved,) = _named(spans, "outer_step.save_update", rnd)
            (bcast,) = _named(spans, "broadcast", rnd)
            assert saved["attrs"]["in_memory"] is in_memory
            assert saved["attrs"]["leaves"] == syncs[rnd]["leaves"]
            assert bcast["attrs"]["source"] == ("memory" if in_memory else "file")
            assert saved["attrs"]["bytes"] == bcast["attrs"]["bytes"] == syncs[rnd]["bytes_down"]


@pytest.mark.parametrize("name", MOMENTUM_FILE)
def test_under_a_checkpoint_dir_the_momentum_file_has_its_span(checkpointed, name):
    """Written in every round, before the round's commit and broadcast; read
    once, in round 0 of the job that found a file there."""
    for job, (spans, _) in enumerate(checkpointed):
        by_id = {s["span_id"]: s for s in spans}
        found = _named(spans, name)
        if name == "outer_step.save_momentum":
            assert sorted(s["attrs"]["round"] for s in found) == list(range(ROUNDS))
        else:
            assert [s["attrs"]["round"] for s in found] == ([0] if job else [])
        for s in found:
            parent = by_id[s["parent_id"]]
            assert parent["name"] == "outer_step" and parent["node"] == s["node"] == "ps"
            assert parent["mono_start_ns"] <= s["mono_start_ns"]
            assert s["mono_end_ns"] <= parent["mono_end_ns"]
            assert s["ok"] and s["attrs"]["bytes"] > 0 and s["attrs"]["leaves"] > 0
            bcast = _named(spans, "broadcast", s["attrs"]["round"])
            assert bcast and all(s["mono_end_ns"] <= b["mono_start_ns"] for b in bcast)


def test_under_a_checkpoint_dir_the_lines_time_the_momentum_file(checkpointed):
    """The line's ``load_s`` and ``save_momentum_s`` are those spans' seconds
    (0.000 where there is no span), and ``mean_s`` stays 0."""
    keys = dict(zip(MOMENTUM_FILE, ("load_s", "save_momentum_s")))
    for spans, lines in checkpointed:
        for o in logs.outer_steps("\n".join(lines)):
            assert o["mean_s"] == 0
            for name, key in keys.items():
                secs = sum((s["mono_end_ns"] - s["mono_start_ns"]) / 1e9
                           for s in _named(spans, name, o["round"]))
                assert abs(secs - o[key]) < 1e-3, (name, o)


def test_one_step_span_a_step_and_their_median_is_the_lines(traced):
    spans, lines = traced
    done = {r["round"]: r for r in logs.rounds("\n".join(lines))}
    assert sorted(done) == list(range(ROUNDS))
    for rnd, line in done.items():
        steps = sorted(_named(spans, "step", rnd), key=lambda s: s["attrs"]["step"])
        assert [s["attrs"]["step"] for s in steps] == list(range(line["steps"]))
        assert sum(s["attrs"]["tokens"] for s in steps) == line["tokens"]
        secs = [(s["mono_end_ns"] - s["mono_start_ns"]) / 1e9 for s in steps]
        assert abs(statistics.median(secs) - line["median_step_s"]) < 1e-4
        assert abs(secs[0] - line["first_step_s"]) < 1e-3
        assert abs(sum(secs) - line["steps_sum_s"]) < 1e-3
        assert abs(max(secs) - line["max_step_s"]) < 1e-4
        for key in ("status_s", "input_wait_s"):
            assert abs(sum(s["attrs"][key] for s in steps) - line[key]) < 1e-3
        for s, sec in zip(steps, secs):
            a = s["attrs"]
            assert 0 <= a["dispatch_s"] and 0 <= a["fetch_s"]
            assert a["dispatch_s"] + a["fetch_s"] <= sec + 1e-3


def test_children_account_for_their_parent(traced):
    """Self time is small beside the parent: no phase was left out. (At
    this size the bound is loose; the chip's is in PERF.md.)"""
    spans, _ = traced
    kids = defaultdict(float)
    for s in spans:
        kids[s["parent_id"]] += (s["mono_end_ns"] - s["mono_start_ns"]) / 1e9
    for name in ("outer_step", "fold", "encode", "merge"):
        for s in _named(spans, name):
            dur = (s["mono_end_ns"] - s["mono_start_ns"]) / 1e9
            assert kids[s["span_id"]] <= dur + 1e-6
            assert dur - kids[s["span_id"]] < 0.05, (name, dur, kids[s["span_id"]])


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_the_lines_carry_the_split_with_tracing_on_and_off(request, which):
    lines = request.getfixturevalue(which)
    lines = lines[1] if which == "traced" else lines
    rounds = logs.rounds("\n".join(lines))
    syncs = _fields(lines, r"sync done: .*")
    outers = logs.outer_steps("\n".join(lines))
    assert len(rounds) == len(syncs) == len(outers) == ROUNDS
    for r in rounds:
        assert ROUND_FIELDS <= set(r)
        assert all(isinstance(r[k], (int, float)) for k in ROUND_FIELDS)
        assert r["steps"] * r["median_step_s"] > 0
        assert r["max_step_s"] >= r["median_step_s"]
        assert r["steps_sum_s"] + r["status_s"] + r["input_wait_s"] <= r["wall_s"] + 1e-2
    for n, s in enumerate(syncs):
        assert SYNC_FIELDS <= set(s) and s["round"] == n
        assert s["bytes_up"] > 0 and s["bytes_down"] > 0
        assert all(s[k] >= 0 for k in ("encode_s", "upload_s", "wait_s", "merge_s"))
    for n, o in enumerate(outers):
        assert OUTER_FIELDS <= set(o) and o["round"] == n
        parts = sum(o[k] for k in ("mean_s", "load_s", "nesterov_s",
                                   "save_update_s", "save_momentum_s"))
        assert parts <= o["wall_s"] + 5e-3 and o["bytes"] > 0


def test_a_lease_renewal_is_a_span_under_the_round_that_is_open(traced):
    spans, _ = traced
    by_id = {s["span_id"]: s for s in spans}
    renewals = _named(spans, "lease_renew")
    assert {s["attrs"]["peer"] for s in renewals} == {"w0", "ps"}
    for s in renewals:
        assert s["node"] == "scheduler" and s["ok"]
        assert s["attrs"]["late_s"] >= 0 and s["attrs"]["rtt_s"] >= 0
        root = by_id[s["parent_id"]]
        assert root["name"] == "round" and root["node"] == "scheduler"
        assert root["attrs"]["round"] == s["attrs"]["round"]
        assert root["mono_start_ns"] <= s["mono_start_ns"] <= root["mono_end_ns"]


def test_the_lease_lines_parse(traced, untraced):
    for line in _fields(untraced, r"lease accepted: .*"):
        assert line["peer"] == "sched" and line["ttl_s"] == LEASE_S
    lines = traced[1]
    accepted = _fields(lines, r"lease accepted: .*")
    renewed = _fields(lines, r"lease renewed: .*")
    renewal = _fields(lines, r"lease renewal: .*")
    # A renewal in flight when the job ends is on the worker's line alone.
    assert len(accepted) == 2 and renewed and 0 <= len(renewed) - len(renewal) <= 2
    for line in accepted + renewed:
        assert line["peer"] == "sched" and line["ttl_s"] == LEASE_S
        assert isinstance(line["margin_s"], float) and isinstance(line["lease"], str)
    # Renewed at 2/3, a lease has a third left unless a loop stood still.
    assert all(0 < line["margin_s"] <= LEASE_S / 3 + 0.01 for line in renewed)
    assert {line["peer"] for line in renewal} == {"w0", "ps"}
    assert all(0 <= line["late_s"] < LEASE_S / 3 and line["rtt_s"] >= 0 for line in renewal)
