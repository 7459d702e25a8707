"""The twelve per-layer metrics of PR 42, which read the spans at the two ends
of a push, what a phase cost the process and the loop's stalls: on a traced
run of ``mistral-7b-d1.steps`` recorded on the chip from this tree
(``data/recorded_journeys/``), on synthetic spans for the reader
``span_attr``'s cases, and in one tiny traced rehearsal on the CPU whose
manifest copy lists the tiny cell for the twelve. Each entry lists
``mistral-7b-d1.steps`` and ``trinity-mini-d5.steps`` and no other cell:
``test_split_metrics.py``, ``test_lfm2_moe_counts.py`` and
``test_afmoe_counts.py`` hold counts that refuse any other choice (PERF.md 7)."""

from __future__ import annotations

import dataclasses
import json
import re
import statistics
import types

import pytest

import manifest_checks
from perfbench import cluster, logs, manifest, measure, readers
from perfbench.readers import span_attr

from perfbench_helpers import DATA as FIXTURES, REPO, make_root, rehearsal_result, run_bench

DATA = FIXTURES / "recorded_journeys"
CELL_NAME = "mistral-7b-d1.steps"
CELLS = ["mistral-7b-d1.steps", "trinity-mini-d5.steps"]
TWELVE = [
    "bcast_receive_s", "bcast_receive_read_s", "bcast_receive_write_s", "bcast_receive_cpu_s",
    "bcast_outside_receive_s", "sync_notify_s", "upload_send_s", "ps_upload_read_s",
    "ps_upload_write_s", "sync_write_sys_s", "sync_encode_kfaults", "loop_stall_max_s",
]
COUNTERS = {"bcast_receive_cpu_s", "sync_write_sys_s", "sync_encode_kfaults", "loop_stall_max_s"}
PS_SIDE = {"bcast_outside_receive_s", "ps_upload_read_s", "ps_upload_write_s"}


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL_NAME, REPO)


@pytest.fixture(scope="module")
def spans():
    return [json.loads(x) for x in (DATA / "spans.jsonl").read_text().splitlines()]


@pytest.fixture(scope="module")
def run(cell, spans):
    texts = {n: (DATA / f"{n}.log").read_text() for n in ("w0", "ps", "scheduler")}
    start = logs.line_time(texts["scheduler"].splitlines()[1]) - 20.0
    run = cluster.Run(t_start=0.0, t_wall=start, out_dir=DATA, trace=True)
    run.texts, run.holders = texts, ["w0"]
    run.events["scheduler_start"] = start + 18.0
    run.spans = spans
    run.reference = json.loads((DATA / "reference.json").read_text())
    measure.from_logs(run, texts, cell.traffic, 51.0)
    assert [r["round"] for r in run.measured] == [1, 2]
    return run


@pytest.fixture(scope="module")
def values(cell, run):
    return readers.read_all(cell, run)


def _of(spans, node, name, rounds=(1, 2)):
    return [s for s in spans if s["node"] == node and s["name"] == name
            and s["attrs"].get("round") in rounds]


def _seconds(s):
    return (s["mono_end_ns"] - s["mono_start_ns"]) / 1e9


# ------------------------------------------------------------ the manifest


def test_the_twelve_are_the_last_entries_and_list_one_dense_and_one_sparse_cell():
    per_layer = manifest.load_manifest(REPO)["per_layer"]
    assert [e["name"] for e in per_layer[-12:]] == TWELVE and len(per_layer) == 62
    for e in per_layer[-12:]:
        assert e["workloads"] == CELLS and e["moves"] == "sync_exposed_s" and e["better"] == "lower"


@pytest.mark.parametrize("cell_name, count", [
    ("mistral-7b-d1.sync-h8", 33), ("mistral-7b-d1.steps", 45),
    ("trinity-mini-d5.steps", 52), ("lfm2-24b-a2b-d5.steps", 43),
])
def test_a_traced_line_of_each_cell_carries_this_many_per_layer_metrics(cell_name, count):
    names = [e["name"] for e, _ in manifest.resolve(cell_name, REPO).per_layer]
    assert len(names) == count
    assert set(TWELVE) <= set(names) if cell_name in CELLS else not set(TWELVE) & set(names)


@pytest.mark.parametrize("name", TWELVE)
def test_spec_resolves_and_agrees_with_its_entry(cell, name):
    m = manifest.load_manifest(REPO)
    entry, spec = next((e, s) for e, s in cell.per_layer if e["name"] == name)
    manifest_checks.check_per_layer_entry(m, entry, REPO)
    assert entry["source"] == ("program_counter" if name in COUNTERS else "program_span")
    assert entry["layer"] == ("Outer sync, PS side" if name in PS_SIDE else "Outer sync, worker side")
    assert entry["unit"] == ("kfaults" if name == "sync_encode_kfaults" else "s")
    # every input of a derived metric stands before it in the manifest's order
    before = [e["name"] for e, _ in cell.per_layer]
    for source in spec.get("inputs", {}).values():
        assert before.index(source["metric"]) < before.index(name)


# ------------------------------------------------------ the recorded run


# What may read 0 on the record: no stall in a round, and a host whose kernel
# keeps no count of minor faults (the chip's: ``ru_minflt`` is 0 in every
# span there, PERF.md 5). The difference of the two ends' spans is a few
# milliseconds of either sign.
MAY_BE_ZERO = {"loop_stall_max_s", "sync_encode_kfaults"}


@pytest.mark.parametrize("name", TWELVE)
def test_each_of_the_twelve_reads_a_number_from_the_record_of_this_tree(values, name):
    assert isinstance(values[name], float)
    if name == "bcast_outside_receive_s":
        assert abs(values[name]) < 0.05
    else:
        assert values[name] >= 0 if name in MAY_BE_ZERO else values[name] > 0


def test_the_recorded_run_is_correct_and_its_line_carries_45_metrics(cell, run, values):
    run.profile = {"busy_s": 13.7, "window_s": 19.4,
                   "breakdown": {"device_ops": [["fusion", 10.0]], "idle_gaps": []}}
    result = measure.result(run, cell, trace=True, layer_values=values)
    assert result["correct"] is True, run.checks
    assert len(result["metrics"]) == 45 and set(TWELVE) <= set(result["metrics"])


def test_the_two_ends_of_the_broadcast_are_one_interval_to_milliseconds(values, spans):
    """Header arrival → payload on disk, on the worker, against fan-out
    starts → the slowest peer's push has returned, on the PS: one host, one
    monotonic clock. ISSUE 42 expected the receiver's span inside the
    sender's. It begins inside it, after the dial (``connect_s``), and ends a
    few milliseconds *after* it: the sender's ``close`` returns when its last
    bytes are in the kernel, and the receiver still has them to take out of
    the socket and write. So ``bcast_outside_receive_s`` is 0 to a hundredth
    of a second, of either sign: the sender's span holds nothing that the
    receiver's does not."""
    assert abs(values["ps_broadcast_s"] - values["bcast_receive_s"]) < 0.03
    assert values["bcast_outside_receive_s"] == pytest.approx(
        values["ps_broadcast_s"] - values["bcast_receive_s"])
    for rnd in (1, 2):
        (receive,) = _of(spans, "w0", "receive", (rnd,))
        (broadcast,) = _of(spans, "ps", "broadcast", (rnd,))
        did = broadcast["attrs"]["pushes"]["w0"]
        assert did["attempts"] == 1
        dial_ns = receive["mono_start_ns"] - broadcast["mono_start_ns"]
        assert 0 <= dial_ns <= did["connect_s"] * 1e9 + 2e6  # the header is read once it is written
        assert 0 <= receive["mono_end_ns"] - broadcast["mono_end_ns"] < 30e6
        assert receive["trace_id"] == broadcast["trace_id"]
        inside = did["connect_s"] + did["send_s"] + did["close_s"]
        assert 0.99 * _seconds(broadcast) < inside <= _seconds(broadcast)
        assert did["send_s"] > 0.99 * inside  # neither the dial nor the close
        assert receive["attrs"]["bytes"] == broadcast["attrs"]["bytes"] == 1_921_041_704
        assert (receive["attrs"]["pages"], receive["attrs"]["path"]) == ("fresh", "loop")
        # the sender waits for the receiver: its process is on a CPU for under half of the span
        a = broadcast["attrs"]
        assert a["cpu_user_s"] + a["cpu_sys_s"] < 0.5 * _seconds(broadcast)


def test_the_senders_span_is_the_other_end_of_the_ps_upload(values, spans):
    for rnd in (1, 2):
        (send,) = _of(spans, "w0", "send", (rnd,))
        (upload,) = _of(spans, "ps", "upload", (rnd,))
        assert send["attrs"]["attempt"] == 1 and send["attrs"]["peer"] == "ps"
        assert send["attrs"]["bytes"] == upload["attrs"]["bytes"]
        assert 0 <= upload["mono_start_ns"] - send["mono_start_ns"] < 30e6  # the header arrives after the dial
        assert abs(upload["mono_end_ns"] - send["mono_end_ns"]) < 30e6
        assert send["trace_id"] == upload["trace_id"]
        # the push is one ``sendfile`` in the kernel on the loop's thread: the
        # process's system seconds are the span's, and the loop says it was held
        assert send["attrs"]["cpu_sys_s"] > 0.8 * _seconds(send)
        held = [s for s in _of(spans, "w0", "loop_stall", (rnd,))
                if send["mono_start_ns"] <= s["attrs"]["due_mono_ns"] and s["mono_end_ns"] <= send["mono_end_ns"] + 5e6]
        assert held and max(s["attrs"]["lag_s"] for s in held) > 0.5 * _seconds(send)
    # the hand-over of a path across the bridge is not the push
    assert values["sync_upload_s"] < 0.1 * values["upload_send_s"]
    assert values["upload_send_s"] == pytest.approx(values["ps_upload_s"], abs=0.03)


def test_the_splits_are_the_ones_the_roles_log_to_a_millisecond(values, run):
    """``ps upload:`` on the PS and ``push received:`` on the worker carry the
    same ``read_s`` and ``write_s`` the spans do."""
    measured = {r["round"] for r in run.measured}
    for role, line, read, write in (
        ("ps", r"ps upload: .*", "ps_upload_read_s", "ps_upload_write_s"),
        ("w0", r"push received: .*", "bcast_receive_read_s", "bcast_receive_write_s"),
    ):
        rows = [logs.parse_fields(m.group(0)) for m in re.finditer(line, run.texts[role])]
        rows = [r for r in rows if r["round"] in measured]
        assert len(rows) == 2
        assert values[read] == pytest.approx(statistics.median(r["read_s"] for r in rows), abs=1e-3)
        assert values[write] == pytest.approx(statistics.median(r["write_s"] for r in rows), abs=1e-3)
    # on the loop's path the two awaits cover the receive between them
    assert values["bcast_receive_read_s"] + values["bcast_receive_write_s"] <= values["bcast_receive_s"]


def test_the_notify_gap_is_the_named_part_of_what_was_unaccounted(values, spans):
    assert values["sync_notify_s"] >= 0
    for rnd in (1, 2):
        (receive,) = _of(spans, "w0", "receive", (rnd,))
        (merge,) = _of(spans, "w0", "merge", (rnd,))
        (wait,) = _of(spans, "w0", "await_update", (rnd,))
        assert receive["mono_end_ns"] <= merge["mono_start_ns"]
        assert wait["mono_start_ns"] <= receive["mono_start_ns"] and receive["mono_end_ns"] <= wait["mono_end_ns"]
    assert values["sync_notify_s"] < values["sync_wait_s"]


def test_usage_is_on_the_spans_issue_42_names_and_on_no_other(spans):
    fields = {"cpu_user_s", "cpu_sys_s", "minflt", "maxrss_kb"}
    asked = {("w0", n) for n in ("encode", "encode.extract", "encode.write", "merge", "merge.read",
                                 "merge.apply", "receive", "send")}
    asked |= {("ps", n) for n in ("upload", "fold", "outer_step.nesterov", "broadcast")}
    seen = {(s["node"], s["name"]) for s in spans if fields <= set(s["attrs"])}
    assert seen == asked
    assert not [s for s in spans if fields & set(s["attrs"]) and (s["node"], s["name"]) not in asked]
    for s in spans:
        if (s["node"], s["name"]) in asked:
            # the process's, all threads: never more than the host's cores could give
            assert 0 <= s["attrs"]["cpu_user_s"] + s["attrs"]["cpu_sys_s"] <= 13 * _seconds(s) + 0.01


def test_the_counters_read_the_spans_they_name(values, spans):
    def med(node, name, *attrs):
        return statistics.median(sum(s["attrs"][a] for a in attrs) for s in _of(spans, node, name))

    assert values["bcast_receive_cpu_s"] == pytest.approx(med("w0", "receive", "cpu_user_s", "cpu_sys_s"))
    assert values["sync_write_sys_s"] == pytest.approx(med("w0", "encode.write", "cpu_sys_s"))
    assert values["sync_encode_kfaults"] == pytest.approx(med("w0", "encode", "minflt") / 1000)
    # a child's faults and seconds are in its parent's
    for rnd in (1, 2):
        (encode,) = _of(spans, "w0", "encode", (rnd,))
        parts = _of(spans, "w0", "encode.extract", (rnd,)) + _of(spans, "w0", "encode.write", (rnd,))
        assert sum(p["attrs"]["minflt"] for p in parts) <= encode["attrs"]["minflt"]


def test_a_stall_is_an_instant_of_a_measured_round_and_the_metric_is_the_longest(values, spans, run):
    stalls = [s for s in spans if s["name"] == "loop_stall"]
    for s in stalls:
        assert s["mono_start_ns"] == s["mono_end_ns"] and s["attrs"]["lag_s"] >= 0.05
    # a stall before the node's first span of a round has no round to carry
    # (set-up: the PS's loop stands still for seconds at its own start);
    # from then on every one has
    first = min(s["mono_start_ns"] for s in spans if s["node"] == "w0" and s["name"] == "inner_steps")
    assert all("round" in s["attrs"] for s in stalls if s["node"] == "w0" and s["mono_start_ns"] > first)
    mine = [s["attrs"]["lag_s"] for s in stalls
            if s["node"] == "w0" and s["attrs"].get("round") in (1, 2)]
    assert values["loop_stall_max_s"] == (max(mine) if mine else 0.0)


def test_the_clock_mark_is_at_each_rounds_opening_and_at_do_updates_entry(spans):
    for rnd in (1, 2):
        marks = _of(spans, "w0", "clock_mark", (rnd,))
        (inner,) = _of(spans, "w0", "inner_steps", (rnd,))
        (encode,) = _of(spans, "w0", "encode", (rnd,))
        assert len(marks) == 2
        opening, entry = sorted(marks, key=lambda s: s["mono_start_ns"])
        assert 0 <= inner["mono_start_ns"] - opening["mono_start_ns"] < 5e6
        assert inner["mono_end_ns"] <= entry["mono_start_ns"] <= encode["mono_start_ns"]
        for m in marks:
            assert m["mono_start_ns"] == m["mono_end_ns"]
            # the two clocks of one moment, read before the annotation was entered
            assert 0 <= m["start_ns"] - m["attrs"]["wall_ns"] < 5e6
            assert 0 <= m["mono_start_ns"] - m["attrs"]["mono_ns"] < 5e6


# ------------------------------------------- the reader, on synthetic spans


def _span(name, rnd, node="w0", t=0, **attrs):
    return {"node": node, "name": name, "mono_start_ns": t, "mono_end_ns": t + 10,
            "attrs": {"round": rnd, **attrs}}


def _run(spans, rounds=(1, 2)):
    return types.SimpleNamespace(spans=spans, measured=[{"round": r} for r in rounds])


SYNTHETIC = [
    _span("receive", 0, read_s=9.0, cpu_user_s=9.0, cpu_sys_s=9.0),  # round 0 is warm-up
    _span("receive", 1, read_s=1.0, write_s=0.5, cpu_user_s=0.25, cpu_sys_s=0.5),
    _span("receive", 2, read_s=3.0, write_s=0.7, cpu_user_s=0.75, cpu_sys_s=1.0),
    _span("send", 1, cpu_user_s=0.125, cpu_sys_s=0.125), _span("send", 2, cpu_sys_s=2.0),
    _span("receive", 1, node="w1", read_s=100.0),
    _span("encode", 1, minflt=469_000), _span("encode", 2, minflt=471_000),
    _span("loop_stall", 1, lag_s=0.07), _span("loop_stall", 1, lag_s=0.3), _span("loop_stall", 2, lag_s=0.1),
    _span("merge", 1, ok_flag=True),
]


@pytest.mark.parametrize("spec, expect", [
    ({"name": "receive", "attr": "read_s"}, 2.0),  # the median of the two measured rounds
    ({"name": "receive", "attr": "read_s", "reduce": "max"}, 3.0),
    ({"name": "receive", "attrs": ["cpu_user_s", "cpu_sys_s"]}, 1.25),  # summed a span: 0.75 and 1.75
    ({"names": ["receive", "send"], "attrs": ["cpu_user_s", "cpu_sys_s"]}, 2.375),  # summed a round: 1.0 and 3.75
    ({"names": ["receive", "send"], "attr": "cpu_sys_s", "per_round": "max", "reduce": "max"}, 2.0),
    ({"name": "encode", "attr": "minflt", "scale": 0.001}, 470.0),
    ({"name": "loop_stall", "attr": "lag_s", "per_round": "max", "reduce": "max", "absent": 0.0}, 0.3),
    ({"name": "loop_stall", "attr": "lag_s", "per_round": "sum", "reduce": "median"}, 0.235),
    ({"name": "cleanup", "attr": "lag_s", "absent": 0.0}, 0.0),  # the node wrote spans, none of the name
    ({"name": "cleanup", "attr": "lag_s"}, None),
    ({"name": "receive", "attr": "pages_s"}, None),  # the span is there, the number is not
    ({"name": "receive", "attr": "pages_s", "absent": 0.0}, 0.0),
    ({"name": "merge", "attr": "ok_flag"}, None),  # a flag is no number
])
def test_span_attr_on_synthetic_spans(spec, expect):
    got = span_attr.read({"reader": "span_attr", "node": "w0", **spec}, _run(SYNTHETIC), None, {})
    assert got == (pytest.approx(expect) if expect is not None else None)


def test_span_attr_without_a_node_reads_every_nodes_and_without_spans_reads_nothing():
    spec = {"reader": "span_attr", "name": "receive", "attr": "read_s", "per_round": "sum", "reduce": "max"}
    assert span_attr.read(spec, _run(SYNTHETIC), None, {}) == 101.0
    absent = dict(spec, node="w0", absent=0.0)
    assert span_attr.read(absent, _run([]), None, {}) is None  # a run without spans
    assert span_attr.read(absent, types.SimpleNamespace(measured=[{"round": 1}]), None, {}) is None
    assert span_attr.read(absent, _run(SYNTHETIC, rounds=()), None, {}) is None  # no measured round
    only_ps = [dict(s, node="ps") for s in SYNTHETIC]
    assert span_attr.read(absent, _run(only_ps), None, {}) is None  # the node wrote nothing
    assert readers.read_spec(spec, _run(SYNTHETIC), None, {}) == 101.0  # found by its kind's name


def test_a_program_from_before_the_spans_reads_none_of_the_eleven_and_raises_nothing(cell, run):
    """The parent commit's traced run under this PR's benchmark files: no
    ``receive``, no ``send``, no ``usage`` fields, no watch task. What has
    ``absent`` reads its 0.0 (the node wrote spans); every other reads
    nothing, and the line leaves it out."""
    fields = {"cpu_user_s", "cpu_sys_s", "minflt", "maxrss_kb", "read_s", "write_s", "pushes"}
    old = [dict(s, attrs={k: v for k, v in s["attrs"].items() if k not in fields})
           for s in run.spans if s["name"] not in ("receive", "send", "loop_stall", "clock_mark")]
    values = readers.read_all(cell, dataclasses.replace(run, spans=old))
    assert values["loop_stall_max_s"] == 0.0
    assert [n for n in TWELVE if values[n] is not None] == ["loop_stall_max_s"]
    assert isinstance(values["ps_broadcast_s"], float) and isinstance(values["sync_unaccounted_s"], float)


# ------------------------------------------------- off the chip, end to end


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("journeys"))
    m = json.loads((root / "BENCHMARK.json").read_text())
    for metric in m["per_layer"]:  # the real cells' twelve, read by the tiny one too
        if metric.get("workloads") == CELLS:
            metric["workloads"] = ["tiny-gpt2.h4"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    r = run_bench(root, "--workload", "tiny-gpt2.h4", "--seed", "2147485133",
                  "--seconds", "15", "--trace", "1")
    out = root / "chiprun_out" / "perfbench" / "tiny-gpt2.h4" / "traced"
    return r, logs.read_spans(out / "spans"), (out / "w0.log").read_text()


def test_the_rehearsal_reads_all_twelve_off_the_chip(rehearsed):
    r, _, _ = rehearsed
    assert r.returncode == 3, r.stderr[-3000:]
    metrics = rehearsal_result(r.stderr)["metrics"]
    assert set(TWELVE) <= set(metrics)
    for name in TWELVE:
        assert isinstance(metrics[name]["value"], float)
        # a difference of two spans of two processes: at 216 KB a push the
        # receiver is still writing when the sender's ``close`` has returned
        assert metrics[name]["value"] >= 0 or name == "bcast_outside_receive_s"
    assert metrics["sync_encode_kfaults"]["unit"] == "kfaults"
    assert metrics["bcast_receive_read_s"]["value"] + metrics["bcast_receive_write_s"]["value"] <= \
        metrics["bcast_receive_s"]["value"]


def test_the_rehearsals_roles_wrote_the_spans_from_both_ends(rehearsed):
    _, spans, w0 = rehearsed
    rounds = {s["attrs"]["round"] for s in spans if s["name"] == "merge"}
    assert len(rounds) >= 2
    for name, node in (("receive", "w0"), ("send", "w0"), ("upload", "ps"), ("broadcast", "ps")):
        found = {s["attrs"]["round"] for s in spans if s["name"] == name and s["node"] == node}
        assert rounds <= found, name
    assert all("pushes" in s["attrs"] for s in spans if s["name"] == "broadcast")
    assert len(re.findall(r"push received: round=\d+ peer=ps bytes=\d+ pages=fresh path=loop", w0)) >= len(rounds)
    marks = [s for s in spans if s["name"] == "clock_mark"]
    assert len(marks) >= 2 * len(rounds) and all(s["node"] == "w0" for s in marks)
