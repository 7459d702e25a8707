"""The line and span readers and the metric arithmetic, on a worker log, a PS
log and a span file recorded on the chip (``data/recorded/``), whole and
with a fault cut into them. Every faulty record still ends in the contract's
last line with ``correct: false``; none ends in a traceback."""

from __future__ import annotations

import json
import math
import re
import dataclasses
import statistics

import pytest

from perfbench import cluster, flops, logs, manifest, measure, readers
from perfbench.readers import derived
from perfbench.xplane import attribute, gaps, merge, op_name

from perfbench_helpers import DATA as FIXTURES, REPO, RESULT_KEYS, recorded_cell

DATA = FIXTURES / "recorded"
CELL = recorded_cell()
ROUND_TOKENS = 8 * 8 * 1024
DEVICE_LINE = "2026-09-27 01:14:15,600 hypha.executor.training INFO device: "


def record(w0: str | None = None, ps: str | None = None, *, seconds: float = 51.0,
           cause: str | None = None, holders=("w0",), cell=CELL) -> cluster.Run:
    """A run as the harness would hold it after the roles have stopped."""
    texts = {
        "w0": (DATA / "w0.log").read_text() if w0 is None else w0,
        "ps": (DATA / "ps.log").read_text() if ps is None else ps,
        "scheduler": (DATA / "scheduler.log").read_text(),
    }
    start = logs.line_time(texts["scheduler"].splitlines()[1]) - 20.0
    run = cluster.Run(t_start=0.0, t_wall=start, out_dir=DATA, trace=True)
    run.texts, run.holders, run.cause = texts, list(holders), cause
    run.events["scheduler_start"] = start + 18.0
    run.spans = [json.loads(x) for x in (DATA / "spans.jsonl").read_text().splitlines()]
    measure.from_logs(run, texts, cell.traffic, seconds)
    return run


def cut(text: str, pattern: str, repl: str = "") -> str:
    out, n = re.subn(pattern, repl, text)
    assert n >= 1, pattern
    return out


W0 = (DATA / "w0.log").read_text()
PS = (DATA / "ps.log").read_text()


def test_the_recorded_run_is_correct_and_its_end_to_end_metrics_are_the_arithmetic():
    run = record()
    result = measure.result(run, CELL, trace=False)
    assert set(result) == RESULT_KEYS and result["correct"] is True, run.checks
    assert (result["attempted"], result["failed"]) == (3, 0)
    walls = [r["wall"] for r in run.measured]
    # Three rounds of 14.2, 16.4 and 14.6 s by the log's own timestamps.
    assert [round(w, 1) for w in walls] == [14.2, 16.4, 14.6]
    m = result["metrics"]
    assert m["tokens_per_s"]["value"] == pytest.approx(3 * ROUND_TOKENS / sum(walls))
    assert m["tokens_per_s"]["unit"] == "tokens/s"
    # All the window's time outside the steps, per round: the slow round 2
    # shows in it, as it would not in a median over rounds.
    exposed = [w - 8 * r["median_step_s"] for w, r in zip(walls, run.measured)]
    assert m["sync_exposed_s"]["value"] == pytest.approx(sum(exposed) / 3)
    assert m["sync_exposed_s"]["value"] > statistics.median(exposed) + 0.4
    assert 13.0 < m["sync_exposed_s"]["value"] < 14.0
    assert m["setup_s"]["value"] == pytest.approx(run.arrivals[0])
    assert result["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "memory_peak_bytes": 9973568000,
    }


def test_a_shorter_window_measures_fewer_rounds_and_never_part_of_one():
    run = record(seconds=31.0)  # round 2 closes 30.6 s after round 0, round 3 at 45.2 s
    assert [r["round"] for r in run.measured] == [1, 2]
    assert measure.result(run, CELL, trace=False)["attempted"] == 2


def test_a_window_that_ends_inside_round_1_runs_on_to_its_close():
    # Round 1 closes 14.2 s after round 0: a slower host gives a longer run
    # and a lower rate, never a run with nothing in it.
    run = record(seconds=10.0)
    assert [r["round"] for r in run.measured] == [1]
    result = measure.result(run, CELL, trace=False)
    assert result["correct"] is True and result["attempted"] == 1
    assert result["metrics"]["tokens_per_s"]["value"] == pytest.approx(
        ROUND_TOKENS / run.measured[0]["wall"])


PER_LAYER = {
    "backend_up_s": 7.993, "worker_setup_s": 24.493, "first_step_s": 30.984,
    "step_ms": 193.0, "ps_outer_step_s": 6.221, "hbm_peak_gb": 9.973568,
    "sync_upload_s": 0.00337321, "sync_encode_s": 0.703110221,
    "sync_merge_s": 0.595013976, "ps_fold_s": 0.745476588,
    "ps_broadcast_s": 1.584047822,
}


@pytest.mark.parametrize("name,expect", sorted(PER_LAYER.items()))
def test_per_layer_reader_gives_what_the_chip_run_reported(name, expect):
    values = readers.read_all(CELL, record())
    assert values[name] == pytest.approx(expect, rel=1e-6)


def test_derived_metrics_follow_their_expressions():
    run = record()
    v = readers.read_all(CELL, run)
    per_token = flops.flops_per_token(CELL.config["flops"], 1024)
    assert v["mfu_step"] == pytest.approx(100 * per_token * 8192 / 0.193 / 197e12)
    assert 50 < v["mfu_step"] < 55 and repr(v["mfu_step"]) == "52.19964170066016"  # as at ccfad04
    span = sum(r["wall"] for r in run.measured)
    in_step = sum(8 * r["median_step_s"] for r in run.measured)
    assert v["in_step_share"] == pytest.approx(100 * in_step / span)
    inner = statistics.median(
        (s["mono_end_ns"] - s["mono_start_ns"]) / 1e9 for s in run.spans
        if s["name"] == "inner_steps" and s["attrs"]["round"] in (1, 2, 3)
    )
    assert v["inner_gap_ms"] == pytest.approx((inner * 1000 - 8 * 193.0) / 8)
    assert v["dispatch_s"] == pytest.approx(
        logs.line_time(logs.find_line(W0, "device: platform=")) - run.events["scheduler_start"]
    )


def test_a_traced_result_carries_the_per_layer_metrics_and_the_profile():
    run = record()
    run.profile = {"busy_s": 1.5, "window_s": 14.2,
                   "breakdown": {"device_ops": [["fusion", 0.49]], "idle_gaps": []}}
    result = measure.result(run, CELL, trace=True, layer_values=readers.read_all(CELL, run))
    assert set(result) == RESULT_KEYS | {"breakdown"}
    # This record is of PR 23's program, before the phase spans, the step
    # record and the lease lines: it gives the metrics of that PR and leaves
    # the later ones out. test_split_metrics.py holds every listed metric to
    # the record of this tree.
    listed = {e["name"] for e, _ in CELL.per_layer}
    assert set(PER_LAYER) | {"mfu_step", "in_step_share", "inner_gap_ms", "dispatch_s"} <= set(
        result["metrics"]) <= listed
    assert result["device"]["busy_s"] == 1.5 and result["device"]["window_s"] == 14.2


def test_an_unknown_device_kind_leaves_mfu_out_and_says_so(capsys):
    w0 = cut(W0, r"kind='TPU v5 lite'", "kind='TPU v9 mega'")
    values = readers.read_all(CELL, record(w0))
    assert values["mfu_step"] is None and values["step_ms"] == 193.0
    assert "no peak FLOP/s known for device_kind 'TPU v9 mega'" in capsys.readouterr().err
    with pytest.raises(KeyError):
        flops.peak_flops("cpu")


def test_a_span_the_program_did_not_write_leaves_its_metric_out():
    run = record()
    run.spans = [s for s in run.spans if s["name"] != "fold"]
    values = readers.read_all(CELL, run)
    assert values["ps_fold_s"] is None and values["ps_broadcast_s"] is not None
    result = measure.result(run, CELL, trace=True, layer_values=values)
    assert "ps_fold_s" not in result["metrics"]


FAULTS = {
    # A recompile in the window costs what round 0's first step did.
    "recompile_in_window": dict(
        w0=cut(W0, r"(round 2 done: .*first_step_s=)0\.233", r"\g<1>28.400"),
        failing={"no_recompile_in_window"}, attempted=3, failed=1),
    # The worker never logged round 2: nothing after the gap is contiguous.
    "missing_round": dict(
        w0=cut(W0, r".*round 2 done: .*\n"),
        failing={"rounds_in_order"}, attempted=1, failed=0),
    # The worker died in round 2; the harness names the cause.
    "dead_worker": dict(
        w0=cut(W0, r"(?s)(.*round 1 done: [^\n]*\n).*", r"\1"),
        cause="role w0 died (return code -9)",
        failing={"no_role_died"}, attempted=2, failed=1),
    "dead_before_round_0": dict(
        w0=cut(W0, r"(?s)(.*setup done: [^\n]*\n).*", r"\1"), ps="",
        cause="deadline: round 0 did not close in time",
        failing=None, attempted=1, failed=1),
    "delta_pushed_twice": dict(
        ps=cut(PS, r"(.*round 2 delta 1/1 \(from w0\)\n)", r"\1\1"),
        failing={"one_delta_per_round"}, attempted=3, failed=1),
    "outer_update_missing": dict(
        ps=cut(PS, r".*ps outer step: round=3 .*\n"),
        failing={"one_outer_update_per_round", "native_ps_and_codec"}, attempted=3, failed=1),
    "numpy_fallback_in_the_ps": dict(
        ps=cut(PS, r"(round=1 .*)native_kernels=True", r"\1native_kernels=False"),
        failing={"native_ps_and_codec"}, attempted=3, failed=0),
    "nonfinite_loss": dict(
        w0=cut(W0, r"(round 3 done: .*)nonfinite=0", r"\1nonfinite=2"),
        failing={"losses_finite"}, attempted=3, failed=1),
    "fewer_steps_than_the_cell_says": dict(
        w0=cut(W0, r"(round 1 done: batch=8 )steps=8 tokens=65536", r"\1steps=7 tokens=57344"),
        failing={"work_as_the_cell_says"}, attempted=3, failed=1),
    "dense_attention": dict(
        w0=cut(W0, r"attention path: pallas flash kernel, compiled", "attention path: XLA dense"),
        failing={"attention_is_compiled_flash"}, attempted=3, failed=0),
    "off_the_tpu": dict(
        w0=cut(W0, r"device: platform=tpu", "device: platform=cpu"), holders=(),
        failing={"device_is_tpu"}, attempted=3, failed=0),
    "a_second_process_on_the_chip": dict(
        holders=("ps", "w0"),
        failing={"only_w0_holds_the_chip", "device_is_tpu"}, attempted=3, failed=0),
    "zeroed_head_or_other_weights": dict(
        w0=cut(W0, r"(round 0 done: .*loss_first=)10\.9979", r"\g<1>10.8249"),
        failing={"first_loss_near_ln_vocabulary"}, attempted=3, failed=0),
    "loss_did_not_fall": dict(  # round 1's mean: the limit reads that round alone
        w0=cut(W0, r"(round 1 done: .*loss_mean=)8\.0927", r"\g<1>9.9000"),
        failing={"loss_fell"}, attempted=3, failed=0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_record_ends_in_the_contracts_line_with_correct_false(fault, capsys):
    f = FAULTS[fault]
    run = record(f.get("w0"), f.get("ps"), cause=f.get("cause"),
                 holders=f.get("holders", ("w0",)))
    if run.cause:
        cluster.report_failure(run)
    for trace in (False, True):
        values = readers.read_all(CELL, run) if trace else None
        result = measure.result(run, CELL, trace, values)
        json.dumps(result)  # one JSON object, whatever happened
        assert set(result) == RESULT_KEYS and result["correct"] is False
        assert (result["attempted"], result["failed"]) == (f["attempted"], f["failed"])
    failing = {k for k, ok in run.checks.items() if not ok}
    if f["failing"] is not None:
        assert failing == f["failing"]
    else:  # nothing was measured: every check that needs a round fails
        assert "rounds_measured" in failing and result["metrics"].keys() <= {"backend_up_s", "worker_setup_s", "dispatch_s"}
    if f.get("cause"):
        err = capsys.readouterr().err
        assert f"perfbench: run failed: {f['cause']}" in err
        assert "--- w0 (return code" in err and "--- ps (return code" in err


@pytest.mark.parametrize("bands,failing", [
    # Round 0 of the record: 8 x 9.6043 - 10.9979 = 65.84; round 1 starts at
    # 7.59, 0.69 of round 0's 10.9979.
    ({"descent_after_first_step": {"low": 60.0, "high": 70.0},
      "loss_first_after_outer_step_share": 0.75}, set()),
    ({"descent_after_first_step": {"low": 66.0, "high": 70.0}}, {"descent_as_recorded"}),
    ({"descent_after_first_step": {"low": 60.0, "high": 65.0}}, {"descent_as_recorded"}),
    ({"loss_first_after_outer_step_share": 0.45}, {"loss_stays_down_after_outer_step"}),
    ({}, set()),  # a mix without a band is not held to one
], ids=["inside", "learns_slower", "learns_faster", "thrown_off_by_the_update", "no_band"])
def test_the_mixs_bands_hold_the_losses_to_what_was_recorded(bands, failing):
    cell = dataclasses.replace(CELL, traffic={**CELL.traffic, "checks": bands})
    run = record(cell=cell)
    result = measure.result(run, cell, trace=False)
    assert {k for k, ok in run.checks.items() if not ok} == failing
    assert result["correct"] is (not failing)
    named = {"descent_as_recorded", "loss_stays_down_after_outer_step"} & set(run.checks)
    assert len(named) == len([k for k in bands if k != "why"])
    for name in named:  # the number is on the note beside its limits
        assert measure.inside(run.margins[name]) is run.checks[name]


def first_losses(*after: float, round0: float = 11.2) -> str:
    """The recorded worker log with round 0 starting at ``round0`` and the
    measured rounds at ``after``; a round not given is cut, with all after it."""
    w0 = cut(W0, r"(round 0 done: .*loss_first=)10\.9979", rf"\g<1>{round0:.4f}")
    for n, was in ((1, "7.5907"), (2, "4.7926"), (3, "2.4101")):
        if n <= len(after):
            w0 = cut(w0, rf"(round {n} done: .*loss_first=){was}", rf"\g<1>{after[n - 1]:.4f}")
        else:
            w0 = cut(w0, rf".*round {n} done: .*\n")
    return w0


@pytest.mark.parametrize("after,share,holds", [
    # The largest of about 95 sound seeds, and the two outliers that the old
    # ceiling of 0.1 refused or nearly did (PERF.md 6): all far below a
    # tenth of round 0's 11.2.
    ((0.0101,), 0.1, True),
    ((0.0813,), 0.1, True),
    ((0.2864,), 0.1, True),
    ((0.0032, 0.2864, 0.0101), 0.1, True),
    ((1.1199,), 0.1, True),  # the edge: 0.1 x 11.2
    ((1.1300,), 0.1, False),
    # An outer update that was not applied, or applied with the wrong sign,
    # puts round 1 back at round 0's first loss or above.
    ((11.2,), 0.1, False),
    # Round 1 alone is held (PR 37): what the outer step's momentum does to a
    # later round in some seeds (14.46 in round 2 of seed 2147602303) is on
    # the note, and is no longer a run's fault.
    ((0.0032, 11.07), 0.1, True),
    ((0.0637, 14.4626), 0.1, True),
    ((11.07, 0.0032), 0.1, False),
    ((11.2 * 0.2,), 0.1, False),
    ((11.2 * 0.2,), 0.3, True),
    ((), 0.1, False),  # no measured round: nothing shows that it held
], ids=["largest_sound", "seed_2147488203", "seed_2147485132", "three_rounds", "edge",
        "past_the_edge", "back_at_round_0", "second_round_back", "seed_2147602303",
        "first_round_back", "a_fifth", "a_fifth_of_0.3", "no_measured_round"])
def test_the_ceiling_after_the_outer_step_is_a_share_of_the_runs_own_first_loss(after, share, holds):
    cell = dataclasses.replace(CELL, traffic={
        **CELL.traffic, "checks": {"loss_first_after_outer_step_share": share}})
    run = record(first_losses(*after), cell=cell)
    measure.result(run, cell, trace=False)
    assert run.checks["loss_stays_down_after_outer_step"] is holds
    if after:
        held = run.margins["loss_stays_down_after_outer_step"]
        assert held["value"] == pytest.approx(after[0], abs=1e-4) and held["high"] == pytest.approx(share * 11.2)
        assert (held["share"], held["of"], held["round"]) == (share, 11.2, 1)
        later = measure.later_rounds(run)  # nothing hidden: every round, and the largest
        assert later["first_loss"] == {n: pytest.approx(v, abs=1e-4) for n, v in enumerate(after, 1)}
        assert later["largest"] == pytest.approx(max(after), abs=1e-4)
        assert after[later["largest_in_round"] - 1] == max(after)
    else:
        assert "loss_stays_down_after_outer_step" not in run.margins
        assert measure.later_rounds(run) == {}


CLOSES = {2: 0.0026, 3: 0.0007, 4: 0.0003}  # the largest on record first (81 later rounds, PR 37)


def four_rounds(first: dict, mean: dict | None = None, last: dict | None = None) -> str:
    """The recorded worker log with a fourth measured round (a copy of round
    3's line, 14.6 s later, so a window of 70 s holds it) and the given first,
    mean and closing losses by round: the window of ``sync-h8`` since PR 33.
    The record is gpt2-medium's, which learns slowly: the later rounds close
    as the cells of the manifest do unless ``last`` says otherwise."""
    line = logs.find_line(W0, r"round 3 done: ")
    stamp = re.match(r"\S+ \S+", line).group(0)
    later = re.sub(r"(\d\d):(\d\d),", lambda m: f"{int(m[1]):02d}:{int(m[2]) + 14:02d},", stamp)
    assert logs.line_time(later + " x") == pytest.approx(logs.line_time(stamp + " x") + 14.0)
    w0 = W0 + line.replace(stamp, later).replace("round 3 done", "round 4 done") + "\n"
    for n, v in first.items():
        w0 = cut(w0, rf"(round {n} done: .*loss_first=)[0-9.]+", rf"\g<1>{v:.4f}")
    for n, v in (mean or {}).items():
        w0 = cut(w0, rf"(round {n} done: .*loss_mean=)[0-9.]+", rf"\g<1>{v:.4f}")
    for n, v in {**CLOSES, **(last or {})}.items():
        w0 = cut(w0, rf"(round {n} done: .*loss_last=)[0-9.]+", rf"\g<1>{v:.4f}")
    return w0


def four_round_run(first: dict, mean: dict | None = None, last: dict | None = None):
    ps = PS + "".join(
        re.sub(r"round=3\b", "round=4", x).replace("round 3 delta", "round 4 delta") + "\n"
        for x in PS.splitlines() if re.search(r"round=3\b|round 3 delta", x))
    cell = dataclasses.replace(CELL, traffic={
        **CELL.traffic, "checks": {"loss_first_after_outer_step_share": 0.1}})
    run = record(four_rounds(first, mean, last), ps, seconds=70.0, cell=cell)
    assert [r["round"] for r in run.measured] == [1, 2, 3, 4]
    return run, cell, measure.result(run, cell, trace=False)


SOUND = {0: 11.0844, 1: 0.0637, 2: 0.0102, 3: 0.0053, 4: 0.0059}


@pytest.mark.parametrize("thrown_off_in,correct", [(3, True), (2, True), (4, True), (1, False)])
def test_a_later_round_thrown_off_by_the_outer_steps_momentum_is_on_the_note_and_only_round_1_is_held(
        thrown_off_in, correct):
    run, cell, result = four_round_run({**SOUND, thrown_off_in: 14.4626})
    assert result["correct"] is correct and result["attempted"] == 4, run.checks
    assert run.checks["loss_stays_down_after_outer_step"] is correct
    held = run.margins["loss_stays_down_after_outer_step"]
    assert held["round"] == 1 and held["high"] == pytest.approx(1.10844)
    assert held["value"] == (14.4626 if thrown_off_in == 1 else 0.0637)
    later = measure.later_rounds(run)
    assert (later["largest"], later["largest_in_round"]) == (14.4626, thrown_off_in)
    assert later["first_loss"][thrown_off_in] == 14.4626 and sorted(later["first_loss"]) == [1, 2, 3, 4]
    # the result's last key: each number compared beside its limits, nothing else
    assert list(result)[-1] == "compared" and result["compared"]["loss_stays_down_after_outer_step"] == {
        "value": held["value"], "high": held["high"]}
    # the per-round checks still hold every measured round
    assert all(run.checks[k] for k in ("work_as_the_cell_says", "one_delta_per_round",
                                       "one_outer_update_per_round", "losses_finite"))


@pytest.mark.parametrize("last,correct,worst", [
    ({}, True, (0.0026, 2)),  # as on record: 0.0026 at most over 81 later rounds
    ({3: 0.0548}, True, (0.0548, 3)),  # as a round from scratch closes (round 0 of Trinity)
    ({3: 1.1084}, True, (1.1084, 3)),  # the ceiling itself: 0.1 x round 0's first loss
    ({4: 1.1085}, False, (1.1085, 4)),
    ({2: 8.1008}, False, (8.1008, 2)),  # thrown to 8.10 and stayed there
    ({1: 9.9}, True, (0.0026, 2)),  # round 1 is held by its first loss and its mean, not here
], ids=["recorded", "from_scratch", "at_the_ceiling", "over_it", "stays_off", "round_1"])
def test_a_later_round_has_to_close_down_again(last, correct, worst):
    """From round 2 on the first loss is on the note and not held; what is
    held is that the round comes back: its closing loss under the share of
    round 0's first loss that holds round 1's first."""
    run, cell, result = four_round_run({**SOUND, 2: 8.1008}, last=last)
    assert result["correct"] is correct and run.checks["later_rounds_close_down"] is correct
    held = run.margins["later_rounds_close_down"]
    assert (held["value"], held["round"]) == worst and held["high"] == pytest.approx(1.10844)
    assert result["compared"]["later_rounds_close_down"] == {"value": worst[0], "high": held["high"]}
    assert run.checks["loss_stays_down_after_outer_step"] and run.checks["loss_fell"]


def test_a_window_of_one_round_has_no_later_round_to_hold():
    cell = dataclasses.replace(CELL, traffic={
        **CELL.traffic, "checks": {"loss_first_after_outer_step_share": 0.1}})
    run = record(four_rounds(SOUND), seconds=1.0, cell=cell)
    measure.result(run, cell, trace=False)
    assert [r["round"] for r in run.measured] == [1]
    assert "later_rounds_close_down" not in run.margins and "later_rounds_close_down" not in run.checks
    assert run.checks["loss_stays_down_after_outer_step"]


@pytest.mark.parametrize("mean,fell", [
    ({1: 0.30, 4: 9.9}, True),  # the last round's mean is not the one compared
    ({1: 9.7, 4: 0.01}, False),  # round 0's mean is 9.6043
    ({1: 9.6043}, False),  # strictly below
], ids=["last_round_up", "round_1_up", "equal"])
def test_loss_fell_reads_round_1s_mean(mean, fell):
    run, cell, result = four_round_run(SOUND, mean)
    assert run.checks["loss_fell"] is fell and result["correct"] is fell
    held = run.margins["loss_fell"]
    assert (held["value"], held["high"], held["round"]) == (mean[1], 9.6043, 1)


def test_a_later_round_still_fails_what_is_held_of_every_round():
    w0 = cut(four_rounds(SOUND), r"(round 4 done: .*)nonfinite=0", r"\1nonfinite=3")
    cell = dataclasses.replace(CELL, traffic={
        **CELL.traffic, "checks": {"loss_first_after_outer_step_share": 0.1}})
    run = record(w0, seconds=70.0, cell=cell)
    result = measure.result(run, cell, trace=False)
    assert result["correct"] is False and not run.checks["losses_finite"]


@pytest.mark.parametrize("mix", ["mistral-7b-d1.sync-h8", "mistral-7b-d1.steps"])
def test_each_cell_of_the_manifest_has_its_bands_and_they_hold_its_recorded_runs(mix):
    bands = manifest.resolve(mix, REPO).traffic["checks"]
    low, high = bands["descent_after_first_step"]["low"], bands["descent_after_first_step"]["high"]
    assert 0 < low < high < 1.75 * low  # a third of the way up or down already fails
    # Four times the largest sound reading on record (0.2864 of 11.2), a
    # tenth of what the faults it names read; no absolute ceiling beside it.
    assert 0.05 <= bands["loss_first_after_outer_step_share"] <= 0.2
    assert "loss_first_after_outer_step_max" not in bands


def test_a_traffic_key_that_nothing_reads_is_refused(tmp_path):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps({**CELL.traffic, "workers": 4, "sync_mode": "overlap"}))
    with pytest.raises(manifest.ManifestError, match=r"nothing reads: \['sync_mode', 'workers'\]"):
        manifest.load_traffic(path)


def test_deltas_are_counted_in_every_sync_modes_wording():
    ps = ("ps j: round 0 delta 1/1 (from w0)\nps j: round 1 fragment 0 delta 1 (from w0)\n"
          "ps j: round 1 fragment 1 delta 1 (from w0)\nps j: round 2 delta 2/2 (from w1)\n")
    assert logs.deltas_pushed(ps) == {0: 1, 1: 2, 2: 1}


@pytest.mark.parametrize("role,line,named", [
    ("w0", "hypha.worker.jobs INFO cancelling job 2e3e-w0 (lease a791e607 expired)", "lease expired (in the w0 log)"),
    ("scheduler", "JobFailed: parameter server shard 0 failed: no route to ps: timed out", "no route to ps (in the scheduler log)"),
    ("w0", "jaxlib.xla_extension.XlaRuntimeError: RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm", "device out of memory (in the w0 log)"),
    # An offer that was not taken expires too: that is no cause.
    ("ps", "hypha.worker.arbiter INFO lease caec1269 expired", None),
    # ... unless the scheduler was about to take it (my chip run, PR 37, seed 2147730001):
    # named, by what the scheduler's log ends in, and not tried again (test_retry.py).
    ("scheduler", 'x\n  File "/r/hypha_tpu/scheduler/worker_handle.py", line 60, in create\n'
     "    timeout = await handle._renew()\n  File \"/r/hypha_tpu/network/node.py\", line 897, in "
     "_request_inner\n    raise RequestError(reply.get(\"error\", \"remote error\"))\n"
     "hypha_tpu.network.node.RequestError: 'd11362ad-fa58-42fa-9898-3f85e7773e1c'",
     "a request about a lease was refused (in the scheduler log)"),
])
def test_known_causes_are_named_from_the_logs(role, line, named):
    assert logs.named_cause({role: f"2026-09-27 00:59:32,863 {line}\n"}) == named


@pytest.mark.parametrize("path,seq,expect_params,expect_flops", [
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 50257 x 1024; 6 N + 12 x 24 x 1024 x 1024
    (FIXTURES / "gpt2-medium.json", 1024, 353_453_056, 2_422_708_224),
    # 2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 14336 + 32000 x 4096; 6 N + 12 x 4096 x 4096
    (REPO / "perfbench/configs/mistral-7b-d1.json", 4096, 349_175_808, 2_296_381_440),
], ids=["gpt2-medium", "mistral-7b-d1"])
def test_flop_count_of_each_configuration(path, seq, expect_params, expect_flops):
    body = json.loads(path.read_text())
    assert flops.matmul_params(body["flops"]) == expect_params
    assert flops.flops_per_token(body["flops"], seq) == expect_flops


def test_h_by_rule_is_the_largest_multiple_of_8_that_fits():
    # gpt2-medium's calibration (PERF.md 7, row 1): 152 * 0.1912 + 10.97 = 40.03 <= 40.8
    assert measure.h_by_rule(0.1912, 10.97, 51) == 152
    assert measure.h_by_rule(1.0, 50.0, 51) == 8  # never under one multiple


@pytest.mark.parametrize("cell_name", ["mistral-7b-d1.steps"])
def test_inner_steps_of_a_steps_cell_follow_the_rule_from_its_calibration(cell_name):
    cell = manifest.resolve(cell_name, REPO)
    rule = cell.traffic["inner_steps_rule"]
    seconds = manifest.load_manifest(REPO)["run_seconds"]
    h = measure.h_by_rule(rule["median_step_s"], rule["sync_exposed_s"], seconds)
    assert h == cell.traffic["inner_steps"] and h % 8 == 0
    assert h * rule["median_step_s"] + rule["sync_exposed_s"] <= 0.8 * seconds
    assert (h + 8) * rule["median_step_s"] + rule["sync_exposed_s"] > 0.8 * seconds
    # twice the sequences of the three rounds a run can start
    assert cell.traffic["data"]["sequences"] >= 2 * 3 * h * cell.traffic["batch"]


def test_mistral_configuration_builds_the_published_widths():
    """``LlamaConfig``'s defaults are Llama-2's; the file has to override them."""
    from hypha_tpu import cli
    from hypha_tpu.models import build_model
    from perfbench import data

    cell = manifest.resolve("mistral-7b-d1.steps", REPO)
    sets = [x for s in data.job_sets(cell.config, cell.traffic, 7) for x in ("--set", s)]
    conf = cli._load_config("scheduler", cli.build_parser().parse_args(["scheduler", "run", *sets]))
    job = conf.job.to_job()
    _, cfg = build_model(dict(job.model))
    published = cell.config
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads, cfg.num_kv_heads) == (
        published["hidden_size"], published["intermediate_size"],
        published["num_attention_heads"], published["num_key_value_heads"],
    ) == (4096, 14336, 32, 8)
    assert cfg.hidden_size // cfg.num_heads == published["head_dim"] == 128
    assert (cfg.vocab_size, cfg.rope_theta, cfg.rms_eps, cfg.sliding_window) == (
        32000, 10000.0, 1e-5, 4096)
    assert cfg.tie_word_embeddings is False and cfg.remat is False
    assert (cfg.num_layers, cfg.max_seq_len) == (1, 4096)  # the two reduced keys
    assert job.rounds.max_batch_size == 4
    assert job.rounds.avg_samples_between_updates == 4 * cell.traffic["inner_steps"]
    assert job.sync_mode == "blocking" and job.delta_codec == "none"


def test_gpt2_medium_configuration_builds_the_published_sizes():
    from hypha_tpu.models import build_model

    spec = {"family": "gpt2", "config": {
        s.split("job.model_config.")[1].split("=")[0]: int(s.split("=")[1])
        for s in CELL.config["job_sets"] if "model_config" in s
    }}
    _, cfg = build_model(spec)
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.n_positions, cfg.vocab_size) == (
        1024, 24, 16, 1024, 50257)
    assert cfg.remat is False


def test_derived_expressions_are_arithmetic_only():
    assert derived.evaluate("(a - 2 * b) / -c", {"a": 10, "b": 2, "c": 3}) == -2.0
    for bad in ("__import__('os')", "a.b", "a if b else c", "[1][0]", "a ** 2"):
        with pytest.raises((ValueError, KeyError)):
            derived.evaluate(bad, {"a": 1, "b": 2, "c": 3})


def test_trace_reduction_unions_operations_and_splits_gaps_by_the_finest_span():
    busy = merge([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert busy == [(0, 20), (30, 40)]
    assert gaps(busy, -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    spans = [
        {"node": "scheduler", "name": "round", "start_ns": 0, "end_ns": 100},
        {"node": "ps", "name": "quorum_wait", "start_ns": 10, "end_ns": 90},
        {"node": "ps", "name": "outer_step", "start_ns": 22, "end_ns": 27},
    ]
    assert attribute((20, 30), spans) == {"ps:outer_step": 5, "ps:quorum_wait": 5}
    assert attribute((95, 120), spans) == {"scheduler:round": 5, "no span": 20}
    assert op_name("%fusion.98 = f32[8]{0} fusion(...)") == "fusion"
    assert op_name('%h_8.5 = custom-call(...), custom_call_target="tpu_custom_call"') == (
        "tpu_custom_call (Pallas kernels)")
    assert op_name("%copy-done = ...") == "copy-done"


def test_log_line_parsing_matches_the_workers_format():
    line = logs.find_line(W0, logs.ROUND_LINE)
    fields = logs.parse_fields(line)
    assert fields["steps"] == 8 and fields["tokens"] == ROUND_TOKENS
    assert isinstance(fields["median_step_s"], float) and fields["peak_bytes"] == 9969300992
    assert logs.parse_fields("native_kernels=True peak_bytes=None kind='TPU v5 lite'") == {
        "native_kernels": True, "peak_bytes": None, "kind": "TPU v5 lite"}
    assert logs.line_time("worker w0 on ['127.0.0.1:1']") is None
    assert math.isclose(
        logs.line_time("2026-09-27 01:15:21,039 x") - logs.line_time("2026-09-27 01:15:20,000 x"),
        1.039, abs_tol=1e-6)
