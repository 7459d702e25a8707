"""The per-layer metrics that read the phase spans and the lease lines, on a
traced run of ``mistral-7b-d1.sync-h8`` recorded on the chip from this
benchmark's own tree (``data/recorded_split/``, PR 28): every entry of
``BENCHMARK.json`` that every cell reports reads a number there, each spec
file resolves through ``perfbench.manifest``, and the parts add up to their
wholes. An entry with a ``workloads`` list is held by a test beside its own
cell's record (``test_afmoe_counts.py`` for Trinity's seven), so a later PR
lists a metric by adding a spec, an entry and a test, and edits nothing here."""

from __future__ import annotations

import json

import pytest

import manifest_checks
from perfbench import cluster, flops, logs, manifest, measure, readers

from perfbench_helpers import DATA as FIXTURES, REPO, RESULT_KEYS

DATA = FIXTURES / "recorded_split"
CELL_NAME = "mistral-7b-d1.sync-h8"
PER_LAYER = manifest.load_manifest(REPO)["per_layer"]
LISTED = [e["name"] for e in PER_LAYER if "workloads" not in e]  # every cell reports these
OF_ONE_CELL = [e["name"] for e in PER_LAYER if "workloads" in e]
# Entered in PR 25 (three) and in PR 28 (fifteen): the ones that read the
# phase spans, the step record and the lease lines.
NEW = [
    "ps_upload_s", "sync_wait_s", "sync_unaccounted_s",
    "ps_step_nesterov_s", "ps_step_save_update_s", "ps_fold_read_s", "ps_fold_accumulate_s",
    "ps_notify_s", "sync_extract_s", "sync_write_s", "sync_merge_read_s", "sync_merge_apply_s",
    "sync_cleanup_s", "step_slowest_ms", "step_status_ms", "step_input_wait_ms",
    "lease_margin_min_s", "renew_late_max_s",
]
LEASE = {"lease_margin_min_s": "higher", "renew_late_max_s": "lower"}  # read log lines
PARTS = {  # whole -> its parts, which leave it only its self time
    "ps_outer_step_s": ["ps_step_nesterov_s", "ps_step_save_update_s"],
    "ps_fold_s": ["ps_fold_read_s", "ps_fold_accumulate_s"],
    "sync_encode_s": ["sync_extract_s", "sync_write_s"],
    "sync_merge_s": ["sync_merge_read_s", "sync_merge_apply_s"],
}


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL_NAME, REPO)


@pytest.fixture(scope="module")
def run(cell):
    texts = {n: (DATA / f"{n}.log").read_text() for n in ("w0", "ps", "scheduler")}
    start = logs.line_time(texts["scheduler"].splitlines()[1]) - 20.0
    run = cluster.Run(t_start=0.0, t_wall=start, out_dir=DATA, trace=True)
    run.texts, run.holders = texts, ["w0"]
    run.events["scheduler_start"] = start + 18.0
    run.spans = [json.loads(x) for x in (DATA / "spans.jsonl").read_text().splitlines()]
    run.reference = json.loads((DATA / "reference.json").read_text())
    measure.from_logs(run, texts, cell.traffic, 51.0)
    assert [r["round"] for r in run.measured] == [1, 2]
    return run


@pytest.fixture(scope="module")
def values(cell, run):
    return readers.read_all(cell, run)


def test_every_specified_metric_is_listed_but_the_two_no_cell_can_read():
    manifest_checks.check_specs_are_listed(manifest.load_manifest(REPO), REPO)
    listed = set(LISTED) | set(OF_ONE_CELL)
    assert len(listed) == len(PER_LAYER) and set(NEW) <= set(LISTED)
    assert len(NEW) == len(set(NEW)) == 18
    # What every cell reports is what the recorded Mistral run can read: the 33.
    assert CELL_NAME.startswith("mistral") and len(LISTED) == len(
        manifest.resolve(CELL_NAME, REPO).per_layer)
    assert not (REPO / "perfbench" / "pending_per_layer.json").exists()


@pytest.mark.parametrize("name", LISTED)
def test_every_listed_metric_reads_a_number_from_the_record_of_this_tree(values, name):
    assert isinstance(values[name], float)
    if name != "sync_unaccounted_s":  # a remainder: small, of either sign
        assert values[name] > 0


def test_the_recorded_run_is_correct_and_its_traced_line_carries_every_listed_metric(cell, run, values):
    run.profile = {"busy_s": 4.6, "window_s": 18.0,
                   "breakdown": {"device_ops": [["fusion", 3.5]], "idle_gaps": []}}
    result = measure.result(run, cell, trace=True, layer_values=values)
    assert result["correct"] is True, run.checks
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert set(result["metrics"]) == set(LISTED)
    assert run.checks["reference_ran"] and run.checks["first_loss_as_reference"]
    held = run.margins["first_loss_as_reference"]
    assert held["value"] < 0.5 * held["high"]
    for name, m in run.margins.items():  # no band nearer its edge than a factor of three
        if name == "loss_stays_down_after_outer_step":
            assert m["value"] * 3 < m["high"]


@pytest.mark.parametrize("name", NEW)
def test_spec_resolves_and_agrees_with_its_entry(cell, name):
    entry, spec = next((e, s) for e, s in cell.per_layer if e["name"] == name)
    assert (entry["source"], entry["better"]) == (
        ("program_counter", LEASE[name]) if name in LEASE else ("program_span", "lower"))
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()


# The least share of the whole that the parts cover. ``fold`` keeps 4 % to
# itself: when ``RoundAccum.fold`` returns, the decoded 1.92 GB delta and its
# scaled copy are freed, 0.16 s that is in the parent and in no child.
COVERED = {"ps_fold_s": 0.95}


@pytest.mark.parametrize("whole", sorted(PARTS))
def test_parts_sum_to_their_whole(values, whole):
    parts = sum(values[p] for p in PARTS[whole])
    assert COVERED.get(whole, 0.98) * values[whole] <= parts <= values[whole] * 1.0005


def test_the_wait_is_the_await_update_span_and_most_of_the_exposed_sync(values, cell):
    spans = [json.loads(x) for x in (DATA / "spans.jsonl").read_text().splitlines()]
    waits = [s for s in spans if s["name"] == "await_update" and s["attrs"]["round"] in (1, 2)]
    assert len(waits) == 2  # the two measured rounds; the metric is their median
    span_s = sum(s["mono_end_ns"] - s["mono_start_ns"] for s in waits) / 2e9
    assert values["sync_wait_s"] == pytest.approx(span_s, abs=1e-3)
    covered = sum(values[k] for k in ("sync_encode_s", "sync_upload_s", "sync_wait_s", "sync_merge_s"))
    assert values["sync_unaccounted_s"] == pytest.approx(values["sync_exposed_s"] - covered)
    assert values["sync_wait_s"] > 0.5 * values["sync_exposed_s"]
    assert abs(values["sync_unaccounted_s"]) < 0.1 * values["sync_exposed_s"]


def test_the_steps_share_of_the_peak_reads_what_it_read_before_the_group_could_state_attention_keys(values, cell):
    """``mfu_step`` of this record, digit for digit as at ccfad04: neither
    configuration's ``flops`` group states ``attention_keys``, and without the
    key ``flops.flops_per_token`` is the formula it was."""
    assert "attention_keys" not in cell.config["flops"]
    assert repr(values["mfu_step"]) == "66.34856090084989" and values["step_ms"] == 287.85
    per_token = 6.0 * flops.matmul_params(cell.config["flops"]) + 12.0 * 1 * 4096 * 4096
    assert flops.flops_per_token(cell.config["flops"], 4096) == per_token == 2_296_381_440
    assert values["mfu_step"] == 100 * per_token * (4 * 4096) / (287.85 / 1000) / 197e12


def test_the_step_record_agrees_with_the_round_line(values):
    assert values["step_slowest_ms"] >= values["step_ms"]
    # Host work between steps, from where it happens, against the same gap
    # worked out from outside (inner_steps span minus H x median).
    inside = values["step_status_ms"] + values["step_input_wait_ms"]
    assert 0 < inside <= values["inner_gap_ms"] + 1.0


def test_the_lease_kept_its_margin_in_the_recorded_run(values):
    """Renewed at 2/3 of 30 s, a lease has 10 s left unless a loop stood
    still; the run recorded here lost under a second of it."""
    assert 9.0 < values["lease_margin_min_s"] <= 10.01
    assert 0 <= values["renew_late_max_s"] < 1.0
    assert values["lease_margin_min_s"] + values["renew_late_max_s"] <= 10.05


def test_the_cleanup_is_the_span_and_0_where_the_program_dropped_it(cell, run, values):
    """The ``cleanup`` span exists only from 10 ms (``trace.SLOW_CLEANUP_S``),
    and on a memory-backed work directory an unlinking takes none: the spec's
    ``absent`` says what the reader gives where ``w0`` wrote spans in the
    measured rounds and none of that name, so the metric is in every traced
    line. A run without spans still reads nothing."""
    import dataclasses

    assert values["sync_cleanup_s"] == pytest.approx(0.709, abs=0.001)  # rounds 1 and 2, not round 0's 0.732
    spec = next(s for e, s in cell.per_layer if e["name"] == "sync_cleanup_s")
    assert (spec["reader"], spec["name"], spec["node"], spec["absent"]) == ("span", "cleanup", "w0", 0.0)
    dropped = dataclasses.replace(run, spans=[sp for sp in run.spans if sp.get("name") != "cleanup"])
    assert readers.read_spec(spec, dropped, cell, {}) == 0.0
    untraced = dataclasses.replace(run, spans=[])
    assert readers.read_spec(spec, untraced, cell, {}) is None
    only_ps = dataclasses.replace(run, spans=[sp for sp in run.spans if sp.get("node") != "w0"])
    assert readers.read_spec(spec, only_ps, cell, {}) is None
    # a span reader without ``absent`` says nothing where its span is not there
    bare = {k: v for k, v in spec.items() if k != "absent"}
    assert readers.read_spec(bare, dropped, cell, {}) is None
