"""The per-layer metrics that read the phase spans and the lease lines of
PR 25, on a traced run of ``mistral-7b-d1.sync-h8`` recorded on the chip
(``data/recorded_split/``): each spec file resolves through
``perfbench.manifest``, reads a number from the recorded spans and lines, and
the parts add up to their wholes."""

from __future__ import annotations

import json

import pytest

from perfbench import cluster, logs, manifest, measure, readers

from perfbench_helpers import DATA as FIXTURES, REPO

DATA = FIXTURES / "recorded_split"
CELL_NAME = "mistral-7b-d1.sync-h8"
PENDING = json.loads((REPO / "perfbench" / "pending_per_layer.json").read_text())["per_layer"]
LISTED_HERE = ["ps_upload_s", "sync_wait_s", "sync_unaccounted_s"]  # in BENCHMARK.json since PR 25
NEW = LISTED_HERE + [e["name"] for e in PENDING]
LEASE = {"lease_margin_min_s": "higher", "renew_late_max_s": "lower"}  # read log lines
PARTS = {  # whole -> its parts, which leave it only its self time
    "ps_outer_step_s": ["ps_step_mean_s", "ps_step_load_s", "ps_step_nesterov_s",
                        "ps_step_save_update_s", "ps_step_save_momentum_s"],
    "ps_fold_s": ["ps_fold_read_s", "ps_fold_accumulate_s"],
    "sync_encode_s": ["sync_extract_s", "sync_write_s"],
    "sync_merge_s": ["sync_merge_read_s", "sync_merge_apply_s"],
}


def full_manifest() -> dict:
    m = manifest.load_manifest(REPO)
    m["per_layer"] = m["per_layer"] + PENDING
    return m


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL_NAME, REPO, full_manifest())


@pytest.fixture(scope="module")
def values(cell):
    texts = {n: (DATA / f"{n}.log").read_text() for n in ("w0", "ps", "scheduler")}
    start = logs.line_time(texts["scheduler"].splitlines()[1]) - 20.0
    run = cluster.Run(t_start=0.0, t_wall=start, out_dir=DATA, trace=True)
    run.texts, run.holders = texts, ["w0"]
    run.events["scheduler_start"] = start + 18.0
    run.spans = [json.loads(x) for x in (DATA / "spans.jsonl").read_text().splitlines()]
    measure.from_logs(run, texts, cell.traffic, 51.0)
    assert [r["round"] for r in run.measured] == [1]
    return readers.read_all(cell, run)


def test_the_pending_list_and_the_manifest_do_not_overlap():
    listed = {e["name"] for e in manifest.load_manifest(REPO)["per_layer"]}
    assert set(LISTED_HERE) <= listed
    assert not listed & {e["name"] for e in PENDING}
    assert len(NEW) == len(set(NEW)) == 21


@pytest.mark.parametrize("name", NEW)
def test_spec_resolves_and_agrees_with_its_entry(cell, name):
    entry, spec = next((e, s) for e, s in cell.per_layer if e["name"] == name)
    assert (entry["source"], entry["better"]) == (
        ("program_counter", LEASE[name]) if name in LEASE else ("program_span", "lower"))
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert (REPO / "perfbench" / "readers" / f"{spec['reader']}.py").is_file()


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_a_number_from_the_recorded_run(values, name):
    assert isinstance(values[name], float)
    if name != "sync_unaccounted_s":  # a remainder: small, of either sign
        assert values[name] > 0


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
    (wait,) = [s for s in spans if s["name"] == "await_update" and s["attrs"]["round"] == 1]
    span_s = (wait["mono_end_ns"] - wait["mono_start_ns"]) / 1e9
    assert values["sync_wait_s"] == pytest.approx(span_s, abs=1e-3)
    covered = sum(values[k] for k in ("sync_encode_s", "sync_upload_s", "sync_wait_s", "sync_merge_s"))
    assert values["sync_unaccounted_s"] == pytest.approx(values["sync_exposed_s"] - covered)
    assert values["sync_wait_s"] > 0.5 * values["sync_exposed_s"]
    assert abs(values["sync_unaccounted_s"]) < 0.1 * values["sync_exposed_s"]


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
