"""Every assertion of the two tests that each hold one literal count of the
manifest (``test_rehearsal.py``: ``len(per_layer) - 7``; ``test_fourth_cell.py``:
``4 workloads and 3 configurations``), over the same fixtures, with the counts
relative to the manifest as it is.

A cell added to ``BENCHMARK.json`` outdates both literals, and a PR that adds
a cell may not edit those files, so ``tests/conftest.py`` expects each test to
fail at that one line (and at no other). What stands after the line there does
not run while it fails; here it does. The next ``benchmark`` PR makes the two
counts relative, takes the hook out and deletes this file (PERF.md 7)."""

from __future__ import annotations

import json

from perfbench import manifest
from perfbench_helpers import REPO, rehearsal_result
from test_data_driven import _digests
from test_fourth_cell import CELL, CONFIG, NEW_METRICS, fourth  # noqa: F401  (the fixture)
from test_rehearsal import traced  # noqa: F401  (the fixture: one more traced tiny run)


def test_a_traced_run_reports_every_metric_all_cells_share_and_names_the_missing_peak(traced):  # noqa: F811
    _, r = traced
    assert r.returncode == 3, r.stderr[-3000:]
    result = rehearsal_result(r.stderr)
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    # What every cell reports; a metric with a ``workloads`` list is its own cells'.
    names = {m["name"] for m in real["per_layer"] if "workloads" not in m}
    never = {"mfu_step", "hbm_peak_gb"}  # no peak for a CPU, no memory statistic from it
    not_always = {"lease_margin_min_s", "renew_late_max_s", "sync_cleanup_s"}
    assert names - never - not_always <= set(result["metrics"]) <= names - never
    # The count, relative: every other entry lists the cells it is read in, each
    # a cell of the manifest and none the tiny one, so none of them is on the line.
    listed = [m for m in real["per_layer"] if "workloads" in m]
    assert len(names) == len(real["per_layer"]) - len(listed) and len(listed) >= 7
    cells = {w["name"] for w in real["workloads"]}
    assert all(m["workloads"] and set(m["workloads"]) <= cells for m in listed)
    assert not {m["name"] for m in listed} & set(result["metrics"])
    assert "no peak FLOP/s known for device_kind 'cpu'" in r.stderr


def test_a_toy_fourth_cell_touches_nothing_that_was_there_and_adds_exactly_its_own(fourth):  # noqa: F811
    root, m, before = fourth
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    added = sorted(set(after) - set(before))
    assert added == sorted(
        [f"perfbench/configs/{CONFIG}.json", f"perfbench/traffic/{CELL}.json",
         "perfbench/reference/smallthinker_toy.py"]
        + [f"perfbench/layer_metrics/{n}.json" for n in NEW_METRICS])
    real = manifest.load_manifest(REPO)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert m[key] == real[key]
    for key in ("configs", "workloads", "per_layer"):
        assert m[key][:len(real[key])] == real[key]  # what was there, as it was, first
    assert len(m["workloads"]) == len(real["workloads"]) + 1
    assert len(m["configs"]) == len(real["configs"]) + 1
    assert len(m["per_layer"]) == len(real["per_layer"]) + len(NEW_METRICS)
