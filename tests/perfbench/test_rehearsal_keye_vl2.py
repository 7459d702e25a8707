"""The harness end to end on the CPU with a keye_vl2 cell at a tiny size (two
blocks; a query keeps 128 of up to 1024 keys; one rank's 2 of 8 experts): the
family goes through the five CLI roles as files and entries only, trains
through the untied head by the routed step with its second objective, its
reference decides the first loss, the worker says what the stack holds, and the
routing counters and the objective's line come on the round's lines."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from perfbench import logs
from perfbench_helpers import (
    DATA, REPO, failing_checks, make_root, notes, processes_under, rehearsal_result, run_bench,
)

CELL, CONFIG = "keye-vl-2-30b-a3b-txt-d4.steps", "keye-vl-2-30b-a3b-txt-d4"
NEW = ["keye_index_scores_ms", "keye_index_scores_roofline", "keye_index_select_ms", "keye_index_kl_ms",
       "keye_flash_sel_ms", "keye_flash_sel_roofline", "keye_keys_picked_share", "keye_moe_route_ms",
       "keye_moe_experts_ms", "keye_moe_experts_roofline", "keye_moe_pairs_per_token", "keye_moe_load_max_over_mean"]
ON_A_CPU = {"keye_keys_picked_share", "keye_moe_pairs_per_token", "keye_moe_load_max_over_mean"}  # counters: no device needed


def add_keye_cell(root) -> None:
    bench = root / "perfbench"
    shutil.copy(DATA / "tiny-keye-vl2.json", bench / "configs" / "tiny-keye-vl2.json")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-keye-vl2", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-keye-vl2.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-keye-vl2.h4", "config": "tiny-keye-vl2", "traffic": "tiny.h4",
                           "chips": 1, "why": "CPU rehearsal"})
    for metric in m["per_layer"]:  # the real cell's twelve, read by this one too
        if metric.get("workloads") == [CELL]:
            metric["workloads"] = ["tiny-keye-vl2.h4"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    from test_data_driven import _digests

    root = make_root(tmp_path_factory.mktemp("keye"))
    before = _digests(root)
    add_keye_cell(root)
    r = run_bench(root, "--workload", "tiny-keye-vl2.h4", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "1")
    w0 = (root / "chiprun_out" / "perfbench" / "tiny-keye-vl2.h4" / "traced" / "w0.log").read_text()
    return root, r, w0, before, _digests(root)


def test_the_family_is_files_and_entries_only(ran):
    _, r, _, before, after = ran
    assert r.returncode == 3, r.stderr[-3000:]
    assert {k: after[k] for k in before} == before  # nothing that was there changed


def test_the_manifest_lists_the_twelve_metrics_for_the_one_cell():
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert [p["name"] for p in mine] == NEW
    assert all(p["moves"] == "tokens_per_s" for p in mine)
    assert {p["name"] for p in mine if p["source"] == "program_counter"} == ON_A_CPU
    assert all(p["source"] == "device_trace" for p in mine if p["name"] not in ON_A_CPU)
    assert {p["layer"] for p in mine} == {"Inner step", "Expert routing and grouped product"}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and len(cell["why"]) <= 200
    assert sum(w["config"] == CONFIG for w in m["workloads"]) == 1
    assert sum(c["name"] == CONFIG for c in m["configs"]) == 1
    assert not any(CELL in p.get("workloads", []) for p in m["per_layer"] if p not in mine)  # no entry that was there extended


def test_correct_is_false_only_because_the_device_is_no_tpu(ran):
    _, r, _, _, _ = ran
    assert rehearsal_result(r.stderr)["correct"] is False
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}
    assert rehearsal_result(r.stderr)["attempted"] >= 1


def test_the_reference_takes_the_same_share_and_decides_the_first_loss(ran):
    _, r, _, _, _ = ran
    checks = notes(r.stdout)["checks"]
    assert checks["reference_ran"] is True and checks["first_loss_as_reference"] is True
    held = checks["margins"]["first_loss_as_reference"]
    assert held["value"] < 0.5 * held["high"] and 5.0 < held["reference"] < 6.2


def test_the_worker_says_what_the_stack_holds(ran):
    _, _, w0, _, _ = ran
    assert re.search(
        r"operators: sparse_attention=2 experts=2 head_dim=8 index_heads=4 index_head_dim=8 index_topk=128 "
        r"router=softmax$", w0, re.M)


def test_nothing_is_dropped_and_the_counters_have_the_line_afmoes_have(ran):
    _, _, w0, _, _ = ran
    rows = [logs.parse_fields(m.group(0)) for m in re.finditer(r"round \d+ routing: .*", w0)]
    assert len(rows) >= 2
    for row in rows:
        assert row["pairs_computed"] == row["pairs_routed"] > 0
        assert (row["steps"], row["expert_layers"], row["experts_held"]) == (4, 2, 2)
        assert 0.1 < row["pairs_per_token"] <= 2.0 and row["load_max_over_mean"] >= 1.0


def test_the_second_objective_has_a_line_of_its_own_and_the_loss_stays_the_cross_entropy(ran):
    _, r, w0, _, _ = ran
    rows = [logs.parse_fields(m.group(0)) for m in re.finditer(r"round \d+ objective: .*", w0)]
    assert len(rows) >= 2
    share = (128 * 129 // 2 + (1024 - 128) * 128) / (1024 * 1025 // 2)  # picked pairs over causal pairs
    for row in rows:
        assert row["steps"] == 4 and 0.0 < row["index_kl"] < 5.0
        assert abs(row["keys_picked_share"] - share) < 1e-4
    # round 0's first loss is the reference's cross-entropy: the KL is no part of the logged loss
    assert notes(r.stdout)["checks"]["first_loss_as_reference"] is True


def test_the_metrics_a_cpu_can_read_are_on_the_line_and_the_devices_are_left_out(ran):
    """No device events on a CPU: the scope and roofline readers return
    nothing and raise nothing, as on a program without the scopes."""
    root, r, _, _, _ = ran
    metrics = rehearsal_result(r.stderr)["metrics"]
    assert set(NEW) & set(metrics) == ON_A_CPU
    assert abs(metrics["keye_keys_picked_share"]["value"] - 0.2344) < 2e-4  # the cell's own share, by chance of the sizes
    shared = {m["name"] for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
              if "workloads" not in m}
    never, not_always = {"mfu_step", "hbm_peak_gb"}, {"lease_margin_min_s", "renew_late_max_s", "sync_cleanup_s"}
    assert shared - never - not_always <= set(metrics)
    assert "Traceback" not in r.stderr


def test_no_child_of_the_run_is_left_alive(ran):
    root, *_ = ran
    assert processes_under(root) == []
