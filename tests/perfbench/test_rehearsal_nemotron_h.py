"""The harness end to end on the CPU with a nemotron_h cell at a tiny size
(source layers 1 to 5 of 7: Mamba-2, experts, Mamba-2, attention, experts; one
rank's 2 of 8 experts): the family goes through the five CLI roles as files and
entries only, trains through the untied head by the routed step, its reference
decides the first loss, the worker says which parts it holds, the scan's chunk
and the experts' form, and the routing counters come on the line afmoe's come
on."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from perfbench import logs
from perfbench_helpers import (
    DATA, REPO, failing_checks, make_root, notes, processes_under, rehearsal_result, run_bench,
)

CELL, CONFIG = "nemotron-twotower-ctx-d7.steps", "nemotron-twotower-ctx-d7"
NEW = ["nemo_mamba2_ms", "nemo_ssd_ms", "nemo_ssd_roofline", "nemo_flash_full_ms", "nemo_flash_full_roofline",
       "nemo_moe_route_ms", "nemo_moe_experts_ms", "nemo_moe_experts_roofline", "nemo_moe_pairs_per_token",
       "nemo_moe_load_max_over_mean"]
ON_A_CPU = {"nemo_moe_pairs_per_token", "nemo_moe_load_max_over_mean"}  # counters: no device needed


def add_nemotron_cell(root) -> None:
    bench = root / "perfbench"
    shutil.copy(DATA / "tiny-nemotron-h.json", bench / "configs" / "tiny-nemotron-h.json")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-nemotron-h", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-nemotron-h.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-nemotron-h.h4", "config": "tiny-nemotron-h", "traffic": "tiny.h4",
                           "chips": 1, "why": "CPU rehearsal"})
    for metric in m["per_layer"]:  # the real cell's ten, read by this one too
        if metric.get("workloads") == [CELL]:
            metric["workloads"] = ["tiny-nemotron-h.h4"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    from test_data_driven import _digests

    root = make_root(tmp_path_factory.mktemp("nemo"))
    before = _digests(root)
    add_nemotron_cell(root)
    r = run_bench(root, "--workload", "tiny-nemotron-h.h4", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "1")
    w0 = (root / "chiprun_out" / "perfbench" / "tiny-nemotron-h.h4" / "traced" / "w0.log").read_text()
    return root, r, w0, before, _digests(root)


def test_the_family_is_files_and_entries_only(ran):
    _, r, _, before, after = ran
    assert r.returncode == 3, r.stderr[-3000:]
    assert {k: after[k] for k in before} == before  # nothing that was there changed


def test_the_manifest_lists_the_ten_metrics_for_the_one_cell_wherever_they_stand():
    """By name, not by place: a later cell's entries may follow these."""
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


def test_the_phi4_cells_nine_stand_where_they_stood():
    """``test_rehearsal_phi4flash.py`` holds that Phi-4's nine are the manifest's
    *last* entries and its cell and configuration the last of their lists, which
    this cell's entries, appended, outdate (``tests/conftest.py`` expects that
    one line to fail): every assertion of it again, by name and not by place."""
    from test_rehearsal_phi4flash import NEW as PHI4

    m = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [p for p in m["per_layer"] if p.get("workloads") == ["phi-4-mini-flash-d5.steps"]]
    names = [p["name"] for p in m["per_layer"]]
    assert [p["name"] for p in mine] == PHI4 == names[names.index(PHI4[0]):names.index(PHI4[0]) + len(PHI4)]
    assert all(p["moves"] == "tokens_per_s" and p["source"] == "device_trace"
               and p["layer"] == "Inner step" for p in mine)
    cell = next(w for w in m["workloads"] if w["name"] == "phi-4-mini-flash-d5.steps")
    assert cell["chips"] == 1 and cell["config"] == "phi-4-mini-flash-d5"
    assert sum(c["name"] == "phi-4-mini-flash-d5" for c in m["configs"]) == 1
    assert sum(w["config"] == "phi-4-mini-flash-d5" for w in m["workloads"]) == 1


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


def test_the_worker_says_which_parts_it_holds_the_scans_chunk_and_the_experts_form(ran):
    from hypha_tpu.ops.ssd_scan import CHUNK

    _, _, w0, _, _ = ran
    assert re.search(
        rf"operators: mamba2=2 experts=2 full_attention=1 head_dim=8 ssd_chunk={CHUNK} expert_form=relu2$", w0, re.M)


def test_nothing_is_dropped_and_the_counters_have_the_line_afmoes_have(ran):
    _, _, w0, _, _ = ran
    rows = [logs.parse_fields(m.group(0)) for m in re.finditer(r"round \d+ routing: .*", w0)]
    assert len(rows) >= 2
    for row in rows:
        assert row["pairs_computed"] == row["pairs_routed"] > 0
        assert (row["steps"], row["expert_layers"], row["experts_held"]) == (4, 2, 2)
        assert 0.2 < row["pairs_per_token"] < 1.0 and row["load_max_over_mean"] >= 1.0


def test_the_metrics_a_cpu_can_read_are_on_the_line_and_the_devices_are_left_out(ran):
    """No device events on a CPU: the scope and roofline readers return
    nothing and raise nothing, as on a program without the scopes."""
    root, r, _, _, _ = ran
    metrics = rehearsal_result(r.stderr)["metrics"]
    assert set(NEW) & set(metrics) == ON_A_CPU
    shared = {m["name"] for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
              if "workloads" not in m}
    never, not_always = {"mfu_step", "hbm_peak_gb"}, {"lease_margin_min_s", "renew_late_max_s", "sync_cleanup_s"}
    assert shared - never - not_always <= set(metrics)
    assert "Traceback" not in r.stderr


def test_the_scopes_of_the_new_layers_are_in_the_step(ran):
    """Device events carry the scopes on the chip; here the traced step's
    jaxpr is what can be read."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    model, _ = build_model({"family": "nemotron_h", "preset": "tiny"})
    ids = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)

    def loss(v):
        return model.apply(v, ids)[0].sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(variables).pretty_print(name_stack=True))
    for scope in ("mamba2", "ssd_scan", "gated_norm", "full_attention", "attention", "router",
                  "shared_expert", "moe_dispatch", "moe_experts", "moe_combine"):
        assert scope in text, scope
    assert "mamba2/ssd_scan" in text and "mamba2/gated_norm" in text  # inside the layer's scope
    # the backward pass's products carry the scan's scope too
    assert re.search(r"transpose\(jvp\(.*mamba2/ssd_scan", text)


def test_no_child_of_the_run_is_left_alive(ran):
    root, *_ = ran
    assert processes_under(root) == []
