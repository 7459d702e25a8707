"""The harness end to end on the CPU with a phi4flash cell at a tiny size
(source layers 3 to 7 of 8: window attention, Mamba, full attention, GMU,
cross-attention): the family goes through the five CLI roles as files and
entries only, trains through the tied head by the chunked step, its reference
decides the first loss, and the worker says which operators it holds and the
scan's chunk."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from perfbench_helpers import (
    DATA, failing_checks, make_root, notes, processes_under, rehearsal_result, run_bench,
)

NEW = ["phi4_mamba_ms", "phi4_scan_ms", "phi4_scan_roofline", "phi4_gmu_ms", "phi4_flash_window_ms",
       "phi4_flash_window_roofline", "phi4_flash_full_ms", "phi4_flash_full_roofline",
       "phi4_diff_combine_ms"]


def add_phi4_cell(root) -> None:
    bench = root / "perfbench"
    shutil.copy(DATA / "tiny-phi4flash.json", bench / "configs" / "tiny-phi4flash.json")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-phi4flash", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-phi4flash.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-phi4flash.h4", "config": "tiny-phi4flash", "traffic": "tiny.h4",
                           "chips": 1, "why": "CPU rehearsal"})
    for metric in m["per_layer"]:  # the real cell's nine, read by this one too
        if metric.get("workloads") == ["phi-4-mini-flash-d5.steps"]:
            metric["workloads"] = ["tiny-phi4flash.h4"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    from test_data_driven import _digests

    root = make_root(tmp_path_factory.mktemp("phi4"))
    before = _digests(root)
    add_phi4_cell(root)
    r = run_bench(root, "--workload", "tiny-phi4flash.h4", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "1")
    w0 = (root / "chiprun_out" / "perfbench" / "tiny-phi4flash.h4" / "traced" / "w0.log").read_text()
    return root, r, w0, before, _digests(root)


def test_the_family_is_files_and_entries_only(ran):
    _, r, _, before, after = ran
    assert r.returncode == 3, r.stderr[-3000:]
    assert {k: after[k] for k in before} == before  # nothing that was there changed


def test_the_manifest_lists_the_nine_metrics_for_the_one_cell():
    from perfbench_helpers import REPO

    m = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [p for p in m["per_layer"] if p.get("workloads") == ["phi-4-mini-flash-d5.steps"]]
    assert [p["name"] for p in mine] == NEW == [p["name"] for p in m["per_layer"][-len(NEW):]]
    assert all(p["moves"] == "tokens_per_s" and p["source"] == "device_trace"
               and p["layer"] == "Inner step" for p in mine)
    assert m["workloads"][-1]["name"] == "phi-4-mini-flash-d5.steps" and m["workloads"][-1]["chips"] == 1
    assert m["configs"][-1]["name"] == m["workloads"][-1]["config"] == "phi-4-mini-flash-d5"
    assert sum(w["config"] == "phi-4-mini-flash-d5" for w in m["workloads"]) == 1


def test_correct_is_false_only_because_the_device_is_no_tpu(ran):
    _, r, _, _, _ = ran
    assert rehearsal_result(r.stderr)["correct"] is False
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}
    assert rehearsal_result(r.stderr)["attempted"] >= 1


def test_the_reference_decides_the_first_loss(ran):
    _, r, _, _, _ = ran
    checks = notes(r.stdout)["checks"]
    assert checks["reference_ran"] is True and checks["first_loss_as_reference"] is True
    held = checks["margins"]["first_loss_as_reference"]
    assert held["value"] < 0.5 * held["high"] and 5.0 < held["reference"] < 6.2


def test_the_worker_says_which_operators_it_holds_and_the_scans_chunk(ran):
    from hypha_tpu.ops.selective_scan import CHUNK

    _, _, w0, _, _ = ran
    assert re.search(
        rf"operators: window_attention=1 mamba=1 full_attention=1 gmu=1 cross_attention=1 head_dim=8 scan_chunk={CHUNK}$",
        w0, re.M)
    assert "routing:" not in w0  # a dense model: the chunked step has no counters


def test_the_device_metrics_are_left_out_on_a_cpu_and_nothing_raises(ran):
    """No device events on a CPU: the scope and roofline readers return
    nothing and raise nothing, as on a program without the scopes."""
    root, r, _, _, _ = ran
    metrics = rehearsal_result(r.stderr)["metrics"]
    assert not set(NEW) & set(metrics)
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

    model, _ = build_model({"family": "phi4flash", "preset": "tiny"})
    ids = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    text = str(jax.make_jaxpr(lambda v: model.apply(v, ids))(variables).pretty_print(name_stack=True))
    for scope in ("mamba", "selective_scan", "gmu", "diff_attention", "window_attention",
                  "full_attention", "cross_attention", "attention"):
        assert scope in text, scope
    assert "mamba/selective_scan" in text  # the scan inside the layer's scope
    assert "cross_attention/layers_4/attn/diff_attention" in text or "cross_attention" in text


def test_no_child_of_the_run_is_left_alive(ran):
    root, *_ = ran
    assert processes_under(root) == []
