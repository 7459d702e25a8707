"""The harness end to end on the CPU with an lfm2_moe cell at a tiny size (one
rank's 2 of 8 experts; conv, conv, full_attention): the family goes through
the five CLI roles as files and entries only, trains through the tied head,
its reference decides the first loss, the worker says which operators it
holds, and the routing counters come on the line afmoe's come on."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from perfbench import logs
from perfbench_helpers import (
    DATA, failing_checks, make_root, notes, processes_under, rehearsal_result, run_bench,
)


def add_lfm2_cell(root) -> None:
    bench = root / "perfbench"
    shutil.copy(DATA / "tiny-lfm2-moe.json", bench / "configs" / "tiny-lfm2-moe.json")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-lfm2-moe", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-lfm2-moe.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-lfm2-moe.h4", "config": "tiny-lfm2-moe", "traffic": "tiny.h4",
                           "chips": 1, "why": "CPU rehearsal"})
    for metric in m["per_layer"]:  # the real cell's ten, read by this one too
        if metric.get("workloads") == ["lfm2-24b-a2b-d5.steps"]:
            metric["workloads"] = ["tiny-lfm2-moe.h4"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    from test_data_driven import _digests

    root = make_root(tmp_path_factory.mktemp("lfm2"))
    before = _digests(root)
    add_lfm2_cell(root)
    r = run_bench(root, "--workload", "tiny-lfm2-moe.h4", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "1")
    w0 = (root / "chiprun_out" / "perfbench" / "tiny-lfm2-moe.h4" / "traced" / "w0.log").read_text()
    return root, r, w0, before, _digests(root)


def test_the_family_is_files_and_entries_only(ran):
    _, r, _, before, after = ran
    assert r.returncode == 3, r.stderr[-3000:]
    assert {k: after[k] for k in before} == before  # nothing that was there changed


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


def test_the_worker_says_which_operators_it_holds(ran):
    _, _, w0, _, _ = ran
    assert re.search(r"operators: conv=2 full_attention=1 head_dim=16$", w0, re.M)


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
    assert {"lfm2_moe_pairs_per_token", "lfm2_moe_load_max_over_mean"} <= set(metrics)
    assert not {"lfm2_short_conv_ms", "lfm2_short_conv_roofline", "lfm2_conv_operator_ms",
                "lfm2_flash_full_ms", "lfm2_flash_full_roofline", "lfm2_moe_experts_roofline"} & set(metrics)
    # ... and every metric all cells share, but the two a CPU has no peak and no
    # memory statistic for and the three a run this short may not reach
    # (``test_rehearsal.py`` holds the same of the dense tiny cell)
    shared = {m["name"] for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
              if "workloads" not in m}
    never, not_always = {"mfu_step", "hbm_peak_gb"}, {"lease_margin_min_s", "renew_late_max_s", "sync_cleanup_s"}
    assert shared - never - not_always <= set(metrics)
    assert not never & set(metrics)
    assert "no peak FLOP/s known for device_kind 'cpu'" in r.stderr


def test_the_scopes_of_the_new_operator_are_in_the_step(ran):
    """Device events carry the scopes on the chip; here the traced step's
    jaxpr is what can be read."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    model, _ = build_model({"family": "lfm2_moe", "preset": "tiny"})
    ids = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    text = str(jax.make_jaxpr(lambda v: model.apply(v, ids)[0])(variables).pretty_print(name_stack=True))
    for scope in ("conv_operator", "short_conv", "attention", "router", "moe_dispatch",
                  "moe_experts", "moe_combine"):
        assert scope in text, scope
    assert "conv_operator/short_conv" in text  # the core inside the operator
    assert "shared_expert" not in text and "attention_gate" not in text  # the family has neither


def test_no_child_of_the_run_is_left_alive(ran):
    root, *_ = ran
    assert processes_under(root) == []
