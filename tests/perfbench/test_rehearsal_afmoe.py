"""The harness end to end on the CPU with an afmoe cell at a tiny size (one
rank's 2 of 8 experts): the family goes through the five CLI roles as files
and entries only, its reference decides the first loss, and the routing
counters come on a line of their own."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from perfbench import logs
from perfbench_helpers import (
    DATA, failing_checks, make_root, notes, processes_under, rehearsal_result, run_bench,
)

ROUND_DONE_FIELDS = [  # the line a Mistral run logs, field for field, as before PR 29
    "batch", "steps", "tokens", "wall_s", "first_step_s", "median_step_s", "loss_first",
    "loss_last", "loss_mean", "nonfinite", "peak_bytes", "steps_sum_s", "max_step_s",
    "status_s", "input_wait_s",
]


def add_afmoe_cell(root) -> None:
    bench = root / "perfbench"
    shutil.copy(DATA / "tiny-afmoe.json", bench / "configs" / "tiny-afmoe.json")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-afmoe", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-afmoe.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-afmoe.h4", "config": "tiny-afmoe", "traffic": "tiny.h4",
                           "chips": 1, "why": "CPU rehearsal"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    from test_data_driven import _digests

    root = make_root(tmp_path_factory.mktemp("afmoe"))
    before = _digests(root)
    add_afmoe_cell(root)
    r = run_bench(root, "--workload", "tiny-afmoe.h4", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "1")
    w0 = (root / "chiprun_out" / "perfbench" / "tiny-afmoe.h4" / "traced" / "w0.log").read_text()
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


def test_nothing_is_dropped_and_the_counters_have_a_line_of_their_own(ran):
    _, _, w0, _, _ = ran
    rows = [logs.parse_fields(m.group(0)) for m in re.finditer(r"round \d+ routing: .*", w0)]
    assert len(rows) >= 2
    for row in rows:
        assert row["pairs_computed"] == row["pairs_routed"] > 0
        assert (row["steps"], row["expert_layers"], row["experts_held"]) == (4, 2, 2)
        # 2 choices x 2 held / 8 routed: half a pair a token on a fresh router
        assert 0.2 < row["pairs_per_token"] < 1.0 and row["load_max_over_mean"] >= 1.0
        assert row["tokens_elsewhere"] < 4 * 2 * 2048


def test_the_round_done_line_keeps_its_fields_in_their_order(ran):
    _, _, w0, _, _ = ran
    line = re.search(r"round 0 done: (.*)", w0).group(1)
    assert [kv.split("=")[0] for kv in line.split()] == ROUND_DONE_FIELDS


def test_the_scopes_of_the_routed_layer_are_in_the_step(ran):
    """Device events carry the scopes on the chip; here the traced step's
    jaxpr is what can be read."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.models import build_model

    model, _ = build_model({"family": "afmoe", "preset": "tiny"})
    ids = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    text = str(jax.make_jaxpr(lambda v: model.apply(v, ids)[0])(variables).pretty_print(name_stack=True))
    for scope in ("router", "moe_dispatch", "moe_experts", "moe_combine", "shared_expert",
                  "attention_gate"):
        assert scope in text, scope


def test_no_child_of_the_run_is_left_alive(ran):
    root, *_ = ran
    assert processes_under(root) == []
