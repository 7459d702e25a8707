"""The plain reference a configuration brings (``perfbench/reference/``): it
makes the program's seeded weights without the program, computes the
program's first loss, and a block that is made wrong, or a sum in lower
precision, lands outside what the harness allows. And the harness's own path
from the worker's ``loss_first`` to ``first_loss_as_reference``."""

from __future__ import annotations

import dataclasses
import json
import shutil
import types

import numpy as np
import pytest

from perfbench import cluster, data, manifest, measure
from perfbench_helpers import (
    DATA as FIXTURES, REPO, failing_checks, make_root, notes, rehearsal_result, run_bench,
)

SMALL = dict(  # the reference's keys (the source's names) ...
    hidden_size=64, intermediate_size=160, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=2, vocab_size=256, rope_theta=10000.0, rms_norm_eps=1e-5,
    sliding_window=192, hidden_act="silu", tie_word_embeddings=False,
)
PROGRAM = dict(  # ... and the same block in the program's
    vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=2, num_heads=4,
    num_kv_heads=2, max_seq_len=128, rope_theta=10000.0, rms_eps=1e-5, sliding_window=192,
    tie_word_embeddings=False, dtype="float32",
)
SEED = 2147485132 % 2**31
TOLERANCE = 1e-4  # float32 against float32


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(0, 256, (2, 128)).astype(np.int32)


def program(ids, **changed):
    """The ``mistral`` family module as the worker builds and seeds it."""
    from hypha_tpu.executor import training

    spec = {"family": "mistral", "config": {**PROGRAM, **changed}, "seed": SEED,
            "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    model, params, causal_lm, _ = training._init_model(cfg, None, "/nonexistent", {"input_ids": ids})
    assert causal_lm
    return model, params


def program_loss(model, params, ids) -> float:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import make_loss_fn
    from hypha_tpu.messages import Loss

    with jax.default_matmul_precision("highest"):
        loss_fn = make_loss_fn(model.apply, Loss.CROSS_ENTROPY, causal_lm=True)
        return float(loss_fn(params, {"input_ids": jnp.asarray(ids)}, 0)[1][0])


@pytest.fixture(scope="module")
def sound(ids):
    from perfbench.reference import mistral

    model, params = program(ids)
    return model, params, mistral.first_loss(SMALL, ids, SEED)


def test_the_reference_makes_the_workers_seeded_weights_without_the_worker(sound):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import mistral

    _, params, _ = sound
    mine, spec = mistral.weights(SMALL, SEED), mistral.table(SMALL)
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) == len(mine) == len(spec) == 21
    for name, (path, _, shape, init) in spec.items():
        theirs = params["params"]
        for part in path:
            theirs = theirs[part]
        if name in ("embed", "head"):
            theirs = theirs["embed_tokens" if name == "embed" else "lm_head"]
        else:
            theirs = theirs["weight" if init == "ones" else "kernel"]
        assert theirs.shape == shape == mine[name].shape
        # Dense kernels to the bit; the two normal(0.02) tables to the last
        # place (a scale folded into the jitted draw).
        assert float(jnp.abs(mine[name] - theirs).max()) <= 1e-8, name
    assert bool(jnp.array_equal(mine["0.q"], params["params"]["layers_0"]["self_attn"]["q_proj"]["kernel"]))


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "perfbench" / "reference" / "mistral.py").read_text()
    code = [x for x in src.split('"""', 2)[2].splitlines() if x.lstrip().startswith(("import ", "from "))]
    assert code and not any("hypha" in x or "flax" in x or "perfbench" in x for x in code), code


def test_the_loss_is_the_programs_to_a_ten_thousandth_in_float32(sound, ids):
    model, params, ref = sound
    assert abs(program_loss(model, params, ids) - ref) < TOLERANCE
    assert 5.0 < ref < 6.2  # ln 256 and the head's spread


WRONG_BLOCKS = {
    "window_shorter_than_the_sequence": dict(sliding_window=32),
    "another_rope_theta": dict(rope_theta=500000.0),
    "no_window_at_all": dict(sliding_window=None),
}


@pytest.mark.parametrize("fault", sorted(WRONG_BLOCKS))
def test_a_block_made_wrong_leaves_the_reference_by_more_than_the_tolerance(ids, fault):
    from perfbench.reference import mistral

    long = np.concatenate([ids, ids[:, ::-1]], axis=1)  # 256 positions: the window of 192 cuts
    ref = mistral.first_loss(SMALL, long, SEED)
    model, params = program(long, max_seq_len=256, **WRONG_BLOCKS[fault])
    sound_model, sound_params = program(long, max_seq_len=256)
    assert abs(program_loss(sound_model, sound_params, long) - ref) < TOLERANCE
    assert abs(program_loss(model, params, long) - ref) > 3 * TOLERANCE


def test_a_zeroed_head_gives_ln_vocabulary_and_not_the_references_loss(sound, ids):
    import jax

    model, params, ref = sound
    zeroed = jax.tree_util.tree_map(lambda x: x, params)
    zeroed["params"]["lm_head"] = zeroed["params"]["lm_head"] * 0
    loss = program_loss(model, zeroed, ids)
    assert loss == pytest.approx(np.log(256), abs=1e-5) and abs(loss - ref) > 30 * TOLERANCE


def test_a_dropped_swiglu_gate_leaves_the_reference(sound, ids, monkeypatch):
    import jax.numpy as jnp

    from hypha_tpu.models import llama

    _, params, ref = sound
    monkeypatch.setattr(llama.nn, "silu", lambda x: jnp.ones_like(x))  # act * up is up alone
    model, _ = program(ids)
    assert abs(program_loss(model, params, ids) - ref) > 3 * TOLERANCE


@pytest.mark.parametrize("operands,least", [("bfloat16", 1e-6), ("float8_e4m3fn", 2e-4)])
def test_the_control_in_lower_precision_is_further_off_than_the_program(sound, ids, operands, least):
    """The control of PERF.md 2 at a size a test can hold: the reference with
    every product's operands rounded to the type below. On the chip at the
    cell's size the float8 control reads several tolerances off (PERF.md 6)."""
    from perfbench.reference import mistral

    _, _, ref = sound
    gap = abs(mistral.first_loss(SMALL, ids, SEED, operands) - ref)
    assert gap > least
    if operands == "float8_e4m3fn":
        assert gap > 3 * abs(mistral.first_loss(SMALL, ids, SEED, "bfloat16") - ref)


# ---- which rows the worker's first step consumes


def test_the_first_batch_is_the_first_rows_of_slice_0(tmp_path):
    from safetensors.numpy import load_file

    traffic = json.loads((FIXTURES / "tiny.h4.json").read_text())
    n = data.write_dataset(tmp_path, traffic, 2147485132)
    assert n == 256 and len(list(tmp_path.iterdir())) == 4
    first = load_file(str(tmp_path / "slice_0000.safetensors"))["input_ids"]
    rows = data.first_batch(traffic, 2147485132)
    assert rows.shape == (2, 1024) and np.array_equal(rows, first[:2])
    assert not np.array_equal(rows, data.first_batch(traffic, 2147485133))
    assert ((rows[:, 1:] - rows[:, :-1]) % 256 == 1).all()  # counting sequences


def test_the_scheduler_hands_a_jobs_first_request_slice_0_and_the_worker_reads_rows_in_order(tmp_path):
    from hypha_tpu.executor.dataset import stream_batches
    from hypha_tpu.scheduler.trackers import SliceTracker

    assert SliceTracker(4).next("w0") == 0
    traffic = json.loads((FIXTURES / "tiny.h4.json").read_text())
    data.write_dataset(tmp_path, traffic, 11)
    paths = iter(sorted(str(p) for p in tmp_path.iterdir()))
    batch = next(stream_batches(lambda: next(paths), traffic["batch"]))
    assert np.array_equal(batch["input_ids"], data.first_batch(traffic, 11))


def test_the_model_seed_is_the_one_the_job_is_given():
    cell = manifest.resolve("mistral-7b-d1.steps", REPO)
    sets = data.job_sets(cell.config, cell.traffic, 2147485132)
    assert f"job.model_seed={data.model_seed(2147485132)}" in sets
    assert data.model_seed(2147485132) == 2147485132 - 2**31


# ---- the harness's path: from the log line to ``first_loss_as_reference``


def tiny_run(cell, loss_first: float, reference: dict | None) -> cluster.Run:
    run = cluster.Run(t_start=0.0, t_wall=0.0, out_dir=FIXTURES, trace=False)
    line = ("2026-09-27 01:00:{sec:02d},000 hypha.executor.training INFO round {n} done: batch=2 "
            "steps=4 tokens=8192 wall_s=1.0 first_step_s={fs} median_step_s=0.05 "
            "loss_first={lf:.4f} loss_last=1.0 loss_mean={lm} nonfinite=0 peak_bytes=None")
    w0 = "\n".join([
        "2026-09-27 01:00:00,000 hypha.executor.training INFO device: platform=tpu kind='TPU v5 lite' count=1",
        "x attention path: pallas flash kernel, compiled (backend=tpu)",
        line.format(sec=10, n=0, fs=2.0, lf=loss_first, lm=3.0),
        line.format(sec=11, n=1, fs=0.06, lf=0.2, lm=0.5),
    ])
    ps = "\n".join(
        f"ps j: round {n} delta 1/1 (from w0)\nps outer step: round={n} wall_s=0.1 "
        "native_kernels=True native_cbor=True" for n in (0, 1))
    run.holders, run.reference = ["w0"], reference
    measure.from_logs(run, {"w0": w0, "ps": ps}, cell.traffic, 20.0)
    return run


@pytest.fixture(scope="module")
def tiny_cell():
    base = manifest.resolve("mistral-7b-d1.steps", REPO)
    return dataclasses.replace(
        base, name="tiny-mistral.h4",
        config=json.loads((FIXTURES / "tiny-mistral.json").read_text()),
        traffic=manifest.load_traffic(FIXTURES / "tiny.h4.json"),
    )


@pytest.mark.parametrize("logged,reference,failing", [
    (5.5458, {"loss": 5.545801}, set()),
    (5.5458, {"loss": 5.5358}, set()),  # half the tolerance off: still inside
    (5.6458, {"loss": 5.545801}, {"first_loss_as_reference"}),  # the log line edited by 0.1
    (5.4458, {"loss": 5.545801}, {"first_loss_as_reference"}),
    (5.5458, {"error": "the reference did not end within 90 s"}, {"reference_ran"}),
    (5.5458, {"skipped": "no work for a CPU"}, {"reference_ran"}),
    (5.5458, {"loss": float("nan")}, {"reference_ran"}),
    (5.5458, None, {"reference_ran"}),
], ids=["agrees", "inside", "log_0.1_up", "log_0.1_down", "timed_out", "skipped", "nan", "never_started"])
def test_the_run_is_held_to_the_reference_and_a_reference_that_did_not_run_fails_by_its_own_name(
        tiny_cell, logged, reference, failing):
    run = tiny_run(tiny_cell, logged, reference)
    result = measure.result(run, tiny_cell, trace=False)
    assert {k for k, ok in run.checks.items() if not ok} == failing
    assert result["correct"] is (not failing)
    assert "first_loss_near_ln_vocabulary" not in run.checks  # the reference replaces the band
    assert ("first_loss_as_reference" in run.checks) == ("reference_ran" not in failing)
    if "reference_ran" not in failing:
        held = run.margins["first_loss_as_reference"]
        assert held["value"] == pytest.approx(abs(logged - reference["loss"]))
        assert (held["program"], held["reference"], held["high"]) == (logged, reference["loss"], 0.02)


def test_a_configuration_without_a_reference_is_judged_by_the_band_as_before(tiny_cell):
    config = json.loads((FIXTURES / "tiny-gpt2.json").read_text())
    cell = dataclasses.replace(tiny_cell, config=config)
    for logged, ok in ((5.55, True), (6.2, False)):
        run = tiny_run(cell, logged, None)
        measure.result(run, cell, trace=False)
        assert run.checks["first_loss_near_ln_vocabulary"] is ok
        assert not {"reference_ran", "first_loss_as_reference"} & set(run.checks)
        assert run.margins["first_loss_near_ln_vocabulary"]["value"] == logged


def test_every_margin_is_inside_exactly_where_its_check_is_true(tiny_cell):
    for logged, reference in ((5.5458, {"loss": 5.5458}), (5.9, {"loss": 5.5458})):
        run = tiny_run(tiny_cell, logged, reference)
        measure.result(run, tiny_cell, trace=False)
        assert set(run.margins) == {"no_recompile_in_window", "first_loss_as_reference", "loss_fell"}
        for name, held in run.margins.items():
            assert measure.inside(held) is run.checks[name], name


def test_the_reference_runs_in_a_process_of_its_own_and_never_raises(bench_root):
    add_reference_cell(bench_root)
    cell = manifest.resolve("tiny-mistral.h2", bench_root)
    out = cluster.run_reference(bench_root, cell, 2147485132, 120.0)
    assert out["platform"] == "cpu" and (out["rows"], out["sequence"]) == (2, 1024)
    assert 5.3 < out["loss"] < 5.8 and out["seconds"] > 0
    assert "error" in cluster.run_reference(bench_root, cell, 1, 3.0)  # no time left: not started
    broken = dataclasses.replace(cell, name="no-such.cell")
    assert "error" in cluster.run_reference(bench_root, broken, 1, 60.0)


def add_reference_cell(root) -> None:
    """What a later ``model_config`` PR adds: a reference module, a
    configuration that names it, a mix with the share, and three entries."""
    bench = root / "perfbench"
    (bench / "reference" / "other.py").write_text(
        "from .mistral import first_loss  # a later family's own file\n")
    config = json.loads((FIXTURES / "tiny-mistral.json").read_text())
    config["checks"]["reference"] = "other"
    (bench / "configs" / "tiny-mistral.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "tiny.h4.json").read_text())
    mix["inner_steps"] = 2
    mix["checks"] = {"loss_first_after_outer_step_share": 1.5}
    (bench / "traffic" / "tiny.h2.json").write_text(json.dumps(mix))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-mistral", "source": "test only", "reduced": [],
                         "file": "perfbench/configs/tiny-mistral.json", "why": "a later PR's"})
    m["workloads"].append({"name": "tiny-mistral.h2", "config": "tiny-mistral",
                           "traffic": "tiny.h2", "chips": 1, "why": "a later PR's"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))


def test_a_configuration_with_a_reference_is_files_and_entries_only(tmp_path):
    from test_data_driven import _digests

    root = make_root(tmp_path)
    before = _digests(root)
    add_reference_cell(root)
    r = run_bench(root, "--workload", "tiny-mistral.h2", "--seed", "2147485132",
                  "--seconds", "15", "--trace", "0")
    assert r.returncode == 3, r.stderr[-3000:]
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}
    checks = notes(r.stdout)["checks"]
    assert checks["reference_ran"] is True and checks["first_loss_as_reference"] is True
    assert "first_loss_near_ln_vocabulary" not in checks
    held = checks["margins"]["first_loss_as_reference"]
    assert held["value"] < 0.5 * held["high"] and held["reference"] == notes(r.stdout)["reference"]["loss"]
    assert checks["margins"]["loss_stays_down_after_outer_step"]["share"] == 1.5
    assert "perfbench: compared: first_loss_as_reference: " in r.stderr
    assert rehearsal_result(r.stderr)["attempted"] >= 1
    after = _digests(root)
    assert {k: after[k] for k in before} == before  # nothing that was there changed
    shutil.rmtree(root)
