"""The plain reference of the phi4flash configurations
(``perfbench/reference/phi4flash.py``) against the program at a small size on
the CPU in float32: the seeded weights to an ulp, the logits, the loss and
the gradient of every leaf; the tied head's two uses; and each of seven faults
planted in a copy of the reference leaves the program by more than the
tolerance."""

from __future__ import annotations

import json
import math
import sys
import types

import numpy as np
import pytest

from perfbench_helpers import REPO

SMALL = dict(  # the reference's keys (the source's names): source layers 3 to 7 of 8 ...
    hidden_size=32, intermediate_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, sliding_window=8, mb_per_layer=2, layer_norm_eps=1e-5, num_hidden_layers=5,
    layers_run=[3, 4, 5, 6, 7], source_values={"num_hidden_layers": 8}, vocab_size=256,
    mamba={"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 2},
)
PROGRAM = dict(  # ... and the same stack in the program's
    vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=8, num_heads=4,
    num_kv_heads=2, head_dim=8, sliding_window=8, d_state=4, d_conv=4, expand=2, dt_rank=2,
    layers_run=[3, 4, 5, 6, 7], dtype="float32",
)
KINDS = ["window_attention", "mamba", "full_attention", "gmu", "cross_attention"]
SEED = 2147485132 % 2**31
TOLERANCE = 1e-5  # float32 against float32: the sound difference is 1e-7 here


def program(ids):
    """The ``phi4flash`` family module as the worker builds and seeds it."""
    from hypha_tpu.executor import training

    spec = {"family": "phi4flash", "config": PROGRAM, "seed": SEED, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    model, variables, causal_lm, _ = training._init_model(
        cfg, None, "/nonexistent", {"input_ids": ids})
    assert causal_lm and list(variables) == ["params"]
    return model, variables


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(0, 256, (2, 96)).astype(np.int32)


@pytest.fixture(scope="module")
def worker(ids):
    return program(ids)


def program_loss_and_grads(model, variables, ids):
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce

    body = model.clone(with_head=False)

    def loss(params):
        hidden = body.apply({"params": params}, jnp.asarray(ids))
        return chunked_causal_ce(hidden[:, :-1], params[model.head_leaf], jnp.asarray(ids)[:, 1:], chunk=32)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(variables["params"])


def reference_loss_and_grads(module, w, ids, config):
    import jax
    import jax.numpy as jnp

    def loss(w):
        total = sum(module.sequence_nll(w, jnp.asarray(row), config) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(w)


def at(tree, path):
    for part in path:
        tree = tree[part]
    return tree


def test_the_reference_gives_each_layer_its_kind_by_the_sources_rule():
    from perfbench.reference import phi4flash

    assert [k for _, k in phi4flash.layers(SMALL)] == KINDS
    published = {"num_hidden_layers": 32, "mb_per_layer": 2}
    kinds = [phi4flash.kind(published, i) for i in range(32)]
    assert kinds[:16] == ["mamba", "window_attention"] * 8
    assert kinds[16:18] == ["mamba", "full_attention"]
    assert kinds[18:] == ["gmu", "cross_attention"] * 7


def test_the_reference_makes_the_workers_seeded_weights_without_the_worker(worker):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import phi4flash

    _, variables = worker
    mine, spec = phi4flash.weights(SMALL, SEED), phi4flash.table(SMALL)
    # embedding, final norm (2); per layer two norms (4) and an MLP (2); window and
    # full attention 9 each, cross 9 (one projection), Mamba 9, GMU 2
    assert len(jax.tree_util.tree_leaves(variables["params"])) == len(spec) == 3 + 5 * 6 + 3 * 9 + 9 + 2
    for name, (path, _, shape, _) in spec.items():
        theirs = at(variables["params"], path)
        assert theirs.shape == shape == mine[name].shape, name
        np.testing.assert_allclose(mine[name], theirs, rtol=2e-7, atol=1e-8, err_msg=name)  # an ulp: made under jit here
    assert "lm_head" not in variables["params"]  # tied: the embedding is the head
    # Mamba-1's published initialisers: a step in [0.001, 0.1], A = -(1 ... d_state), D ones
    step = np.log1p(np.exp(np.asarray(mine["1.dt.b"])))
    assert 0.001 <= step.min() < 0.01 < step.max() <= 0.1 and step.shape == (64,)
    np.testing.assert_allclose(np.exp(np.asarray(mine["1.a_log"])), np.tile(np.arange(1.0, 5.0), (64, 1)), rtol=1e-6)
    assert float(mine["1.d"].min()) == 1.0 and 0.05 < float(mine["0.lambda_q1"].std()) < 0.2


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "perfbench" / "reference" / "phi4flash.py").read_text()
    code = [x for x in src.split('"""', 2)[2].splitlines() if x.lstrip().startswith(("import ", "from "))]
    assert code and not any("hypha" in x or "flax" in x or "perfbench" in x for x in code), code


def test_logits_loss_and_every_leafs_gradient_are_the_programs(worker, ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import phi4flash

    model, variables = worker
    w = phi4flash.weights(SMALL, SEED)
    with jax.default_matmul_precision("highest"):
        logits = model.apply(variables, jnp.asarray(ids))
        theirs = phi4flash.hidden(w, jnp.asarray(ids[0]), SMALL) @ w["embed"].T
    np.testing.assert_allclose(logits[0], theirs, atol=2e-5)
    loss, grads = program_loss_and_grads(model, variables, ids)
    ref, ref_grads = reference_loss_and_grads(phi4flash, w, ids, SMALL)
    assert abs(float(loss) - float(ref)) < TOLERANCE
    assert 5.0 < float(ref) < 6.5  # ln 256 and the head's spread
    for name, (path, *_rest) in phi4flash.table(SMALL).items():
        scale = float(jnp.abs(ref_grads[name]).max())
        off = float(jnp.abs(at(grads, path) - ref_grads[name]).max())
        assert scale > 0 and off < 2e-3 * scale + 1e-7, (name, off, scale)


def test_the_embeddings_gradient_is_the_sum_of_its_two_uses(worker, ids):
    """Tied head: as the table that is looked up and as the matrix the logits
    are taken against. Each use alone, by a copy held out of the gradient."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce

    model, variables = worker
    body, tokens = model.clone(with_head=False), jnp.asarray(ids)

    def loss(lookup, head):
        hidden = body.apply({"params": {**variables["params"], "embed_tokens": lookup}}, tokens)
        return chunked_causal_ce(hidden[:, :-1], head, tokens[:, 1:], chunk=32)

    e = variables["params"]["embed_tokens"]
    with jax.default_matmul_precision("highest"):
        as_lookup, as_head = jax.grad(loss, (0, 1))(e, e)
    _, grads = program_loss_and_grads(model, variables, ids)
    assert float(jnp.abs(as_lookup).max()) > 0 and float(jnp.abs(as_head).max()) > 0
    np.testing.assert_allclose(grads["embed_tokens"], as_lookup + as_head, atol=1e-7)


# fault -> (the reference's own line, the line a copy is given instead)
FAULTS = {
    "the_export_taken_after_the_gate": (
        'return mm(y * jax.nn.silu(z), w[f"{j}.out"]), y',
        'return mm(y * jax.nn.silu(z), w[f"{j}.out"]), y * jax.nn.silu(z)'),
    "the_cross_layer_attends_to_its_own_inputs_keys": (
        "        k, v = kv\n",
        '        k, v = (t.reshape(s, kv_heads, hd) for t in jnp.split(\n'
        '            mm(u, w[f"{j - 2}.qkv"]) + w[f"{j - 2}.qkv.b"], [heads * hd, (heads + kv_heads) * hd], axis=-1)[1:])\n'),
    "lambda_init_from_the_cuts_index": (
        "init = 0.8 - 0.6 * math.exp(-0.3 * source)", "init = 0.8 - 0.6 * math.exp(-0.3 * j)"),
    "the_one_minus_lambda_init_factor_dropped": (
        'o = (o * w[f"{j}.subln"] * (1.0 - init))', 'o = (o * w[f"{j}.subln"])'),
    "the_window_off_by_one": (
        'keep &= kpos[None, :] > qpos - c["sliding_window"]',
        'keep &= kpos[None, :] >= qpos - c["sliding_window"]'),
    "the_D_term_dropped": ('y = y + w[f"{j}.d"] * x', "y = y"),
    "softplus_dropped_from_the_step": (
        'dt = jax.nn.softplus(mm(delta, w[f"{j}.dt"]) + w[f"{j}.dt.b"])',
        'dt = mm(delta, w[f"{j}.dt"]) + w[f"{j}.dt.b"]'),
}


def a_copy_with(fault: str):
    """The reference's source with one line made wrong, as a module of its own."""
    src = (REPO / "perfbench" / "reference" / "phi4flash.py").read_text()
    right, wrong = FAULTS[fault]
    assert src.count(right) == 1, f"the reference no longer has the line {right!r}"
    module = types.ModuleType(f"phi4flash_with_{fault}")
    sys.modules[module.__name__] = module  # dataclasses and jit look a module up by name
    exec(compile(src.replace(right, wrong), module.__name__, "exec"), module.__dict__)
    return module


@pytest.fixture(scope="module")
def sound(worker, ids):
    from perfbench.reference import phi4flash

    ref = phi4flash.first_loss(SMALL, ids, SEED)
    loss, _ = program_loss_and_grads(*worker, ids)
    assert abs(float(loss) - ref) < TOLERANCE
    return float(loss)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_planted_in_a_copy_leaves_the_program_by_more_than_the_tolerance(fault, ids, sound):
    wrong = a_copy_with(fault).first_loss(SMALL, ids, SEED)
    assert not abs(wrong - sound) <= 3 * TOLERANCE, (fault, wrong, sound)  # nan leaves it too


def test_products_in_float8_leave_the_reference_by_more_than_float32_noise(ids, sound):
    from perfbench.reference import phi4flash

    low = phi4flash.first_loss(SMALL, ids, SEED, operands="float8_e4m3fn")
    assert abs(low - sound) > 10 * TOLERANCE


CATALOG_ROW = dict(  # the catalog row's config (architectures.jsonl, Phi-4-mini-flash-reasoning)
    embd_pdrop=0, hidden_act="silu", hidden_size=2560, intermediate_size=10240, layer_norm_eps=1e-5,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash", num_attention_heads=40,
    num_hidden_layers=32, num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, vocab_size=200064,
)


def test_the_cells_configuration_is_the_catalog_rows_but_for_what_reduced_names():
    from perfbench.reference import phi4flash

    config = json.loads((REPO / "perfbench" / "configs" / "phi-4-mini-flash-d5.json").read_text())
    entry = next(c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]
                 if c["name"] == "phi-4-mini-flash-d5")
    reduced = ["num_hidden_layers", "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == list(config["source_values"]) == list(
        config["reduced_why"]) == reduced
    assert entry["source"] == config["source"]
    assert {k: config[k] for k in CATALOG_ROW if k not in reduced} == {
        k: v for k, v in CATALOG_ROW.items() if k not in reduced}
    assert config["source_values"] == {k: CATALOG_ROW[k] for k in reduced}
    assert config["layers_run"] == [15, 16, 17, 18, 19] and config["num_hidden_layers"] == 5
    assert [k for _, k in phi4flash.layers(config)] == KINDS
    assert config["head_dim"] == 2560 // 40
    assert config["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": math.ceil(2560 / 16)}
    assert set(config["assumed"]) >= {
        "head_dim", "d_state", "d_conv", "expand", "dt_rank", "biases", "hinge", "head_pairing",
        "initializers", "precision"}
    assert config["vocab_size"] * config["share"]["chips_sharing_embedding_and_head"] == 200064
    assert len(config["stands_for"]) > 100 and config["checks"]["reference"] == "phi4flash"
    # the job keys say what the file says
    sets = dict(s.removeprefix("job.model_config.").split("=", 1) for s in config["job_sets"][1:])
    assert config["job_sets"][0] == "job.model_family=phi4flash"
    assert json.loads(sets["layers_run"]) == config["layers_run"]
    assert int(sets["num_layers"]) == config["source_values"]["num_hidden_layers"]
    for key, source in (("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"),
                        ("intermediate_size", "intermediate_size"), ("num_heads", "num_attention_heads"),
                        ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
                        ("sliding_window", "sliding_window"), ("mb_per_layer", "mb_per_layer")):
        assert int(sets[key]) == config[source], key
    assert {k: int(sets[k]) for k in config["mamba"]} == config["mamba"]
    assert float(sets["layer_norm_eps"]) == config["layer_norm_eps"]
    traffic = json.loads((REPO / "perfbench" / "traffic" / "phi-4-mini-flash-d5.steps.json").read_text())
    assert traffic["data"]["modulus"] <= config["vocab_size"]
    assert int(sets["max_seq_len"]) == config["max_position_embeddings"] >= traffic["sequence"]
    assert traffic["data"]["sequences"] >= 2 * 3 * traffic["inner_steps"] * traffic["batch"]
    assert traffic["inner_steps"] % 8 == 0
