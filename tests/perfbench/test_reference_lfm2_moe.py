"""The plain reference of the lfm2_moe configurations
(``perfbench/reference/lfm2_moe.py``) against the program at a small size on
the CPU: the seeded weights bit for bit, the logits, the loss and every
gradient for the whole model and for one rank's share, with a selection bias
that is not zero; the tied head's two uses; the shares add up in the reference
too; and a layer made wrong leaves it."""

from __future__ import annotations

import json
import types

import numpy as np
import pytest

from perfbench_helpers import REPO

KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
SMALL = dict(  # the reference's keys (the source's names) ...
    hidden_size=64, intermediate_size=160, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, conv_L_cache=3, conv_bias=False, num_hidden_layers=5,
    num_dense_layers=1, layers_run=[0, 3, 4, 5, 6], layer_types=KINDS, vocab_size=256,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, norm_eps=1e-5,
    num_experts=16, num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True,
)
PROGRAM = dict(  # ... and the same stack in the program's
    vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
    num_layers=5, num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
    experts_per_token=4, layer_types=["conv", "conv", "conv", "conv", "full_attention"],
    dtype="float32", moe_chunk=64,
)
SHARE = dict(held=4, offset=8)
EXPERT_LAYERS = (1, 2, 3, 4)
SEED = 2147485132 % 2**31
TOLERANCE = 1e-4  # float32 against float32


def reference_config(share: bool) -> dict:
    if not share:
        return SMALL
    return {**SMALL, "num_experts": SHARE["held"],
            "share": {"experts_routed": 16, "expert_offset": SHARE["offset"]}}


def program(ids, share: bool, **changed):
    """The ``lfm2_moe`` family module as the worker builds and seeds it."""
    from hypha_tpu.executor import training

    config = {**PROGRAM, **changed}
    if share:
        config.update(experts_held=SHARE["held"], expert_offset=SHARE["offset"])
    spec = {"family": "lfm2_moe", "config": config, "seed": SEED, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    model, variables, causal_lm, _ = training._init_model(
        cfg, None, "/nonexistent", {"input_ids": ids})
    assert causal_lm
    return model, variables


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(0, 256, (2, 96)).astype(np.int32)


def some_bias(variables, reference_weights, spread=0.05):
    """A selection bias that is not zero, the same on both sides."""
    import jax.numpy as jnp

    from hypha_tpu.models.routed import STATE

    rng = np.random.default_rng(17)
    state = {}
    for layer in sorted(variables[STATE], key=lambda n: int(n.split("_")[1])):
        b = jnp.asarray(rng.normal(0, spread, 16), jnp.float32)
        state[layer] = {"feed_forward": {"expert_bias": b}}
        reference_weights[f"{layer.split('_')[1]}.bias"] = b
    return {"params": variables["params"], STATE: state}


def program_loss_and_grads(model, variables, ids):
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce
    from hypha_tpu.models.routed import STATE

    body = model.clone(with_head=False)

    def loss(params):
        hidden, _ = body.apply({"params": params, STATE: variables[STATE]}, jnp.asarray(ids))
        return chunked_causal_ce(hidden[:, :-1], params[model.head_leaf], jnp.asarray(ids)[:, 1:], chunk=32)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(variables["params"])


def reference_loss_and_grads(w, ids, config):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    def loss(w):
        total = sum(lfm2_moe.sequence_nll(w, jnp.asarray(row), config) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(w)


def at(tree, path):
    for part in path:
        tree = tree[part]
    return tree


@pytest.mark.parametrize("share", [False, True], ids=["whole", "one_rank_of_four"])
def test_the_reference_makes_the_workers_seeded_weights_without_the_worker(ids, share):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    config = reference_config(share)
    _, variables = program(ids, share)
    mine, spec = lfm2_moe.weights(config, SEED), lfm2_moe.table(config)
    # embedding, final norm; 4 conv layers of 5 + ffn, one attention layer of 8 + ffn
    assert len(jax.tree_util.tree_leaves(variables["params"])) == len(spec) == 2 + 8 + 3 * 9 + 12
    for name, (path, _, shape, _) in spec.items():
        theirs = at(variables["params"], path)
        assert theirs.shape == shape == mine[name].shape, name
        assert float(jnp.abs(mine[name] - theirs).max()) <= 1e-8, name
    assert bool(jnp.array_equal(
        mine["2.experts_up"], variables["params"]["layers_2"]["feed_forward"]["experts_up"]))
    assert bool(jnp.array_equal(mine["1.taps"], variables["params"]["layers_1"]["conv"]["conv_weight"]))
    assert "lm_head" not in variables["params"]  # tied: the embedding is the head
    assert float(jnp.abs(mine["1.taps"]).max()) > 0.1  # drawn, not ones
    assert all(float(jnp.abs(mine[f"{i}.bias"]).max()) == 0.0 for i in EXPERT_LAYERS)


def test_the_reference_imports_nothing_of_the_program():
    src = (REPO / "perfbench" / "reference" / "lfm2_moe.py").read_text()
    code = [x for x in src.split('"""', 2)[2].splitlines() if x.lstrip().startswith(("import ", "from "))]
    assert code and not any("hypha" in x or "flax" in x or "perfbench" in x for x in code), code


@pytest.mark.parametrize("share", [False, True], ids=["whole", "one_rank_of_four"])
def test_logits_loss_and_every_gradient_are_the_programs_with_a_bias_that_is_not_zero(ids, share):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    config = reference_config(share)
    model, variables = program(ids, share)
    w = lfm2_moe.weights(config, SEED)
    variables = some_bias(variables, w)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(variables, jnp.asarray(ids))
        theirs = lfm2_moe.hidden(w, jnp.asarray(ids[0]), config) @ w["embed"].T
    np.testing.assert_allclose(logits[0], theirs, atol=2e-5)
    loss, grads = program_loss_and_grads(model, variables, ids)
    ref, ref_grads = reference_loss_and_grads(w, ids, config)
    assert abs(float(loss) - float(ref)) < TOLERANCE
    assert 5.0 < float(ref) < 6.5  # ln 256 and the head's spread
    for name, (path, *_rest) in lfm2_moe.table(config).items():
        scale = float(jnp.abs(ref_grads[name]).max())
        off = float(jnp.abs(at(grads, path) - ref_grads[name]).max())
        assert scale > 0 and off < 2e-3 * scale + 1e-7, name
    assert all(float(jnp.abs(ref_grads[f"{i}.bias"]).max()) == 0.0 for i in EXPERT_LAYERS)  # a choice has no gradient


def test_the_embeddings_gradient_is_the_sum_of_its_two_uses(ids):
    """Tied head: as the table that is looked up and as the matrix the logits
    are taken against. Each use alone, by a copy held out of the gradient."""
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.train import chunked_causal_ce
    from hypha_tpu.models.routed import STATE

    model, variables = program(ids, False)
    body, tokens = model.clone(with_head=False), jnp.asarray(ids)

    def loss(lookup, head):
        params = {**variables["params"], "embed_tokens": lookup}
        hidden, _ = body.apply({"params": params, STATE: variables[STATE]}, tokens)
        return chunked_causal_ce(hidden[:, :-1], head, tokens[:, 1:], chunk=32)

    e = variables["params"]["embed_tokens"]
    with jax.default_matmul_precision("highest"):
        as_lookup, as_head = jax.grad(loss, (0, 1))(e, e)
    _, grads = program_loss_and_grads(model, variables, ids)
    assert float(jnp.abs(as_lookup).max()) > 0 and float(jnp.abs(as_head).max()) > 0
    np.testing.assert_allclose(grads["embed_tokens"], as_lookup + as_head, atol=1e-7)


def test_in_the_reference_too_the_shares_add_up_to_the_uncut_layer(ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    w = lfm2_moe.weights(SMALL, SEED)
    w["2.bias"] = jnp.asarray(np.random.default_rng(5).normal(0, 0.05, 16), jnp.float32)
    m = jax.random.normal(jax.random.key(1), (96, 64))
    _, mm = lfm2_moe._matmul(None)
    uncut = lfm2_moe.experts_part(w, 2, m, SMALL, mm)
    total = jnp.zeros_like(uncut)
    for rank in range(4):
        part = {**SMALL, "num_experts": 4, "share": {"experts_routed": 16, "expert_offset": 4 * rank}}
        mine = dict(w)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[f"2.{name}"] = w[f"2.{name}"][4 * rank: 4 * rank + 4]
        total += lfm2_moe.experts_part(mine, 2, m, part, mm)
    np.testing.assert_allclose(total, uncut, atol=2e-5)  # no shared expert to count once
    assert float(jnp.abs(uncut).max()) > 0.1


def _short_conv_with(change):
    from hypha_tpu.ops.short_conv import short_conv

    return lambda b, c, x, taps: short_conv(*change(b, c, x, taps))


def _ones(a):
    return a * 0 + 1


# fault -> what the program is given: job keys, or another core of the operator
WRONG_KEYS = {
    "rope_theta_ten_thousand": dict(rope_theta=10000.0),
    "the_renormalisation_dropped": dict(route_norm=False),
}
WRONG_CORES = {
    "gate_B_dropped": lambda b, c, x, taps: (_ones(b), c, x, taps),
    "gate_C_dropped": lambda b, c, x, taps: (b, _ones(c), x, taps),
    "taps_reversed": lambda b, c, x, taps: (b, c, x, taps[::-1]),
    "two_taps": lambda b, c, x, taps: (b, c, x, taps.at[0].set(0.0)),
}


@pytest.fixture(scope="module")
def sound_reference(ids):
    from perfbench.reference import lfm2_moe

    ref = lfm2_moe.first_loss(SMALL, ids, SEED)
    loss, _ = program_loss_and_grads(*program(ids, False), ids)
    assert abs(float(loss) - ref) < TOLERANCE
    return ref


@pytest.mark.parametrize("fault", sorted(WRONG_KEYS) + sorted(WRONG_CORES) + ["qk_norm_dropped"])
def test_a_layer_made_wrong_leaves_the_reference_by_more_than_the_tolerance(
        ids, fault, sound_reference, monkeypatch):
    from hypha_tpu.models import lfm2_moe as family

    ref = sound_reference
    if fault in WRONG_CORES:
        monkeypatch.setattr(family, "short_conv", _short_conv_with(WRONG_CORES[fault]))
    elif fault == "qk_norm_dropped":
        monkeypatch.setattr(family, "rms_norm", lambda x, w, eps: x)  # the per-head norms alone use it
    loss, _ = program_loss_and_grads(*program(ids, False, **WRONG_KEYS.get(fault, {})), ids)
    assert abs(float(loss) - ref) > 3 * TOLERANCE, (fault, float(loss), ref)


def test_the_bias_added_into_the_weights_leaves_the_reference_once_it_is_not_zero(ids):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_moe

    w = lfm2_moe.weights(SMALL, SEED)
    model, variables = program(ids, False)
    # renormalised weights of scale 1 hardly feel 0.05: a bias as large as the scores' spread
    variables = some_bias(variables, w, spread=0.3)
    sound, _ = reference_loss_and_grads(w, ids, SMALL)

    def wrong_route(w, i, m, c, mm):  # w = (s + b)[idx]: the bias in the weight
        scores = jax.nn.sigmoid(mm(m, w[f"{i}.router"])) + w[f"{i}.bias"]
        _, idx = jax.lax.top_k(scores, c["num_experts_per_tok"])
        wt = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, wt / (wt.sum(-1, keepdims=True) + 1e-6)

    right = lfm2_moe.route
    try:
        lfm2_moe.route = wrong_route
        wrong, _ = reference_loss_and_grads(w, ids, SMALL)
    finally:
        lfm2_moe.route = right
    loss, _ = program_loss_and_grads(model, variables, ids)
    assert abs(float(loss) - float(sound)) < TOLERANCE < abs(float(loss) - float(wrong)) / 3


def test_products_in_float8_leave_the_reference_by_more_than_float32_noise(ids, sound_reference):
    from perfbench.reference import lfm2_moe

    ref = sound_reference
    low = lfm2_moe.first_loss(SMALL, ids, SEED, operands="float8_e4m3fn")
    assert abs(low - ref) > 10 * TOLERANCE


CATALOG_ROW = dict(  # the catalog row's numbers (architectures.jsonl, LFM2-24B-A2B)
    conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=11776,
    max_position_embeddings=128000, model_type="lfm2_moe", moe_intermediate_size=1536,
    norm_eps=1e-5, norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, routed_scaling_factor=1,
    use_expert_bias=True, vocab_size=65536,
)


def test_the_cells_configuration_is_the_catalog_rows_but_for_what_reduced_names():
    config = json.loads((REPO / "perfbench" / "configs" / "lfm2-24b-a2b-d5.json").read_text())
    entry = next(c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]
                 if c["name"] == "lfm2-24b-a2b-d5")
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
               "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == list(config["source_values"]) == list(
        config["reduced_why"]) == reduced
    assert entry["source"] == config["source"]
    assert {k: config[k] for k in CATALOG_ROW if k not in reduced} == {
        k: v for k, v in CATALOG_ROW.items() if k not in reduced}
    assert config["source_values"] == {k: CATALOG_ROW[k] for k in reduced}
    assert len(config["layer_types"]) == 40 and config["layer_types"][:8] == KINDS
    assert config["layer_types"].count("full_attention") == 10
    assert config["head_dim"] == 2048 // 32 and set(config["assumed"]) >= {
        "head_dim", "tie_word_embeddings", "bias_update", "no_auxiliary_loss", "initializers",
        "precision"}
    assert config["vocab_size"] * config["share"]["chips_sharing_embedding_and_head"] == 65536
    assert config["num_experts"] * config["share"]["chips_sharing_a_layers_experts"] == 64
    assert config["share"]["experts_routed"] == 64 and len(config["stands_for"]) > 100
    # the job keys say what the file says
    sets = dict(s.removeprefix("job.model_config.").split("=", 1) for s in config["job_sets"][1:])
    assert config["job_sets"][0] == "job.model_family=lfm2_moe"
    assert json.loads(sets["layer_types"]) == [config["layer_types"][i] for i in config["layers_run"]]
    assert config["layers_run"] == [0, 3, 4, 5, 6]
    assert (int(sets["num_experts"]), int(sets["experts_held"]), int(sets["expert_offset"])) == (
        config["share"]["experts_routed"], config["num_experts"], config["share"]["expert_offset"])
    assert int(sets["vocab_size"]) == config["vocab_size"] and int(sets["num_layers"]) == 5
    assert (int(sets["head_dim"]), int(sets["conv_taps"]), int(sets["experts_per_token"])) == (64, 3, 4)
    assert float(sets["rope_theta"]) == 1e6 and float(sets["route_eps"]) == 1e-6
    traffic = json.loads((REPO / "perfbench" / "traffic" / "lfm2-24b-a2b-d5.steps.json").read_text())
    assert traffic["data"]["modulus"] <= config["vocab_size"] and traffic["sequence"] == 8192
    assert traffic["data"]["sequences"] >= 2 * 3 * traffic["inner_steps"] * traffic["batch"]
    assert traffic["inner_steps"] % 8 == 0
