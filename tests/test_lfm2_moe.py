"""The lfm2_moe family (models/lfm2_moe.py) and what it forced: an operator
kind a layer that is no attention (ops/short_conv.py), the routed layer
without a shared expert (models/routed.py, shared with afmoe), the head's leaf
asked of the model, and afmoe's program left as it was."""

from __future__ import annotations

import dataclasses
import hashlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.models import build_model
from hypha_tpu.models.lfm2_moe import CONV, FULL, Lfm2MoeConfig, _ConvOperator
from hypha_tpu.models.routed import STATE, _MoE
from hypha_tpu.ops.short_conv import short_conv


def _tiny(**changed):
    return build_model({"family": "lfm2_moe", "preset": "tiny",
                        "config": {"dtype": "float32", **changed}})


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 48)), jnp.int32)


@pytest.fixture(scope="module")
def whole(ids):
    model, cfg = _tiny()
    variables = model.init(jax.random.key(7), ids)
    bias = jax.tree.map(
        lambda b: jnp.asarray(np.random.default_rng(11).normal(0, 0.05, b.shape), jnp.float32),
        variables[STATE])
    return model, cfg, {"params": variables["params"], STATE: bias}


# --------------------------------------------------------------------------
# The operator's core
# --------------------------------------------------------------------------


def _core_inputs(s=12, d=5, taps=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    b, c, x = (jax.random.normal(k, (2, s, d)) for k in ks[:3])
    return b, c, x, jax.random.normal(ks[3], (taps, d))


def _three_taps_by_position(z, taps):
    """c_t = sum_j w[j] z_{t - 2 + j}: tap 2 weighs the current position, tap 0
    the one two back, and nothing stands before the sequence's start."""
    z, taps = np.asarray(z), np.asarray(taps)
    conv = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(3):
            if t - 2 + j >= 0:
                conv[:, t] += taps[j] * z[:, t - 2 + j]
    return conv


def test_the_core_is_the_per_position_loop():
    b, c, x, taps = _core_inputs()
    want = np.asarray(c) * _three_taps_by_position(b * x, taps)
    np.testing.assert_allclose(short_conv(b, c, x, taps), want, atol=1e-6)


@pytest.mark.parametrize("taps", [1, 2, 3, 4])
def test_the_core_is_causal_and_as_long_as_its_taps(taps):
    b, c, x, w = _core_inputs(taps=taps)
    t = 5
    moved = short_conv(b, c, x.at[:, t].add(1.0), w) - short_conv(b, c, x, w)
    changed = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 0)
    assert list(changed) == list(range(t, t + taps))  # none before t, none after t + taps - 1


def test_within_one_conv_layer_token_t_reaches_t_to_t_plus_two_and_no_other(whole):
    _, cfg, variables = whole
    op, p = _ConvOperator(cfg), {"params": variables["params"]["layers_1"]["conv"]}
    u = jax.random.normal(jax.random.key(2), (1, 20, cfg.hidden_size))
    t = 7
    moved = op.apply(p, u.at[:, t].add(0.5)) - op.apply(p, u)
    changed = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 1e-7)
    assert list(changed) == [t, t + 1, t + 2]


def test_the_operator_is_the_sources_equations(whole):
    _, cfg, variables = whole
    p = variables["params"]["layers_1"]["conv"]
    u = jax.random.normal(jax.random.key(4), (2, 16, cfg.hidden_size))
    b, c, x = np.split(np.asarray(u @ p["in_proj"]["kernel"]), 3, axis=-1)  # B, C, x in that order
    want = (c * _three_taps_by_position(b * x, p["conv_weight"])) @ np.asarray(p["out_proj"]["kernel"])
    np.testing.assert_allclose(_ConvOperator(cfg).apply({"params": p}, u), want, atol=2e-5)


# --------------------------------------------------------------------------
# The model: kinds of layer, shares, the head
# --------------------------------------------------------------------------


def test_the_family_builds_from_job_keys_with_a_list_of_layer_kinds():
    model, cfg = build_model({"family": "lfm2_moe", "config": {
        "vocab_size": 64, "hidden_size": 32, "num_layers": 2, "num_dense_layers": 1,
        "num_heads": 2, "num_kv_heads": 1, "head_dim": 16, "num_experts": 8, "experts_held": 2,
        "expert_offset": 4, "layer_types": ["conv", "full_attention"]}})
    assert cfg.layer_types == (CONV, FULL) and cfg.held == 2 and model.head_leaf == "embed_tokens"
    published = Lfm2MoeConfig()  # LFM2-24B-A2B's own sizes and pattern
    assert published.layer_types[:7] == (CONV, CONV, FULL, CONV, CONV, CONV, FULL)
    assert len(published.layer_types) == 40 and published.layer_types.count(FULL) == 10
    assert (published.held, published.head_dim, published.num_shared_experts) == (64, 64, 0)
    assert (published.route_eps, published.route_scale, published.conv_taps) == (1e-6, 1.0, 3)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_layers=3, layer_types=("conv",))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_layers=1, layer_types=("sliding_attention",))
    with pytest.raises(ValueError, match="experts_held"):
        Lfm2MoeConfig(experts_held=8, expert_offset=57)
    with pytest.raises(TypeError):  # the family has no shared expert to ask for
        build_model({"family": "lfm2_moe", "preset": "tiny", "config": {"num_shared_experts": 1}})


def test_a_layers_kind_chooses_its_operator_and_there_is_no_head_of_its_own(whole):
    _, cfg, variables = whole
    p = variables["params"]
    for i, kind in enumerate(cfg.layer_types):
        assert ("conv" in p[f"layers_{i}"]) == (kind == CONV)
        assert ("self_attn" in p[f"layers_{i}"]) == (kind == FULL)
        assert set(p[f"layers_{i}"]) - {"conv", "self_attn"} == {"operator_norm", "ffn_norm", "feed_forward"}
    assert set(p) == {"embed_tokens", "embedding_norm"} | {f"layers_{i}" for i in range(4)}
    assert set(p["layers_1"]["feed_forward"]) == {"router", "experts_gate", "experts_up", "experts_down"}
    assert set(variables[STATE]) == {"layers_1", "layers_2", "layers_3"}  # layer 0 is dense


def test_the_logits_are_taken_against_the_embedding(whole, ids):
    model, _, variables = whole
    logits, _ = model.apply(variables, ids)
    hidden, _ = model.clone(with_head=False).apply(variables, ids)
    np.testing.assert_allclose(
        logits, jnp.einsum("bse,ve->bsv", hidden, variables["params"]["embed_tokens"]), atol=1e-5)


def test_the_bias_enters_the_choice_alone(whole, ids):
    model, _, variables = whole
    _, stats = model.apply(variables, ids)
    zero = {**variables, STATE: jax.tree.map(jnp.zeros_like, variables[STATE])}
    _, stats0 = model.apply(zero, ids)
    assert not np.array_equal(stats["chosen"], stats0["chosen"])  # it moves the choice
    grads = jax.grad(lambda v: model.apply(v, ids)[0].sum())(variables)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(grads[STATE]))


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """No shared expert: the routed parts of all the shares are the whole
    layer's output, nothing to count once."""
    _, cfg, variables = whole
    layer = "layers_2"
    m = jax.random.normal(jax.random.key(5), (2, 48, cfg.hidden_size))
    p, b = variables["params"][layer]["feed_forward"], variables[STATE][layer]["feed_forward"]
    assert "shared_experts" not in p
    uncut, stats = _MoE(cfg).apply({"params": p, STATE: b}, m)
    total, pairs, held = jnp.zeros_like(uncut), 0, 2
    for rank in range(cfg.num_experts // held):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=rank * held)
        mine = {**p, **{k: p[k][rank * held:(rank + 1) * held]
                        for k in ("experts_gate", "experts_up", "experts_down")}}
        out, st = _MoE(share).apply({"params": mine, STATE: b}, m)
        total = total + out
        pairs += int(st["pairs_computed"])
        assert int(st["pairs_computed"]) == int(st["pairs_routed"])
        np.testing.assert_array_equal(st["chosen"], stats["chosen"])  # routed over all, everywhere
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert pairs == int(stats["pairs_computed"]) == m.shape[0] * m.shape[1] * cfg.experts_per_token


def test_the_chosen_scores_are_renormalised_with_the_sources_epsilon(whole):
    """With every expert held and identity-free weights the layer's output is
    sum_i w_i expert_i(m), w = s / (sum s + 1e-6): read back by a loop."""
    _, cfg, variables = whole
    p, b = (variables[k]["layers_1"]["feed_forward"] for k in ("params", STATE))
    m = jax.random.normal(jax.random.key(6), (1, 8, cfg.hidden_size))
    out, _ = _MoE(cfg).apply({"params": p, STATE: b}, m)
    x = m[0]
    s = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(s + b["expert_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    want = jnp.zeros_like(x)
    for t in range(x.shape[0]):
        for k in range(cfg.experts_per_token):
            e = int(idx[t, k])
            act = jax.nn.silu(x[t] @ p["experts_gate"][e]) * (x[t] @ p["experts_up"][e])
            want = want.at[t].add(w[t, k] * (act @ p["experts_down"][e]))
    np.testing.assert_allclose(out[0], want, atol=2e-5)


def test_the_worker_logs_which_operators_it_holds(ids, caplog):
    import types

    from hypha_tpu.executor import training

    spec = {"family": "lfm2_moe", "preset": "tiny", "seed": 0, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    with caplog.at_level(logging.INFO, logger="hypha.executor.training"):
        training._init_model(cfg, None, "/nonexistent", {"input_ids": np.asarray(ids)})
    assert "operators: conv=3 full_attention=1 head_dim=16" in caplog.text


def test_a_configuration_with_kinds_and_no_head_size_is_logged_without_one(ids, caplog, monkeypatch):
    """The fallbacks hand set-up a library's configuration: it may list
    ``layer_types`` and carry no ``head_dim``, and set-up does not fail on it."""
    import types

    from hypha_tpu.executor import training

    import hypha_tpu.models as models

    build = models.build_model

    def foreign(spec, attn_impl=None):
        model, mcfg = build(spec, attn_impl)
        return model, types.SimpleNamespace(layer_types=list(mcfg.layer_types))

    monkeypatch.setattr(models, "build_model", foreign)  # set-up imports it when called
    spec = {"family": "lfm2_moe", "preset": "tiny", "seed": 0, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    with caplog.at_level(logging.INFO, logger="hypha.executor.training"):
        training._init_model(cfg, None, "/nonexistent", {"input_ids": np.asarray(ids)})
    assert "operators: conv=3 full_attention=1\n" in caplog.text + "\n"
    assert "head_dim" not in caplog.text


# --------------------------------------------------------------------------
# afmoe's program is the parent's
# --------------------------------------------------------------------------

# The afmoe ``tiny`` routed step, lowered (StableHLO text), as commit 30533df
# lowers it: the same script run on both trees. ``_MoE`` moved to
# ``models/routed.py``, took its epsilon from the configuration and learnt to
# leave the shared expert out; Trinity's cell runs this program and it must
# not move. (Since PR 52 the hash is that tree's: it changed, for every routed
# family on purpose, how the grouped product's rows go onto the tokens, in
# batches, and how the router makes the pairs' weights, by selection and
# through the sort; PR 51 had changed the backward walk the same way.)
AFMOE_STEP_AT_THE_PARENT = "61505bc922d89977289981fc321b233fd02ca26af46d7748639bf0b2e53508f9"


def test_afmoes_step_lowers_to_the_program_of_the_parent_commit():
    import optax

    from hypha_tpu.executor.train import TrainState, make_routed_train_step

    model, _ = build_model({"family": "afmoe", "preset": "tiny"})
    ids = jnp.zeros((2, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    state = jax.eval_shape(
        lambda v: TrainState.create({"params": v["params"]}, optax.adamw(1e-3), {STATE: v[STATE]}),
        variables)
    text = make_routed_train_step(model, loss_chunk=16).lower(state, {"input_ids": ids}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == AFMOE_STEP_AT_THE_PARENT
    assert "shared_experts" in str(jax.tree.structure(variables["params"]))  # afmoe keeps its own
