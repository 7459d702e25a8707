"""The ``keye_vl2`` family (``models/keye_vl2.py``): the block, the indexer's
objective kept apart from the language model's by two ``stop_gradient``s, the
softmax router of ``models/routed.py``, the routed step's second objective,
set-up's line, and the other families' steps, which must not move."""

from __future__ import annotations

import hashlib
import logging
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hypha_tpu.models import build_model
from hypha_tpu.models.keye_vl2 import KeyeVL2, KeyeVL2Config
from hypha_tpu.models.routed import STATE, _MoE


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)), jnp.int32)  # four times topk


@pytest.fixture(scope="module")
def whole(ids):
    model, cfg = build_model({"family": "keye_vl2", "preset": "tiny", "config": {"dtype": "float32"}})
    return model, cfg, model.init(jax.random.key(0), ids)


# --------------------------------------------------------------------------
# The configuration and the parameters
# --------------------------------------------------------------------------


def test_the_registry_builds_the_family_and_its_defaults_are_the_published_sizes():
    model, cfg = build_model({"family": "keye_vl2", "preset": "tiny"})
    assert isinstance(model, KeyeVL2) and cfg == KeyeVL2Config.tiny()
    p = KeyeVL2Config()
    assert (p.vocab_size, p.hidden_size, p.num_layers, p.num_heads, p.num_kv_heads, p.head_dim) == (
        151936, 2048, 48, 32, 4, 128)
    assert (p.index_heads, p.index_head_dim, p.index_topk, p.q_chunk, p.kv_chunk) == (16, 64, 2048, 512, 512)
    assert (p.num_experts, p.experts_per_token, p.moe_intermediate_size, p.route_norm, p.held) == (128, 8, 768, True, 128)
    assert (p.rope_theta, p.rms_eps, p.router, p.num_shared_experts, p.load_balance_coeff) == (1e7, 1e-6, "softmax", 0, 0.0)
    assert p.layer_types == ("sparse_attention",) * 48 + ("experts",) * 48 and p.num_expert_layers == 48
    with pytest.raises(ValueError, match="experts_held"):
        KeyeVL2Config(experts_held=16, expert_offset=120)
    with pytest.raises(TypeError):  # the router's kind, the shared expert and the bias's pace are no keys of a job
        build_model({"family": "keye_vl2", "preset": "tiny", "config": {"router": "sigmoid"}})


@pytest.mark.parametrize("held,total", [(16, 465_391_104), (8, 314_396_160)])
def test_the_cells_cut_counts_the_parameters_the_hand_count_gives(held, total):
    """ISSUE 50's arithmetic: a block is 18874624 of attention, 2261120 of
    indexer, 262144 of router, 4718592 an expert and two norms of 2048."""
    cut = {"vocab_size": 18992, "num_layers": 4, "experts_held": held, "max_seq_len": 16384}
    model, _ = build_model({"family": "keye_vl2", "config": cut})
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 16), jnp.int32))
    sizes = {jax.tree_util.keystr(k): int(np.prod(v.shape)) for k, v in jax.tree_util.tree_leaves_with_path(variables["params"])}
    block = {k.split("']['", 1)[1]: n for k, n in sizes.items() if k.startswith("['layers_0']")}
    attention = sum(n for k, n in block.items() if k.startswith("self_attn") and "index_" not in k)
    indexer = sum(n for k, n in block.items() if "index_" in k)
    assert (attention, indexer, block["mlp']['router']"]) == (18_874_624, 2_261_120, 262_144)
    assert block["mlp']['experts_gate']"] * 3 == held * 4_718_592
    assert sum(sizes.values()) == total == 4 * (18_874_624 + 2_261_120 + 262_144 + held * 4_718_592 + 4096) + 2 * 38_895_616 + 2048
    assert len(sizes) == 4 * 17 + 3  # the leaves a round's delta carries


# --------------------------------------------------------------------------
# The two objectives and what each trains
# --------------------------------------------------------------------------


def _both(model, variables, ids):
    from hypha_tpu.executor.train import _head_loss

    body = model.clone(with_head=False)

    def losses(params):
        hidden, stats = body.apply({"params": params, STATE: variables[STATE]}, ids)
        return _head_loss(model, {"params": params}, hidden, ids, 16), stats["aux_loss"].sum()

    return losses


def test_the_indexer_learns_from_the_kl_alone_and_the_rest_from_the_cross_entropy_alone(whole, ids):
    model, _, variables = whole
    losses = _both(model, variables, ids)
    from_ce = jax.grad(lambda p: losses(p)[0])(variables["params"])
    from_kl = jax.grad(lambda p: losses(p)[1])(variables["params"])
    seen = {True: 0, False: 0}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(from_ce), jax.tree.leaves(from_kl)):
        indexer = "index_" in jax.tree_util.keystr(path)
        seen[indexer] += 1
        assert (float(jnp.abs(a).max()) == 0.0) == indexer, path  # exact zeros, by stop_gradient
        assert (float(jnp.abs(b).max()) == 0.0) == (not indexer), path
    assert seen == {True: 2 * 5, False: 2 * 12 + 3}


def test_the_model_returns_a_kl_a_layer_and_the_share_the_sequence_and_topk_give(whole, ids):
    model, cfg, variables = whole
    logits, stats = model.apply(variables, ids)
    assert logits.shape == (2, 64, 256) and logits.dtype == jnp.float32
    assert stats["aux_loss"].shape == stats["keys_picked_share"].shape == (2,)
    assert bool((stats["aux_loss"] > 0).all()) and bool(jnp.isfinite(stats["aux_loss"]).all())
    picked = sum(min(t + 1, cfg.index_topk) for t in range(64))
    np.testing.assert_allclose(stats["keys_picked_share"], picked / (64 * 65 // 2), rtol=1e-6)
    assert int(stats["pairs_routed"].sum()) == int(stats["pairs_computed"].sum()) == 2 * ids.size * 2  # all held


def test_a_topk_no_query_reaches_is_plain_causal_attention(ids):
    """With ``index_topk >= S`` every query keeps every causal key: the logits
    are those of the same weights under ``dot_product_attention``."""
    from hypha_tpu.ops.attention import dot_product_attention
    from hypha_tpu.ops import index_select

    model, _ = build_model({"family": "keye_vl2", "preset": "tiny", "config": {"dtype": "float32", "index_topk": 64}})
    variables = model.init(jax.random.key(1), ids)
    mine, stats = model.apply(variables, ids)
    assert float(stats["keys_picked_share"].min()) == 1.0
    was = index_select.masked_attention
    index_select.masked_attention = lambda q, k, v, packed, scale: (
        dot_product_attention(q, k, v, causal=True), was(q, k, v, packed, scale)[1])
    try:
        theirs, _ = model.apply(variables, ids)
    finally:
        index_select.masked_attention = was
    np.testing.assert_allclose(mine, theirs, atol=2e-5)


def test_the_selection_cuts_what_later_queries_see(whole, ids):
    """Changing a key the indexer drops for the last query leaves that query's
    logits as they were; changing one it keeps moves them."""
    model, cfg, variables = whole
    logits, _ = model.apply(variables, ids[:1])
    other = ids[:1].at[0, 10].set((ids[0, 10] + 1) % 256)
    moved, _ = model.apply(variables, other)
    assert float(jnp.abs(logits[0, :10] - moved[0, :10]).max()) == 0.0  # causal
    assert float(jnp.abs(logits[0, 10:] - moved[0, 10:]).max()) > 1e-4


# --------------------------------------------------------------------------
# The softmax router of routed.py
# --------------------------------------------------------------------------


def test_the_router_is_a_softmax_over_all_experts_renormalised_over_the_chosen():
    cfg = KeyeVL2Config(hidden_size=32, num_experts=8, experts_per_token=3, moe_intermediate_size=16,
                        dtype="float32", moe_chunk=32)
    m = jax.random.normal(jax.random.key(2), (1, 30, 32))
    variables = _MoE(cfg).init(jax.random.key(3), m)
    assert set(variables["params"]) == {"router", "experts_gate", "experts_up", "experts_down"}  # nothing dense beside
    out, stats = _MoE(cfg).apply(variables, m)
    p = variables["params"]
    prob = jax.nn.softmax(m[0] @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(prob, 3)
    top = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(m[0])
    for e in range(8):
        y = (jax.nn.silu(m[0] @ p["experts_gate"][e]) * (m[0] @ p["experts_up"][e])) @ p["experts_down"][e]
        want += jnp.where(idx == e, top, 0.0).sum(-1)[:, None] * y
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    assert int(stats["pairs_computed"]) == 30 * 3
    sigmoid = types.SimpleNamespace(**{**{k: getattr(cfg, k) for k in (
        "dtype", "num_experts", "experts_per_token", "held", "expert_offset", "moe_intermediate_size", "moe_chunk",
        "num_shared_experts", "route_norm", "route_scale", "route_eps")}})  # no ``router``: sigmoid, as before
    other, _ = _MoE(sigmoid).apply(variables, m)
    assert float(jnp.abs(other - out).max()) > 1e-3


# --------------------------------------------------------------------------
# Training: the routed step's second objective, and set-up's line
# --------------------------------------------------------------------------


def test_the_routed_step_adds_the_kl_logs_it_apart_and_both_objectives_fall(whole, ids):
    from hypha_tpu.executor.train import ROUTING_FIELDS, TrainState, aux_fields, make_routed_train_step

    model, cfg, variables = whole
    assert model.head_leaf == "lm_head" and model.aux_name == "index_kl"
    AUX_FIELDS = aux_fields(model)  # the model names what rides after the routing counters
    assert AUX_FIELDS == ("aux_loss", "keys_picked_share")
    state = TrainState.create({"params": variables["params"]}, optax.adamw(1e-2), {STATE: variables[STATE]})
    step = make_routed_train_step(model, loss_chunk=16, donate=False)
    seen = []
    for _ in range(10):
        state, metrics = step(state, {"input_ids": ids})
        seen.append((float(metrics["loss"]), float(metrics["aux_loss"])))
        assert abs(float(metrics["total_loss"]) - sum(seen[-1])) < 1e-5  # what was differentiated
    assert seen[-1][0] < 0.8 * seen[0][0] and seen[-1][1] < seen[0][1]  # the indexer learns too
    host = dict(zip(ROUTING_FIELDS + AUX_FIELDS, np.asarray(metrics["host"]).tolist()))
    assert host["loss"] == seen[-1][0] and abs(host["aux_loss"] - seen[-1][1]) < 1e-6  # the logged loss stays the cross-entropy
    assert host["pairs_routed"] == host["pairs_computed"] == 2 * ids.size * cfg.experts_per_token
    assert abs(host["keys_picked_share"] - sum(min(t + 1, 16) for t in range(64)) / 2080) < 1e-6
    bias = state.extras[STATE]["layers_0"]["mlp"]["expert_bias"]
    assert float(jnp.abs(bias).max()) == 0.0  # load_balance_coeff 0: the selection bias never moves


@pytest.mark.parametrize("named", [(), ("keys_picked_share",), None])
def test_the_step_carries_the_fields_the_model_names_and_knows_none_itself(whole, ids, named):
    """A second objective with no key selection names no further field, and
    the step asks its ``stats`` for none; a model with no ``aux_name`` has no
    such part of the vector at all."""
    from hypha_tpu.executor.train import ROUTING_FIELDS, TrainState, aux_fields, make_routed_train_step

    _, cfg, variables = whole

    class Other(KeyeVL2):
        aux_name = None if named is None else "second"
        aux_fields = named or ()

    model = Other(cfg)
    assert aux_fields(model) == (() if named is None else ("aux_loss", *named))
    assert aux_fields(object()) == ()
    if named is None:
        return  # the family's stats still carry aux_loss: the vector's reader is what the name decides
    state = TrainState.create({"params": variables["params"]}, optax.adamw(1e-2), {STATE: variables[STATE]})
    _, metrics = make_routed_train_step(model, loss_chunk=16, donate=False)(state, {"input_ids": ids})
    assert metrics["host"].shape == (len(ROUTING_FIELDS) + 1 + len(named),)


def test_a_step_without_a_second_objective_has_the_host_vector_it_had():
    from hypha_tpu.executor.train import ROUTING_FIELDS, TrainState, make_routed_train_step

    model, _ = build_model({"family": "afmoe", "preset": "tiny"})
    ids = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.key(0), ids)
    state = TrainState.create({"params": variables["params"]}, optax.adamw(1e-3), {STATE: variables[STATE]})
    _, metrics = make_routed_train_step(model, loss_chunk=16, donate=False)(state, {"input_ids": ids})
    assert metrics["host"].shape == (len(ROUTING_FIELDS),) and float(metrics["aux_loss"]) == 0.0
    assert float(metrics["total_loss"]) == float(metrics["loss"])


def test_set_up_says_what_the_stack_holds_the_indexers_sizes_and_the_routers_kind(caplog, ids):
    from hypha_tpu.executor import training

    spec = {"family": "keye_vl2", "preset": "tiny", "seed": 3, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    with caplog.at_level(logging.INFO, logger="hypha.executor.training"):
        model, variables, causal_lm, _ = training._init_model(cfg, None, "/nonexistent", {"input_ids": np.asarray(ids)})
    assert causal_lm and set(variables) == {"params", STATE}
    assert ("operators: sparse_attention=2 experts=2 head_dim=16 index_heads=4 index_head_dim=8 index_topk=16 "
            "router=softmax\n") in caplog.text + "\n"


def test_no_familys_name_is_in_the_executor_and_no_switch_for_the_forms():
    import hypha_tpu.executor as executor

    for path in pathlib.Path(executor.__file__).parent.glob("*.py"):
        text = path.read_text().lower()
        assert "keye_vl" not in text and "keyevl" not in text and "keye-vl" not in text and "indexer" not in text and "softmax router" not in text, path.name
    ops = pathlib.Path(executor.__file__).parent.parent / "ops"
    for name in ("index_select.py", "flash_attention.py"):
        text = (ops / name).read_text()
        assert "os.environ" not in text and "approx_max_k" not in text and "approx_min_k" not in text, name
    assert sorted(p.name for p in ops.glob("flash_attention*.py")) == ["flash_attention.py"]  # one file
    assert sorted(p.name for p in ops.glob("grouped_matmul*.py")) == ["grouped_matmul.py"]  # not forked
    models = pathlib.Path(executor.__file__).parent.parent / "models"
    assert not any("vision" in p.name or "vit" in p.name.lower() for p in models.glob("*.py"))  # no tower


# Each family's step, lowered (StableHLO text), as commit 096803d lowers it: the
# same script run on both trees. The flash kernels learnt a selection, ``_MoE`` a
# router's kind and the routed step a second objective; the six cells that are
# there run these programs and they must not move. (The three routed families'
# are the programs since PR 52, which changed on purpose how the grouped product's
# rows go onto the tokens and how the router makes the pairs' weights, as PR 51 had
# changed the backward walk; mistral's and phi4flash's, which have no routed layer,
# are 096803d's: the proof that the controls' programs did not change.)
STEPS_AT_THE_PARENT = {
    "afmoe": "61505bc922d89977289981fc321b233fd02ca26af46d7748639bf0b2e53508f9",
    "lfm2_moe": "db2b81830a3dcfe5e3beb6d164699c9ecac4dbb901a3aa7f59d6795f2a3277e5",
    "mistral": "1ce07af37cee0bec6bbbc61e1a738285162c89baa436722008662b6367f6ad5d",
    "nemotron_h": "65aada133e7c05802d4be8278e614273db2dcbe979e98be3f3927f33da1afe53",
    "phi4flash": "4301376caff8d494dbae9584114c6039cd3e085e2b2d24fbb9d7802f61f22972",
}
MISTRAL = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128, "num_layers": 2,
           "num_heads": 4, "num_kv_heads": 2, "sliding_window": 32}


@pytest.mark.parametrize("family", sorted(STEPS_AT_THE_PARENT))
def test_the_other_families_steps_lower_to_the_program_of_the_parent_commit(family):
    from hypha_tpu.executor.train import (
        TrainState, make_chunked_train_step, make_routed_train_step, make_train_step)

    ids = jnp.zeros((2, 64), jnp.int32)
    spec = {"family": "mistral", "config": MISTRAL} if family == "mistral" else {"family": family, "preset": "tiny"}
    model, _ = build_model(spec)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)
    if STATE in variables:
        state = jax.eval_shape(
            lambda v: TrainState.create({"params": v["params"]}, optax.adamw(1e-3), {STATE: v[STATE]}), variables)
        step = make_routed_train_step(model, loss_chunk=16)
    else:
        state = jax.eval_shape(lambda v: TrainState.create(v, optax.adamw(1e-3)), variables)
        step = make_train_step(model.apply) if family == "mistral" else make_chunked_train_step(model, loss_chunk=16)
    with jax.default_matmul_precision("default"):  # as the script ran: the file's fixture is not the program's
        text = step.lower(state, {"input_ids": ids}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS_AT_THE_PARENT[family]


def test_the_scopes_of_the_new_layers_are_in_the_step(whole, ids):
    """Device events carry the scopes on the chip; here the traced step's
    jaxpr is what can be read."""
    model, _, variables = whole

    def loss(params):
        out, stats = model.apply({"params": params, STATE: variables[STATE]}, ids)
        return out.sum() + stats["aux_loss"].sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(variables["params"]).pretty_print(name_stack=True))
    for scope in ("index_scores", "index_select", "index_kl", "router", "moe_dispatch", "moe_experts", "moe_combine"):
        assert scope in text, scope
    # inside the layer's scope (a loop's body prints its scopes from the loop on: index_scores, index_select)
    assert re.search(r"layers_\d/sparse_attention/\S*index_kl", text)
    assert "[index_scores]" in text and "[index_select]" in text
    assert "shared_expert" not in text and not re.search(r"index_kl/\S*index_scores", text)  # the walk's own scores are the walk's
