"""The afmoe family (models/afmoe.py) and what it forced: the flash kernel's
window, the grouped product without drops, one rank's share of a layer's
experts, and the selection bias that is no parameter."""

from __future__ import annotations

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.models import build_model
from hypha_tpu.models.afmoe import FULL, SLIDING, STATE, AfmoeConfig, update_bias
from hypha_tpu.ops.attention import dot_product_attention
from hypha_tpu.ops.flash_attention import flash_attention
from hypha_tpu.ops.grouped_matmul import grouped_experts, sort_pairs, sort_pairs_weighted

# --------------------------------------------------------------------------
# The flash kernel's window
# --------------------------------------------------------------------------


def _qkv(s, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (2, s, 4, 32))
    k = jax.random.normal(ks[1], (2, s, 2, 32))
    v = jax.random.normal(ks[2], (2, s, 2, 32))
    return q, k, v, jax.random.normal(ks[3], (2, s, 4, 32))


WINDOWS = {
    "a_multiple_of_the_block": (128, 64, 64),
    "no_multiple_of_the_block": (100, 64, 64),
    "smaller_than_a_block": (24, 64, 64),
    "blocks_of_two_sizes": (70, 64, 128),
    "one_key": (1, 64, 64),
}


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_windowed_flash_is_the_dense_masked_path_output_and_all_three_gradients(case):
    window, bq, bk = WINDOWS[case]
    q, k, v, ct = _qkv(256)

    def flash(q, k, v):
        out = flash_attention(q, k, v, window=window, block_q=bq, block_k=bk, interpret=True)
        return (out * ct).sum(), out

    def dense(q, k, v):
        out = dot_product_attention(q, k, v, causal=True, window=window)
        return (out * ct).sum(), out

    (_, a), ga = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(q, k, v)
    (_, b), gb = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(a, b, atol=2e-5)
    for mine, theirs, name in zip(ga, gb, "qkv"):
        np.testing.assert_allclose(mine, theirs, atol=5e-5, err_msg=f"d{name}")


def test_a_window_changes_what_a_query_sees():
    q, k, v, _ = _qkv(256)
    full = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    cut = flash_attention(q, k, v, window=64, block_q=64, block_k=64, interpret=True)
    np.testing.assert_array_equal(full[:, :64], cut[:, :64])  # the band is not reached yet
    assert float(jnp.abs(full[:, 64:] - cut[:, 64:]).max()) > 1e-2


def test_a_window_no_query_reaches_is_the_plain_causal_program_bit_for_bit():
    q, k, v, ct = _qkv(256)
    f = lambda w: jax.value_and_grad(
        lambda q, k, v: (flash_attention(q, k, v, window=w, block_q=64, block_k=64,
                                         interpret=True) * ct).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(f(None)), jax.tree.leaves(f(256))):
        np.testing.assert_array_equal(a, b)


def test_a_window_needs_causal_self_attention():
    q, k, v, _ = _qkv(128)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=32, interpret=True)


# The traced program of the kernels at window=None, forward and backward. Until
# PR 56 these were the hashes of the commit before the window existed (2778437):
# a window was an addition beside a program that did not move. PR 56 changed every
# causal call's grid on purpose (the needed tiles listed, with or without a
# window), so the hashes are PR 56's tree's (the same script) and hold later PRs
# to it: a Mistral cell runs these; they must not move unnoticed.
WITHOUT_A_WINDOW_AT_PR_56 = {
    "fwd": "15df38448bc2dd77e12e96a20292dcc60891b6c5f4c731f3e69ebf39f656269b",
    "bwd": "e2e7b7d417e20554f593c6679d399c27a37d2be2c4526c0f814248f27ed27d51",
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_without_a_window_the_kernels_trace_to_the_program_of_pr_56(direction):
    q, kv = jnp.zeros((1, 256, 4, 32), jnp.bfloat16), jnp.zeros((1, 256, 2, 32), jnp.bfloat16)
    f = lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64, interpret=False)
    if direction == "bwd":
        fwd = f
        f = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(f)(q, kv, kv)))
    assert hashlib.sha256(text.encode()).hexdigest() == WITHOUT_A_WINDOW_AT_PR_56[direction]


def test_the_scope_of_a_windowed_call_names_its_window():
    q, k, v, _ = _qkv(256)
    f = lambda q, k, v: flash_attention(q, k, v, window=64, block_q=64, block_k=64,
                                        interpret=True).sum()
    text = str(jax.make_jaxpr(jax.grad(f))(q, k, v).pretty_print(name_stack=True))
    assert "flash_attention_w64" in text and "flash_attention_bwd_w64" in text


# --------------------------------------------------------------------------
# The grouped product
# --------------------------------------------------------------------------


def _experts(g=4, d=16, f=24, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (g, d, f)) * 0.3, jax.random.normal(ks[1], (g, d, f)) * 0.3,
            jax.random.normal(ks[2], (g, f, d)) * 0.3)


def _loop(x, w_gate, w_up, w_down, idx, wt, offset):
    """Expert by expert, every token, a weight of zero where not chosen."""
    out = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        mine = jnp.sum(jnp.where(idx == offset + e, wt, 0.0), axis=-1)
        out += mine[:, None] * ((jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e])
    return out


def _grouped(x, w_gate, w_up, w_down, idx, wt, offset, chunk):
    order, sizes = sort_pairs(idx, offset, w_gate.shape[0])
    return grouped_experts(x, (w_gate, w_up, w_down), order // idx.shape[1],
                           wt.reshape(-1)[order], sizes, chunk=chunk)


def _routing(kind: str, tokens=40, k=2, experts=8):
    rng = np.random.default_rng(5)
    if kind == "uniform":
        idx = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif kind == "an_empty_expert":  # expert 3 (held: 2..5) is never chosen
        idx = np.stack([rng.permutation([0, 1, 2, 4, 5, 6, 7])[:k] for _ in range(tokens)])
    elif kind == "every_token_to_one_expert":
        idx = np.stack([[4, rng.choice([0, 1, 6, 7])] for _ in range(tokens)])
    elif kind == "every_choice_held":
        idx = np.stack([rng.permutation([2, 3, 4, 5])[:k] for _ in range(tokens)])
    elif kind == "nothing_held":
        idx = np.stack([rng.permutation([0, 1, 6, 7])[:k] for _ in range(tokens)])
    return jnp.asarray(idx, jnp.int32), jnp.asarray(rng.uniform(0.2, 1.5, (tokens, k)), jnp.float32)


@pytest.mark.parametrize("chunk", [8, 32, 4096])
@pytest.mark.parametrize("kind", ["uniform", "an_empty_expert", "every_token_to_one_expert",
                                  "every_choice_held", "nothing_held"])
def test_the_grouped_product_is_the_per_expert_loop_under_any_routing(kind, chunk):
    idx, wt = _routing(kind)
    x = jax.random.normal(jax.random.key(2), (idx.shape[0], 16))
    ws = _experts()
    ct = jax.random.normal(jax.random.key(3), x.shape)
    mine = lambda x, wt, *ws: (_grouped(x, *ws, idx, wt, 2, chunk) * ct).sum()
    plain = lambda x, wt, *ws: (_loop(x, *ws, idx, wt, 2) * ct).sum()
    np.testing.assert_allclose(_grouped(x, *ws, idx, wt, 2, chunk), _loop(x, *ws, idx, wt, 2), atol=1e-5)
    ga = jax.grad(mine, (0, 1, 2, 3, 4))(x, wt, *ws)
    gb = jax.grad(plain, (0, 1, 2, 3, 4))(x, wt, *ws)
    for a, b, name in zip(ga, gb, ("x", "weights", "gate", "up", "down")):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_sorted_pairs_count_every_held_choice_once():
    idx, _ = _routing("uniform")
    order, sizes = sort_pairs(idx, 2, 4)
    flat = np.asarray(idx).reshape(-1)
    assert [int(s) for s in sizes] == [int((flat == e).sum()) for e in (2, 3, 4, 5)]
    held = flat[np.asarray(order)[: int(sizes.sum())]]
    assert list(held) == sorted(held) and set(held) <= {2, 3, 4, 5}


ROUTINGS = ["uniform", "an_empty_expert", "every_token_to_one_expert", "every_choice_held", "nothing_held"]


@pytest.mark.parametrize("kind", ROUTINGS)
def test_the_sort_that_carries_the_weights_is_the_gather_by_the_order_bit_for_bit(kind):
    """``sort_pairs_weighted`` against ``order // k`` and ``w.reshape(-1)[order]``:
    the tokens, the weights and the weights' gradient, to the bit."""
    idx, wt = _routing(kind)
    k = idx.shape[1]
    order, sizes = sort_pairs(idx, 2, 4)
    tokens, weights, sizes_too = sort_pairs_weighted(idx, wt, 2, 4)
    assert tokens.dtype == jnp.int32 and weights.dtype == wt.dtype
    np.testing.assert_array_equal(tokens, order // k)
    np.testing.assert_array_equal(weights, wt.reshape(-1)[order])
    np.testing.assert_array_equal(sizes_too, sizes)
    ct = jax.random.normal(jax.random.key(9), (idx.size,))
    carried = jax.grad(lambda wt: (sort_pairs_weighted(idx, wt, 2, 4)[1] * ct).sum())(wt)
    gathered = jax.grad(lambda wt: (wt.reshape(-1)[order] * ct).sum())(wt)
    np.testing.assert_array_equal(carried, gathered)
    jitted = jax.jit(jax.grad(lambda wt, idx: (sort_pairs_weighted(idx, wt, 2, 4)[1] * ct).sum()))(wt, idx)
    np.testing.assert_array_equal(jitted, gathered)


@pytest.mark.parametrize("shape", [(96,), (3, 32)])
@pytest.mark.parametrize("bound", [5, 2 ** 31], ids=["one_word", "key_and_place_apart"])
def test_a_key_sorted_with_its_place_in_one_word_is_the_stable_sort(bound, shape):
    """``_sorted_stably`` packs key and place into one word where both fit (every
    word differs: no tie left to keep) and sorts stably where they do not: the
    same keys, places and carried values either way, along the last axis."""
    from hypha_tpu.ops.grouped_matmul import _sorted_stably

    rng = np.random.default_rng(1)
    key = rng.integers(0, 5, shape).astype(np.int32)  # many ties
    carried = rng.random(shape).astype(np.float32)
    got_key, got_at, got_carried = _sorted_stably(jnp.asarray(key), bound, jnp.asarray(carried))
    at = np.argsort(key, axis=-1, kind="stable")
    np.testing.assert_array_equal(got_at, at)
    np.testing.assert_array_equal(got_key, np.take_along_axis(key, at, -1))
    np.testing.assert_array_equal(got_carried, np.take_along_axis(carried, at, -1))


@pytest.mark.parametrize("squash", ["sigmoid", "softmax"])
@pytest.mark.parametrize("kind", ROUTINGS)
def test_the_chosen_scores_by_selection_are_take_along_axis_bit_for_bit(kind, squash):
    from hypha_tpu.models.routed import chosen_scores

    idx, _ = _routing(kind)
    logits = jax.random.normal(jax.random.key(4), (idx.shape[0], 8)) * 3
    ct = jax.random.normal(jax.random.key(6), idx.shape)
    scores = lambda logits: getattr(jax.nn, squash)(logits)
    hot = idx[..., None] == jnp.arange(8, dtype=idx.dtype)
    selected = lambda logits: chosen_scores(scores(logits), hot)
    indexed = lambda logits: jnp.take_along_axis(scores(logits), idx, axis=-1)
    np.testing.assert_array_equal(selected(logits), indexed(logits))
    # the gradient in the scores themselves: a select there, a scatter-add here
    np.testing.assert_array_equal(
        jax.grad(lambda s: (chosen_scores(s, hot) * ct).sum())(scores(logits)),
        jax.grad(lambda s: (jnp.take_along_axis(s, idx, axis=-1) * ct).sum())(scores(logits)))
    np.testing.assert_allclose(
        jax.grad(lambda l: (selected(l) * ct).sum())(logits),
        jax.grad(lambda l: (indexed(l) * ct).sum())(logits), rtol=0, atol=0)


def test_the_trip_count_follows_the_pairs_held_not_the_pairs_there_are():
    """The loop's bound is ceil(pairs held / chunk): read from the jaxpr's
    while condition by running it on two routings of the same shapes."""
    from hypha_tpu.ops import grouped_matmul

    for kind, trips in (("nothing_held", 0), ("every_token_to_one_expert", 5), ("every_choice_held", 10)):
        idx, wt = _routing(kind)
        order, sizes = sort_pairs(idx, 2, 4)
        n, _ = grouped_matmul._walk(order // 2, wt.reshape(-1)[order], sizes, 8)
        assert int(n) == trips, kind


# --------------------------------------------------------------------------
# The model: shares, the bias, the step
# --------------------------------------------------------------------------


def _tiny(**changed):
    return build_model({"family": "afmoe", "preset": "tiny",
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


def test_the_family_builds_from_job_keys_with_a_list_of_layer_kinds():
    model, cfg = build_model({"family": "afmoe", "config": {
        "vocab_size": 64, "hidden_size": 32, "num_layers": 2, "num_dense_layers": 1,
        "num_heads": 2, "num_kv_heads": 1, "head_dim": 16, "num_experts": 8, "experts_held": 2,
        "expert_offset": 4, "layer_types": ["sliding_attention", "full_attention"]}})
    assert cfg.layer_types == (SLIDING, FULL) and cfg.held == 2
    assert AfmoeConfig().layer_types[:4] == (SLIDING, SLIDING, SLIDING, FULL)
    assert AfmoeConfig().held == 128 and len(AfmoeConfig().layer_types) == 32
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_layers=3, layer_types=("sliding_attention",))
    with pytest.raises(ValueError, match="experts_held"):
        AfmoeConfig(experts_held=8, expert_offset=121)


def test_the_bias_is_state_beside_the_parameters_and_enters_the_choice_alone(whole, ids):
    model, _, variables = whole
    assert set(variables) == {"params", STATE}
    out, stats = model.apply(variables, ids)
    zero = {**variables, STATE: jax.tree.map(jnp.zeros_like, variables[STATE])}
    out0, stats0 = model.apply(zero, ids)
    assert not np.array_equal(stats["chosen"], stats0["chosen"])  # it moves the choice
    grads = jax.grad(lambda v: model.apply(v, ids)[0].sum())(variables)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(grads[STATE]))


def test_the_shares_add_up_to_the_uncut_layer(whole, ids):
    """The routed parts of all the shares, and what every rank computes alike
    (the shared expert) counted once, are the whole layer's output."""
    import dataclasses

    from hypha_tpu.models.afmoe import _MoE, _SwiGLU

    _, cfg, variables = whole
    layer = "layers_2"
    m = jax.random.normal(jax.random.key(5), (2, 48, cfg.hidden_size))
    p, b = variables["params"][layer]["mlp"], variables[STATE][layer]["mlp"]
    uncut, stats = _MoE(cfg).apply({"params": p, STATE: b}, m)
    only_shared = _SwiGLU(cfg.moe_intermediate_size, jnp.float32).apply(
        {"params": p["shared_experts"]}, m)
    total, pairs = jnp.zeros_like(uncut), 0
    held = 2
    for rank in range(cfg.num_experts // held):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=rank * held)
        mine = {**p, **{k: p[k][rank * held:(rank + 1) * held]
                        for k in ("experts_gate", "experts_up", "experts_down")}}
        out, st = _MoE(share).apply({"params": mine, STATE: b}, m)
        total = total + (out - only_shared)
        pairs += int(st["pairs_computed"])
        assert int(st["pairs_computed"]) == int(st["pairs_routed"])
        np.testing.assert_array_equal(st["chosen"], stats["chosen"])  # routed over all, everywhere
    np.testing.assert_allclose(total + only_shared, uncut, atol=2e-5)
    assert pairs == int(stats["pairs_computed"]) == m.shape[0] * m.shape[1] * cfg.experts_per_token


def test_one_bias_update_is_the_formula():
    state = {"layers_2": {"mlp": {"expert_bias": jnp.asarray([0.1, 0.0, -0.1, 0.0])}},
             "layers_10": {"mlp": {"expert_bias": jnp.zeros(4)}}}
    chosen = jnp.asarray([[10, 2, 4, 4], [1, 1, 1, 9]])  # row 0: layers_2, row 1: layers_10
    new = update_bias(state, chosen, 0.001)
    for name, row, old in (("layers_2", [10, 2, 4, 4], [0.1, 0.0, -0.1, 0.0]),
                           ("layers_10", [1, 1, 1, 9], [0.0] * 4)):
        c = np.asarray(row, np.float64)
        d = 0.001 * np.sign(c.mean() - c)
        np.testing.assert_allclose(new[name]["mlp"]["expert_bias"], np.asarray(old) + d - d.mean(),
                                   atol=1e-7)


@pytest.mark.parametrize("family", ["afmoe", "lfm2_moe"])
def test_the_routed_step_learns_updates_the_bias_and_keeps_it_out_of_adamw(ids, family):
    """One step for every family with routed experts: the head's leaf is asked
    of the model (afmoe: ``lm_head``; lfm2_moe: the embedding, tied)."""
    import optax

    from hypha_tpu.executor.train import ROUTING_FIELDS, TrainState, make_routed_train_step

    model, cfg = build_model({"family": family, "preset": "tiny", "config": {
        "dtype": "float32", "experts_held": 2, "expert_offset": 4}})
    variables = model.init(jax.random.key(0), ids)
    assert model.head_leaf in variables["params"]
    assert ("lm_head" in variables["params"]) == (family == "afmoe")
    state = TrainState.create({"params": variables["params"]}, optax.adamw(3e-3),
                              {STATE: variables[STATE]})
    assert STATE not in str(jax.tree.structure(state.opt_state))
    step = make_routed_train_step(model, loss_chunk=16)
    losses = []
    for _ in range(8):
        before = jax.tree.map(np.asarray, state.extras)  # the step donates its state
        state, metrics = step(state, {"input_ids": ids})
        host = dict(zip(ROUTING_FIELDS, np.asarray(metrics["host"]).tolist()))
        losses.append(host["loss"])
        assert host["pairs_routed"] == host["pairs_computed"] > 0
        assert 0 < host["combines"] <= host["trips"]  # a trip a combine at the most
        assert host["loss"] == pytest.approx(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), state.extras, before)
    assert all(0 < x <= 0.002 for x in jax.tree.leaves(moved))
    assert set(state.params) == {"params"} and set(state.extras) == {STATE}
