"""The nemotron_h family (models/nemotron_h.py) and what it forced: a block of
one norm and one part by the pattern's letter, the Mamba-2 mixer with its
grouped gated norm, attention at sixteen query heads to a key head, routed
experts of two matrices with a squared ReLU beside a shared one of another
width, one rank's share that adds up to the whole, the routed step through an
untied head, and the other families' programs left as they were."""

from __future__ import annotations

import functools
import hashlib
import logging
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hypha_tpu.models import build_model
from hypha_tpu.models.nemotron_h import EXPERTS, FULL, MAMBA2, NemotronHConfig, _Attention, _Mamba2
from hypha_tpu.models.routed import STATE, _MoE
from hypha_tpu.ops import grouped_matmul
from hypha_tpu.ops.grouped_matmul import _window, grouped_experts, plan_trips, sort_pairs

SOURCE = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _tiny(**changed):
    return build_model({"family": "nemotron_h", "preset": "tiny",
                        "config": {"dtype": "float32", **changed}})


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 48)), jnp.int32)


@pytest.fixture(scope="module")
def whole(ids):
    model, cfg = _tiny()
    return model, cfg, model.init(jax.random.key(7), ids)


# --------------------------------------------------------------------------
# Which block holds which part
# --------------------------------------------------------------------------


def test_a_blocks_part_follows_the_patterns_letter():
    published = NemotronHConfig()  # the causal tower's own sizes
    kinds = published.layer_types
    assert published.pattern == SOURCE and len(kinds) == 52
    assert [kinds.count(k) for k in (MAMBA2, EXPERTS, FULL)] == [23, 23, 6]
    assert (published.d_inner, published.ssm_state_size, published.n_groups, published.conv_kernel) == (4096, 128, 8, 4)
    assert (published.num_heads, published.num_kv_heads, published.head_dim) == (32, 2, 128)
    assert (published.expert_form, published.ssd_chunk, published.route_scale) == ("relu2", 128, 2.5)
    assert published.residual_scale == 1 / 52 and published.num_expert_layers == 23
    cut = NemotronHConfig(layers_run=[0, 1, 2, 3, 4, 5, 6], experts_held=8)
    assert cut.layer_types == (MAMBA2, EXPERTS, MAMBA2, EXPERTS, MAMBA2, FULL, EXPERTS)
    assert cut.num_expert_layers == 3 and cut.held == 8 and cut.residual_scale == 1 / 52  # the source's depth
    assert NemotronHConfig.tiny().layer_types == (MAMBA2, EXPERTS, MAMBA2, FULL, EXPERTS)


@pytest.mark.parametrize("changed,what", [
    ({"pattern": "ME-"}, "letters"), ({"layers_run": [3, 2]}, "rising"), ({"layers_run": [52]}, "below 52"),
    ({"n_groups": 5}, "whole groups"), ({"experts_held": 8, "expert_offset": 121}, "exceed"),
])
def test_a_configuration_that_cannot_be_built_is_refused(changed, what):
    with pytest.raises(ValueError, match=what):
        NemotronHConfig(**changed)


def test_a_block_is_one_norm_and_one_part(whole):
    p = whole[2]["params"]
    assert set(p) == {"embed_tokens", "lm_head", "norm_f"} | {f"layers_{i}" for i in range(5)}
    assert all(set(p[f"layers_{i}"]) == {"norm", "mixer"} for i in range(5))  # no second norm, no second part
    assert set(p["layers_0"]["mixer"]) == {"in_proj", "conv_weight", "conv_bias", "dt_bias", "A_log", "D", "norm", "out_proj"}
    assert set(p["layers_3"]["mixer"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(p["layers_1"]["mixer"]) == {"router", "experts_up", "experts_down", "shared_experts"}  # no gate
    assert set(p["layers_1"]["mixer"]["shared_experts"]) == {"up_proj", "down_proj"}
    leaves = jax.tree_util.tree_leaves_with_path(p)
    assert [jax.tree_util.keystr(k) for k, _ in leaves if "bias" in jax.tree_util.keystr(k)] == [
        "['layers_0']['mixer']['conv_bias']", "['layers_0']['mixer']['dt_bias']",
        "['layers_2']['mixer']['conv_bias']", "['layers_2']['mixer']['dt_bias']"]  # no bias but the convolution's and the step's
    assert set(whole[2][STATE]) == {"layers_1", "layers_4"}


def test_the_cells_stack_holds_the_parameters_counted_by_hand():
    model, cfg = build_model({"family": "nemotron_h", "config": {
        "vocab_size": 16384, "layers_run": [0, 1, 2, 3, 4, 5, 6], "experts_held": 8, "max_seq_len": 8192}})
    shapes = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 16), jnp.int32))
    per_layer = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
                 for name, tree in shapes["params"].items()}
    mamba, attention, experts = 38_744_896, 23_399_040, 100_125_312
    assert per_layer == {
        "embed_tokens": 44_040_192, "lm_head": 44_040_192, "norm_f": 2_688,
        "layers_0": mamba, "layers_1": experts, "layers_2": mamba, "layers_3": experts,
        "layers_4": mamba, "layers_5": attention, "layers_6": experts}
    assert sum(per_layer.values()) == 528_092_736 and len(jax.tree.leaves(shapes["params"])) == 53
    assert shapes["params"]["layers_0"]["mixer"]["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert shapes["params"]["layers_1"]["mixer"]["shared_experts"]["up_proj"]["kernel"].shape == (2688, 3712)
    assert shapes["params"]["layers_1"]["mixer"]["experts_up"].shape == (8, 2688, 1856)
    assert shapes["params"]["layers_1"]["mixer"]["router"].shape == (2688, 128)


# --------------------------------------------------------------------------
# The parts, each against its equations written out
# --------------------------------------------------------------------------


def test_the_mamba2_mixer_is_the_sources_equations_position_by_position(whole):
    _, cfg, variables = whole
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), variables["params"]["layers_0"]["mixer"])
    s, heads, hp, n, g, di = 150, 8, 8, 16, 2, 64  # longer than a chunk of 128
    u = np.asarray(jax.random.normal(jax.random.key(4), (1, s, cfg.hidden_size)), np.float64)
    out = _Mamba2(cfg).apply({"params": variables["params"]["layers_0"]["mixer"]}, jnp.asarray(u, jnp.float32))
    z, xbc, dt = np.split(u[0] @ p["in_proj"]["kernel"], [di, 2 * di + 2 * g * n], axis=-1)
    conv = np.zeros_like(xbc)
    for t in range(s):
        for j in range(4):  # tap 3 weighs the current position, tap 0 the one three back
            if t - 3 + j >= 0:
                conv[t] += p["conv_weight"][j] * xbc[t - 3 + j]
    silu = lambda a: a / (1 + np.exp(-a))
    x, b, c = np.split(silu(conv + p["conv_bias"]), [di, di + g * n], axis=-1)
    x, b, c = x.reshape(s, heads, hp), b.reshape(s, g, n), c.reshape(s, g, n)
    step = np.log1p(np.exp(dt + p["dt_bias"]))
    a = -np.exp(p["A_log"])
    y, state = np.zeros((s, heads, hp)), np.zeros((heads, hp, n))
    for t in range(s):
        for h in range(heads):
            grp = h // (heads // g)
            state[h] = np.exp(step[t, h] * a[h]) * state[h] + step[t, h] * np.outer(x[t, h], b[t, grp])
            y[t, h] = state[h] @ c[t, grp] + p["D"][h] * x[t, h]
    gated = (y.reshape(s, di) * silu(z)).reshape(s, g, di // g)  # the gate before the norm
    normed = gated / np.sqrt((gated**2).mean(-1, keepdims=True) + 1e-5)  # a group's channels
    want = (normed.reshape(s, di) * p["norm"]) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    np.testing.assert_allclose(np.exp(p["A_log"]), np.arange(1.0, 9.0), rtol=1e-6)
    assert (p["D"] == 1).all() and 0.001 <= np.log1p(np.exp(p["dt_bias"])).min()


def test_attention_is_sixteen_query_heads_to_a_key_head_with_no_position_encoding():
    cfg = NemotronHConfig(hidden_size=64, num_heads=16, num_kv_heads=1, head_dim=8, dtype="float32")
    u = jax.random.normal(jax.random.key(1), (1, 12, 64))
    layer = _Attention(cfg)
    params = layer.init(jax.random.key(2), u)
    p = jax.tree.map(np.asarray, params["params"])
    q = (np.asarray(u[0]) @ p["q_proj"]["kernel"]).reshape(12, 16, 8)
    k = np.asarray(u[0]) @ p["k_proj"]["kernel"]  # one key head for all sixteen
    v = np.asarray(u[0]) @ p["v_proj"]["kernel"]
    o = np.zeros((12, 16, 8))
    for h in range(16):
        scores = q[:, h] @ k.T / np.sqrt(8) + np.triu(np.full((12, 12), -np.inf), 1)
        w = np.exp(scores - scores.max(-1, keepdims=True))
        o[:, h] = (w / w.sum(-1, keepdims=True)) @ v
    np.testing.assert_allclose(layer.apply(params, u)[0], o.reshape(12, 128) @ p["o_proj"]["kernel"], atol=1e-5)
    assert set(p) == {"q_proj", "k_proj", "v_proj", "o_proj"} and not any("bias" in leaf for leaf in p["q_proj"])
    # shifting the sequence's start moves nothing but the mask: position enters nowhere else
    shifted = layer.apply(params, jnp.concatenate([u[:, 3:], u[:, :3]], axis=1))
    assert float(jnp.abs(shifted[0, 0] - layer.apply(params, u[:, 3:])[0, 0]).max()) < 1e-6


def _dense_experts(p, state, u, cfg, held=None, offset=0, with_shared=True):
    """The expert part written out: every token through every held expert."""
    relu2 = lambda a: np.maximum(a, 0) ** 2
    scores = 1 / (1 + np.exp(-(u @ p["router"])))
    idx = np.argsort(-(scores + state), axis=-1, kind="stable")[:, :cfg.experts_per_token]
    w = np.take_along_axis(scores, idx, axis=-1)  # without the bias
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale
    out = np.zeros_like(u)
    if with_shared:
        out += relu2(u @ p["shared_experts"]["up_proj"]["kernel"]) @ p["shared_experts"]["down_proj"]["kernel"]
    for e in range(p["experts_up"].shape[0] if held is None else held):
        mine = np.where(idx == offset + e, w, 0.0).sum(-1)
        out += mine[:, None] * (relu2(u @ p["experts_up"][e]) @ p["experts_down"][e])
    return out


def test_the_expert_part_is_squared_relu_experts_beside_a_shared_one(whole):
    _, cfg, variables = whole
    params = variables["params"]["layers_1"]["mixer"]
    bias = jnp.asarray(np.random.default_rng(5).normal(0, 0.2, 8), jnp.float32)
    u = jax.random.normal(jax.random.key(6), (1, 40, cfg.hidden_size))
    out, stats = _MoE(cfg).apply({"params": params, STATE: {"expert_bias": bias}}, u)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    want = _dense_experts(p, np.asarray(bias, np.float64), np.asarray(u[0], np.float64), cfg)
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    assert int(stats["pairs_computed"]) == int(stats["pairs_routed"]) == 40 * 2  # all held: nothing elsewhere
    assert p["shared_experts"]["up_proj"]["kernel"].shape[1] == 64 != 32 == p["experts_up"].shape[2]  # a width of its own


def test_the_sixteen_shares_add_up_to_the_uncut_models_expert_part():
    """One rank's share is tied to the model: the sixteen ranks' routed sums,
    with the shared expert counted once, are the uncut layer's output as the
    plain reference computes it."""
    from perfbench.reference import nemotron_h as reference

    uncut = NemotronHConfig(
        vocab_size=64, hidden_size=32, pattern="E", num_experts=16, experts_per_token=6,
        moe_intermediate_size=16, shared_expert_intermediate_size=24, dtype="float32", moe_chunk=64)
    u = jax.random.normal(jax.random.key(8), (1, 50, 32))
    params = _MoE(uncut).init(jax.random.key(9), u)["params"]
    bias = jnp.asarray(np.random.default_rng(2).normal(0, 0.1, 16), jnp.float32)
    whole, _ = _MoE(uncut).apply({"params": params, STATE: {"expert_bias": bias}}, u)
    shared = None
    total = jnp.zeros_like(whole)
    for rank in range(16):
        share = NemotronHConfig(**{**uncut.__dict__, "experts_held": 1, "expert_offset": rank})
        mine = {**params, "experts_up": params["experts_up"][rank:rank + 1],
                "experts_down": params["experts_down"][rank:rank + 1]}
        out, stats = _MoE(share).apply({"params": mine, STATE: {"expert_bias": bias}}, u)
        ours = out - _MoE(share).apply(  # the shared expert: what a rank gives with its routed expert's matrices zeroed
            {"params": jax.tree.map(jnp.zeros_like, mine) | {"shared_experts": mine["shared_experts"], "router": mine["router"]},
             STATE: {"expert_bias": bias}}, u)[0]
        shared = out - ours if shared is None else shared
        total = total + ours
        assert int(stats["pairs_routed"]) == int(stats["pairs_computed"])
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)
    # ... and the uncut layer is the reference's, which knows no share
    c = {"n_routed_experts": 16, "num_experts_per_tok": 6, "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    w = {"0.router": params["router"], "0.bias": bias, "0.experts_up": params["experts_up"],
         "0.experts_down": params["experts_down"], "0.up": params["shared_experts"]["up_proj"]["kernel"],
         "0.down": params["shared_experts"]["down_proj"]["kernel"]}
    theirs, _ = reference.experts(w, 0, u[0], c, reference.Plain())
    np.testing.assert_allclose(total[0] + shared[0], theirs, atol=2e-5)


@pytest.mark.parametrize("form,matrices", [("relu2", 2), ("swiglu", 3)])
def test_the_grouped_product_takes_the_expert_form_and_its_gradients_are_the_dense_loops(form, matrices):
    rng = np.random.default_rng(0)
    t, d, f, g, k = 40, 16, 24, 4, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    shapes = [(g, d, f)] * (matrices - 1) + [(g, f, d)]
    ws = tuple(jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    idx = jnp.asarray(rng.integers(0, 6, (t, k)), jnp.int32)  # experts 4 and 5 are held elsewhere
    wts = jnp.asarray(rng.random((t, k)), jnp.float32)

    def grouped(x, ws, wts):
        order, sizes = sort_pairs(idx, 0, g)
        return grouped_experts(x, ws, order // k, wts.reshape(-1)[order], sizes, form=form, chunk=16)

    def dense(x, ws, wts):
        out = jnp.zeros_like(x)
        for e in range(g):
            if form == "relu2":
                y = jnp.square(jax.nn.relu(x @ ws[0][e])) @ ws[1][e]
            else:
                y = (jax.nn.silu(x @ ws[0][e]) * (x @ ws[1][e])) @ ws[2][e]
            out += jnp.where(idx == e, wts, 0.0).sum(-1)[:, None] * y
        return out

    np.testing.assert_allclose(grouped(x, ws, wts), dense(x, ws, wts), atol=1e-4)
    probe = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(grouped(*a) * probe), argnums=(0, 1, 2))(x, ws, wts)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * probe), argnums=(0, 1, 2))(x, ws, wts)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()) + 1e-6)
    with pytest.raises(ValueError, match="matrices"):
        grouped_experts(x, ws + ws[:1], idx[:, 0], wts[:, 0], jnp.zeros((g,), jnp.int32), form=form)


# The sorted pairs of each load, as pairs of every held expert; ``held`` 8 or 16.
LOADS = {
    "even": lambda held: [80 // held] * held,
    "every_pair_on_one_expert": lambda held: [0] * 3 + [70] + [0] * (held - 4),
    # one row an expert: the chunk spans more experts than a window holds
    "nearly_empty_experts": lambda held: [1] * held,
    # empty experts inside a window and at both ends: the last rows' window is clipped
    "empty_experts_and_both_ends": lambda held: [0, 20, 0] + [0] * (held - 6) + [0, 20, 0],
    "no_pair_held": lambda held: [0] * held,
}


def _dense_body(x, ws, tokens, wts, sizes, form):
    """The plain reference: every pair through every held expert, all but its
    own thrown away; no chunk, no loop, no window."""
    ends = jnp.cumsum(sizes)
    expert = jnp.sum(ends[None, :] <= jnp.arange(tokens.shape[0])[:, None], axis=1)
    own = jax.nn.one_hot(expert, sizes.shape[0], dtype=x.dtype)  # a pair past the held ones: no expert
    through = lambda rows, w: jnp.einsum("ngd,gdf->ngf", rows, w)
    rows = jnp.broadcast_to(x[tokens][:, None], (tokens.shape[0], sizes.shape[0], x.shape[1]))
    if form == "relu2":
        act = jnp.square(jax.nn.relu(through(rows, ws[0])))
    else:
        act = jax.nn.silu(through(rows, ws[0])) * through(rows, ws[1])
    out = (through(act, ws[-1]) * own[:, :, None]).sum(1) * wts[:, None]
    return jnp.zeros_like(x).at[tokens].add(out)


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("held", [8, 16])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("load", list(LOADS))
def test_the_windowed_backward_walk_gives_the_dense_bodys_output_and_gradients(load, form, held, chunk):
    rng = np.random.default_rng(3)
    t, d, f, n = 48, 16, 24, 96
    sizes = jnp.asarray(LOADS[load](held), jnp.int32)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    shapes = [(held, d, f)] * (2 if form == "swiglu" else 1) + [(held, f, d)]
    ws = tuple(jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    tokens = jnp.asarray(rng.integers(0, t, n), jnp.int32)
    wts = jnp.asarray(rng.random(n), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)

    grouped = lambda x, ws, wts: grouped_experts(x, ws, tokens, wts, sizes, form=form, chunk=chunk)
    dense = lambda x, ws, wts: _dense_body(x, ws, tokens, wts, sizes, form)
    np.testing.assert_allclose(grouped(x, ws, wts), dense(x, ws, wts), atol=1e-4)
    got = jax.grad(lambda *a: jnp.sum(grouped(*a) * probe), argnums=(0, 1, 2))(x, ws, wts)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * probe), argnums=(0, 1, 2))(x, ws, wts)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()) + 1e-6)

    plan = plan_trips(sizes, chunk, n, x.shape[0])
    trips, windowed = int(plan["trips"]), np.asarray(plan["windowed"])[: int(plan["trips"])]
    assert trips == -(-int(sizes.sum()) // chunk) and _window(held) == 4
    assert int(plan["grad_experts"]) == int(np.where(windowed, 4, held).sum())
    if load == "nearly_empty_experts":  # one trip over all of them: no window holds it
        assert list(windowed) == [False]
    elif load == "empty_experts_and_both_ends":  # only the chunk that holds rows of both spans more
        assert sorted(windowed) == [False] + [True] * (trips - 1)
        assert int(plan["start"][trips - 1]) == held - 4  # the last rows' expert is the last but one: clipped
    elif (load, held, chunk) == ("even", 16, 32):  # five rows an expert: 32 rows span seven, seven, four
        assert list(windowed) == [False, False, True]
    else:
        assert windowed.all()


@pytest.mark.parametrize("sizes,chunk,trips,grad_experts", [
    # rows 0-15 are experts 0, 1, 2; 16-31 experts 2, 4, 5 (3 is empty); 32-42 experts 5, 6, 7
    ([5, 9, 3, 0, 11, 7, 2, 6], 16, 3, 4 + 4 + 4),
    ([1] * 8, 16, 1, 8),  # one chunk of eight experts: all held
    ([40] + [0] * 6 + [1], 16, 3, 4 + 4 + 8),  # the third chunk holds expert 0's last rows and expert 7's one
    ([6] * 16, 32, 3, 3 * 16),  # 16 held: rows 0-31 experts 0-5, 32-63 experts 5-10, 64-95 experts 10-15
    ([10] * 16, 32, 5, 5 * 4),  # rows 0-31 experts 0-3, 32-63 experts 3-6, ... 128-159 experts 12-15
    ([0] * 8, 16, 0, 0),
])
def test_the_walks_counters_are_the_trips_and_windows_counted_by_hand(sizes, chunk, trips, grad_experts):
    plan = plan_trips(jnp.asarray(sizes, jnp.int32), chunk, 256, 64)
    assert (int(plan["trips"]), int(plan["grad_experts"])) == (trips, grad_experts)


# How many of the walk's 96 sorted pairs are held, for a combine of two trips
# (``rows`` 32 or 64), and the trips and combines that makes, counted by hand.
COMBINES = {
    "fewer_rows_than_one_batch": lambda rows: 20,  # one combine, most of it masked
    "exactly_one_batch": lambda rows: rows,
    "several_batches_and_a_masked_tail": lambda rows: 70,  # 32: 32 + 32 + 6; 64: 64 + 6
    "every_pair_of_every_token": lambda rows: 96,  # the worst case: T * k / rows combines
    "no_pair_held": lambda rows: 0,
    "a_token_in_two_batches": lambda rows: 96,
}


@functools.lru_cache(maxsize=None)
def _batched_and_dense(form, held, chunk):
    """The grouped product with two trips a combine, and the dense body, jitted
    once for all loads: the routing is an argument, as the router makes it."""
    def grouped(x, ws, tokens, wts, sizes):
        return grouped_experts(x, ws, tokens, wts, sizes, form=form, chunk=chunk)

    def both(fn):
        probe = jnp.asarray(np.random.default_rng(5).normal(size=(48, 16)), jnp.float32)
        grads = jax.grad(lambda x, ws, tokens, wts, sizes: jnp.sum(fn(x, ws, tokens, wts, sizes) * probe),
                         argnums=(0, 1, 3))
        return jax.jit(lambda *a: (fn(*a), grads(*a)))

    return both(grouped), both(lambda *a: _dense_body(*a, form))


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("held", [8, 16])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("case", list(COMBINES))
def test_trips_combined_in_batches_give_the_dense_bodys_output_and_gradients(case, form, held, chunk, monkeypatch):
    rows = 2 * chunk  # of a combine; the module's own rule would give these 48 tokens a trip a combine
    monkeypatch.setattr(grouped_matmul, "_combine_rows", lambda tokens: rows)  # read while tracing
    rng = np.random.default_rng(7)
    t, d, f, n = 48, 16, 24, 96
    total = COMBINES[case](rows)
    sizes = np.full(held, total // held)
    sizes[-1] += total - sizes.sum()
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    shapes = [(held, d, f)] * (2 if form == "swiglu" else 1) + [(held, f, d)]
    ws = tuple(jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in shapes)
    tokens = rng.integers(0, t, n)  # 96 rows onto 48 tokens: a token has several rows in a batch
    if case == "a_token_in_two_batches":
        tokens[rows - 2: rows + 2] = 7  # the first batch's last rows and the second's first
    args = (x, ws, jnp.asarray(tokens, jnp.int32), jnp.asarray(rng.random(n), jnp.float32), jnp.asarray(sizes, jnp.int32))

    grouped, dense = _batched_and_dense(form, held, chunk)
    (got, got_grads), (want, want_grads) = grouped(*args), dense(*args)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()) + 1e-6)
    plan = plan_trips(args[-1], chunk, n, t)
    by_hand = {  # (chunk, case): trips, combines
        (16, "fewer_rows_than_one_batch"): (2, 1), (32, "fewer_rows_than_one_batch"): (1, 1),
        (16, "exactly_one_batch"): (2, 1), (32, "exactly_one_batch"): (2, 1),
        (16, "several_batches_and_a_masked_tail"): (5, 3), (32, "several_batches_and_a_masked_tail"): (3, 2),
        (16, "every_pair_of_every_token"): (6, 3), (32, "every_pair_of_every_token"): (3, 2),
        (16, "no_pair_held"): (0, 0), (32, "no_pair_held"): (0, 0),
        (16, "a_token_in_two_batches"): (6, 3), (32, "a_token_in_two_batches"): (3, 2),
    }
    assert (int(plan["trips"]), int(plan["combines"])) == by_hand[chunk, case]


def test_a_trip_is_a_combine_where_a_trip_is_as_large_as_a_batch_may_be(monkeypatch):
    """``combines == trips`` there, and the walk is the one without held rows."""
    sizes = jnp.asarray([5, 9, 3, 0, 11, 7, 2, 6], jnp.int32)
    for rows in (1, 16, 31):  # under two trips' rows: a batch is one trip's
        monkeypatch.setattr(grouped_matmul, "_combine_rows", lambda tokens, rows=rows: rows)
        plan = plan_trips(sizes, 16, 256, 64)
        assert (int(plan["trips"]), int(plan["combines"])) == (3, 3)
        assert grouped_matmul._batch_rows(64, 16, 256) == (16, 16)
    monkeypatch.setattr(grouped_matmul, "_combine_rows", lambda tokens: 10 ** 6)
    assert grouped_matmul._batch_rows(64, 16, 250) == (16, 256)  # no more than the padded pairs
    assert int(plan_trips(sizes, 16, 250, 64)["combines"]) == 1


# --------------------------------------------------------------------------
# Training: the routed step through the untied head, and set-up's line
# --------------------------------------------------------------------------


def test_the_routed_step_learns_and_moves_the_bias_outside_the_gradient(whole, ids):
    from hypha_tpu.executor.train import TrainState, make_routed_train_step

    model, cfg, variables = whole
    assert model.head_leaf == "lm_head"
    state = TrainState.create({"params": variables["params"]}, optax.adamw(3e-3), {STATE: variables[STATE]})
    step = make_routed_train_step(model, loss_chunk=16, donate=False)
    first = None
    for _ in range(12):
        new, metrics = step(state, {"input_ids": ids})
        first = float(metrics["loss"]) if first is None else first
        assert jax.tree.structure(new.opt_state) == jax.tree.structure(state.opt_state)
        state = new
    assert float(metrics["loss"]) < 0.8 * first
    bias = state.extras[STATE]["layers_1"]["mixer"]["expert_bias"]
    assert float(jnp.abs(bias).max()) > 0 and abs(float(bias.sum())) < 1e-6
    assert STATE not in state.params and "moe_state" not in str(jax.tree.structure(state.opt_state))
    host = np.asarray(metrics["host"])
    assert host[1] == host[2] == 2 * ids.size * cfg.experts_per_token  # two expert layers, all held: nothing dropped


def test_set_up_says_which_parts_the_model_holds_the_scans_chunk_and_the_experts_form(caplog, ids):
    from hypha_tpu.executor import training

    spec = {"family": "nemotron_h", "preset": "tiny", "seed": 3, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    with caplog.at_level(logging.INFO, logger="hypha.executor.training"):
        model, variables, causal_lm, _ = training._init_model(cfg, None, "/nonexistent", {"input_ids": np.asarray(ids)})
    assert causal_lm and set(variables) == {"params", STATE}
    assert "operators: mamba2=2 experts=2 full_attention=1 head_dim=16 ssd_chunk=128 expert_form=relu2\n" in caplog.text + "\n"


def test_no_familys_name_is_in_the_executor():
    import pathlib

    import hypha_tpu.executor as executor

    for path in pathlib.Path(executor.__file__).parent.glob("*.py"):
        text = path.read_text()
        assert "nemotron" not in text.lower() and "relu2" not in text, path.name


# Each family's step, lowered (StableHLO text), as commit f98571c lowers it: the
# same script run on both trees. The grouped product took its experts' matrices as
# a tuple and their form as a static argument, ``_MoE`` its shared expert's form
# and width from the configuration, and the routing line its count of expert
# layers from the model; the cells that are there run these programs and they
# must not move. (phi4flash's is the program since PR 48, which made its
# differential attention one call a layer with a value twice as wide as the keys:
# the same script on that tree; afmoe's and lfm2_moe's are the programs since PR 52,
# which changed for every routed family on purpose how the grouped product's rows go
# onto the tokens (in batches, ``ops/grouped_matmul.py``) and how the router makes the
# pairs' weights (``models/routed.py``), as PR 51 had changed the backward walk.
# mistral's and phi4flash's must not move: they are the proof that a program with
# no routed layer is the one it was.)
STEPS_AT_THE_PARENT = {
    "afmoe": "61505bc922d89977289981fc321b233fd02ca26af46d7748639bf0b2e53508f9",
    "lfm2_moe": "db2b81830a3dcfe5e3beb6d164699c9ecac4dbb901a3aa7f59d6795f2a3277e5",
    "mistral": "1ce07af37cee0bec6bbbc61e1a738285162c89baa436722008662b6367f6ad5d",
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
