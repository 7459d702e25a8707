"""The phi4flash family (models/phi4flash.py) and what it forced: a layer's
kind by the source's rule on its index, two tensors handed from one layer to
later ones outside the residual stream, differential attention as one call of
the attention kernel with a value twice as wide as its keys, LayerNorm, a dense
model's tied head through a chunked step of its own, and the other families'
programs left as they were."""

from __future__ import annotations

import hashlib
import logging
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hypha_tpu.models import build_model
from hypha_tpu.models.phi4flash import (
    CROSS, FULL, GMU, MAMBA, WINDOW, Phi4FlashConfig, _DiffAttention, _Mamba,
)

KINDS = (WINDOW, MAMBA, FULL, GMU, CROSS)


def _tiny(**changed):
    return build_model({"family": "phi4flash", "preset": "tiny",
                        "config": {"dtype": "float32", **changed}})


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 48)), jnp.int32)


@pytest.fixture(scope="module")
def whole(ids):
    model, cfg = _tiny()
    return model, cfg, model.init(jax.random.key(7), ids)


# --------------------------------------------------------------------------
# Which layer is of which kind
# --------------------------------------------------------------------------


def test_a_layers_kind_follows_the_sources_rule_on_its_index():
    published = Phi4FlashConfig()  # Phi-4-mini-flash-reasoning's own sizes
    kinds = published.layer_types
    assert len(kinds) == 32 and kinds[:16] == (MAMBA, WINDOW) * 8
    assert kinds[16:18] == (MAMBA, FULL) and kinds[18:] == (GMU, CROSS) * 7
    assert [kinds.count(k) for k in (MAMBA, WINDOW, FULL, GMU, CROSS)] == [9, 8, 1, 7, 7]
    assert (published.d_inner, published.dt_rank, published.d_state, published.d_conv) == (5120, 160, 16, 4)
    assert (published.head_dim, published.sliding_window) == (64, 512)
    cut = Phi4FlashConfig(layers_run=[15, 16, 17, 18, 19])
    assert cut.layer_types == KINDS and cut.layers_run == (15, 16, 17, 18, 19)
    assert Phi4FlashConfig.tiny().layer_types == KINDS
    assert Phi4FlashConfig(hidden_size=2000).dt_rank == math.ceil(2000 / 16)


@pytest.mark.parametrize("run,what", [
    ([18], "gmu layer reads source layer 16"), ([16, 19], "cross_attention layer reads source layer 17"),
    ([3, 2], "rising"), ([32], "below 32"),
])
def test_a_cut_that_leaves_out_what_a_layer_reads_is_refused(run, what):
    with pytest.raises(ValueError, match=what):
        Phi4FlashConfig(layers_run=run)


def test_the_family_builds_from_job_keys(whole):
    model, cfg = build_model({"family": "phi4flash", "config": {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 48, "num_layers": 4, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 8, "sliding_window": 4, "d_state": 2, "layers_run": [1, 2, 3]}})
    assert cfg.layer_types == (WINDOW, MAMBA, FULL) and model.head_leaf == "embed_tokens"
    with pytest.raises(TypeError):  # no list of kinds is taken: the rule gives them
        build_model({"family": "phi4flash", "preset": "tiny", "config": {"layer_types": ["mamba"]}})
    p = whole[2]["params"]
    assert set(p) == {"embed_tokens", "final_layernorm"} | {f"layers_{i}" for i in range(5)}
    mixers = [set(p[f"layers_{i}"]) - {"input_layernorm", "post_attention_layernorm", "mlp"} for i in range(5)]
    assert mixers == [{"attn"}, {"mamba"}, {"attn"}, {"gmu"}, {"attn"}]
    assert "Wqkv" in p["layers_0"]["attn"] and "Wqkv" in p["layers_2"]["attn"]
    assert set(p["layers_4"]["attn"]) == {  # a query projection alone
        "Wq", "out_proj", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"}
    assert set(p["layers_0"]["input_layernorm"]) == {"scale", "bias"}  # LayerNorm, not RMSNorm
    assert "bias" in p["layers_1"]["mamba"]["dt_proj"] and "bias" not in p["layers_1"]["mamba"]["in_proj"]
    assert "bias" not in p["layers_0"]["mlp"]["gate_up_proj"] and "bias" in p["layers_0"]["attn"]["out_proj"]


def test_the_cells_stack_holds_the_parameters_counted_by_hand():
    model, _ = build_model({"family": "phi4flash", "config": {
        "vocab_size": 25008, "layers_run": [15, 16, 17, 18, 19], "max_seq_len": 8192}})
    shapes = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 16), jnp.int32))
    per_layer = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
                 for name, tree in shapes["params"].items()}
    norms, mlp = 10_240, 78_643_200
    assert per_layer == {
        "embed_tokens": 64_020_480, "final_layernorm": 5_120,
        "layers_0": 19_668_864 + norms + mlp, "layers_1": 41_241_600 + norms + mlp,
        "layers_2": 19_668_864 + norms + mlp, "layers_3": 26_214_400 + norms + mlp,
        "layers_4": 13_112_704 + norms + mlp}
    assert sum(per_layer.values()) == 577_199_232


# --------------------------------------------------------------------------
# The layers, each against its equations written out position by position
# --------------------------------------------------------------------------


def test_the_mamba_layer_is_the_sources_equations(whole):
    _, cfg, variables = whole
    p = jax.tree.map(np.asarray, variables["params"]["layers_1"]["mamba"])
    u = np.asarray(jax.random.normal(jax.random.key(4), (1, 20, cfg.hidden_size)))
    out, y = _Mamba(cfg).apply({"params": variables["params"]["layers_1"]["mamba"]}, jnp.asarray(u))
    x, z = np.split(u[0] @ p["in_proj"]["kernel"], 2, axis=-1)
    conv = np.zeros_like(x)
    for t in range(20):
        for j in range(4):  # tap 3 weighs the current position, tap 0 the one three back
            if t - 3 + j >= 0:
                conv[t] += p["conv_weight"][j] * x[t - 3 + j]
    silu = lambda a: a / (1 + np.exp(-a))
    x = silu(conv + p["conv_bias"])
    delta, b, c = np.split(x @ p["x_proj"]["kernel"], [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1)
    dt = np.log1p(np.exp(delta @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"]))
    a, h, want = -np.exp(p["A_log"]), np.zeros((cfg.d_inner, cfg.d_state)), []
    for t in range(20):
        h = np.exp(dt[t][:, None] * a) * h + (dt[t] * x[t])[:, None] * b[t][None, :]
        want.append(h @ c[t] + p["D"] * x[t])
    want = np.stack(want)
    np.testing.assert_allclose(y[0], want, atol=2e-5)  # the export: before the gate
    np.testing.assert_allclose(out[0], (want * silu(z)) @ p["out_proj"]["kernel"], atol=2e-5)


@pytest.mark.parametrize("kind,source", [(WINDOW, 3), (FULL, 5)])
def test_differential_attention_is_two_maps_subtracted_over_a_doubled_value(whole, kind, source):
    _, cfg, variables = whole
    layer = {WINDOW: "layers_0", FULL: "layers_2"}[kind]
    tree = variables["params"][layer]["attn"]
    rng = np.random.default_rng(5)
    tree = {**tree, "subln": jnp.asarray(rng.normal(1, 0.2, 16), jnp.float32),
            "Wqkv": {**tree["Wqkv"], "bias": jnp.asarray(rng.normal(0, 0.3, 64), jnp.float32)}}
    p = jax.tree.map(np.asarray, tree)
    s, hd = 24, cfg.head_dim
    u = np.asarray(jax.random.normal(jax.random.key(6), (1, s, cfg.hidden_size)))
    out, (k_out, v_out) = _DiffAttention(cfg, source, kind).apply({"params": tree}, jnp.asarray(u))
    qkv = u[0] @ p["Wqkv"]["kernel"] + p["Wqkv"]["bias"]
    q, k, v = qkv[:, :32].reshape(s, 4, hd), qkv[:, 32:48].reshape(s, 2, hd), qkv[:, 48:].reshape(s, 2, hd)
    np.testing.assert_allclose(k_out[0], k, atol=1e-5)  # the export: as projected
    np.testing.assert_allclose(v_out[0], v, atol=1e-5)
    init = 0.8 - 0.6 * math.exp(-0.3 * source)
    lam = math.exp(p["lambda_q1"] @ p["lambda_k1"]) - math.exp(p["lambda_q2"] @ p["lambda_k2"]) + init

    def softmax_map(qh, kh):
        scores = qh @ kh.T / math.sqrt(hd)
        for i in range(s):
            for j in range(s):
                if j > i or (kind == WINDOW and j <= i - cfg.sliding_window):
                    scores[i, j] = -np.inf
        e = np.exp(scores - scores.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    value = np.concatenate([v[:, 0], v[:, 1]], axis=-1)  # the one pair of value heads, 2 hd wide
    heads = []
    for pair in range(2):  # query heads (2 pair, 2 pair + 1); both pairs share the one key pair
        o = (softmax_map(q[:, 2 * pair], k[:, 0]) - lam * softmax_map(q[:, 2 * pair + 1], k[:, 1])) @ value
        heads.append(o / np.sqrt((o * o).mean(-1, keepdims=True) + cfg.layer_norm_eps) * p["subln"] * (1 - init))
    want = np.concatenate(heads, axis=-1) @ p["out_proj"]["kernel"] + p["out_proj"]["bias"]
    np.testing.assert_allclose(out[0], want, atol=3e-5)


def test_a_window_layer_reaches_back_its_window_and_no_further(whole):
    _, cfg, variables = whole
    op, p = _DiffAttention(cfg, 3, WINDOW), {"params": variables["params"]["layers_0"]["attn"]}
    u = jax.random.normal(jax.random.key(2), (1, 30, cfg.hidden_size))
    t = 5
    moved = op.apply(p, u.at[:, t].add(0.5))[0] - op.apply(p, u)[0]
    changed = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 1e-7)
    assert list(changed) == list(range(t, t + cfg.sliding_window))  # keys in (i - 8, i]


def test_no_layer_looks_ahead(whole, ids):
    model, _, variables = whole
    t = 20
    moved = model.apply(variables, ids.at[:, t].set((ids[:, t] + 1) % 256)) - model.apply(variables, ids)
    changed = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 1e-6)
    assert changed.min() == t and len(changed) > 10


# --------------------------------------------------------------------------
# The two hand-overs outside the residual stream
# --------------------------------------------------------------------------


def _grads_with(variables, model, ids, silenced: tuple):
    """Gradients of the logits' sum with one projection's output made zero."""
    params = variables["params"]
    layer, mixer, proj = silenced
    zeroed = jax.tree.map(jnp.zeros_like, params[layer][mixer][proj])
    cut = {**params, layer: {**params[layer], mixer: {**params[layer][mixer], proj: zeroed}}}
    return jax.grad(lambda p: model.apply({"params": p}, ids).sum())(cut)


def test_the_gmu_reads_the_scans_output_and_gradients_flow_back_through_it(whole, ids):
    """With the Mamba layer's own output projection at zero it adds nothing to
    the residual stream; what it computed still reaches the GMU two layers on."""
    model, _, variables = whole
    g = _grads_with(variables, model, ids, ("layers_1", "mamba", "out_proj"))["layers_1"]["mamba"]
    for name in ("in_proj", "x_proj", "dt_proj"):
        assert float(jnp.abs(g[name]["kernel"]).max()) > 0, name
    assert float(jnp.abs(g["A_log"]).max()) > 0 and float(jnp.abs(g["D"]).max()) > 0
    # ... and only through it: with the GMU's output silenced too, nothing comes back
    p = variables["params"]
    zero = lambda tree: jax.tree.map(jnp.zeros_like, tree)
    both = {**p, "layers_1": {**p["layers_1"], "mamba": {**p["layers_1"]["mamba"], "out_proj": zero(p["layers_1"]["mamba"]["out_proj"])}},
            "layers_3": {**p["layers_3"], "gmu": {**p["layers_3"]["gmu"], "out_proj": zero(p["layers_3"]["gmu"]["out_proj"])}}}
    g = jax.grad(lambda p: model.apply({"params": p}, ids).sum())(both)["layers_1"]["mamba"]
    assert float(jnp.abs(g["in_proj"]["kernel"]).max()) == 0.0


def test_the_cross_layer_attends_to_the_full_layers_keys_and_values(whole, ids):
    """With the full layer's own output projection at zero (and its bias), its
    queries matter to nothing; its keys and values still reach the cross layer."""
    model, cfg, variables = whole
    g = _grads_with(variables, model, ids, ("layers_2", "attn", "out_proj"))["layers_2"]["attn"]["Wqkv"]["kernel"]
    q_width = cfg.num_heads * cfg.head_dim
    assert float(jnp.abs(g[:, :q_width]).max()) == 0.0
    assert float(jnp.abs(g[:, q_width:]).max()) > 0


# --------------------------------------------------------------------------
# The head, the step, the worker's set-up
# --------------------------------------------------------------------------


def test_the_logits_are_taken_against_the_embedding(whole, ids):
    model, _, variables = whole
    hidden = model.clone(with_head=False).apply(variables, ids)
    np.testing.assert_allclose(
        model.apply(variables, ids), jnp.einsum("bse,ve->bsv", hidden, variables["params"]["embed_tokens"]), atol=1e-5)


def test_the_chunked_step_is_the_unrouted_steps_loss_and_update(whole, ids):
    from hypha_tpu.executor.train import TrainState, make_chunked_train_step, make_train_step

    model, _, variables = whole
    fresh = lambda: TrainState.create(jax.tree.map(jnp.copy, variables), optax.sgd(0.1))  # an update as the gradient
    a, ma = make_chunked_train_step(model, loss_chunk=16)(fresh(), {"input_ids": ids})
    b, mb = make_train_step(model.apply)(fresh(), {"input_ids": ids})
    assert abs(float(ma["loss"]) - float(mb["loss"])) < 1e-5
    assert abs(float(ma["grad_norm"]) - float(mb["grad_norm"])) < 1e-4 * float(mb["grad_norm"])
    assert set(ma) == set(mb)  # no routing counters: the loop reads the loss alone
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_the_worker_takes_the_chunked_step_for_a_dense_model_that_names_its_head():
    src = (pathlib.Path(__file__).parent.parent / "hypha_tpu" / "executor" / "training.py").read_text()
    assert 'getattr(model, "head_leaf", None)' in src and "make_chunked_train_step(model)" in src
    for path in (pathlib.Path(__file__).parent.parent / "hypha_tpu" / "executor").glob("*.py"):
        text = path.read_text().lower()
        assert "phi4" not in text and "sambay" not in text, path  # no family's name in the executor


def test_the_worker_logs_which_operators_it_holds_and_the_scans_chunk(ids, caplog):
    import types

    from hypha_tpu.executor import training
    from hypha_tpu.ops.selective_scan import CHUNK

    spec = {"family": "phi4flash", "preset": "tiny", "seed": 0, "model_type": "causal-lm"}
    cfg = types.SimpleNamespace(model=spec, lora=None, sharding=None)
    with caplog.at_level(logging.INFO, logger="hypha.executor.training"):
        training._init_model(cfg, None, "/nonexistent", {"input_ids": np.asarray(ids)})
    assert ("operators: window_attention=1 mamba=1 full_attention=1 gmu=1 cross_attention=1 "
            f"head_dim=8 value_dim=16 scan_chunk={CHUNK}\n") in caplog.text + "\n"


def test_the_model_learns_a_counting_sequence_through_the_chunked_step():
    from hypha_tpu.executor.train import TrainState, make_chunked_train_step

    model, _ = _tiny()
    seq = jnp.asarray((np.arange(48)[None] + np.array([[3], [77]])) % 256, jnp.int32)
    state = TrainState.create(model.init(jax.random.key(1), seq), optax.adamw(1e-2))
    step = make_chunked_train_step(model, loss_chunk=16)
    losses = []
    for _ in range(30):
        state, metrics = step(state, {"input_ids": seq})
        losses.append(float(metrics["loss"]))
    assert losses[0] > 5.0 and losses[-1] < 0.4 * losses[0] and np.isfinite(losses).all()


# --------------------------------------------------------------------------
# The other cells' programs are the parent's
# --------------------------------------------------------------------------

# Each family's step, lowered (StableHLO text), as commit e8de834 lowers it:
# the same script run on both trees. ``short_conv`` gave its convolution a name
# of its own (``causal_taps``), the routed step its loss (``_head_loss``), and
# set-up's ``operators:`` line learnt a second size; the cells that are there
# run these programs and they must not move. (afmoe's and lfm2_moe's are the
# programs since PR 52, which changed for every routed family on purpose how the
# grouped product's rows go onto the tokens and how the router makes the pairs'
# weights, as PR 51 had changed the backward walk; mistral's is e8de834's.)
STEPS_AT_THE_PARENT = {
    "afmoe": "61505bc922d89977289981fc321b233fd02ca26af46d7748639bf0b2e53508f9",
    "lfm2_moe": "db2b81830a3dcfe5e3beb6d164699c9ecac4dbb901a3aa7f59d6795f2a3277e5",
    "mistral": "1ce07af37cee0bec6bbbc61e1a738285162c89baa436722008662b6367f6ad5d",
}
MISTRAL = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128, "num_layers": 2,
           "num_heads": 4, "num_kv_heads": 2, "sliding_window": 32}


@pytest.mark.parametrize("family", sorted(STEPS_AT_THE_PARENT))
def test_the_other_families_steps_lower_to_the_program_of_the_parent_commit(family):
    from hypha_tpu.executor.train import TrainState, make_routed_train_step, make_train_step
    from hypha_tpu.models.routed import STATE

    ids = jnp.zeros((2, 64), jnp.int32)
    if family == "mistral":
        model, _ = build_model({"family": "mistral", "config": MISTRAL})
        variables = jax.eval_shape(model.init, jax.random.key(0), ids)
        state = jax.eval_shape(lambda v: TrainState.create(v, optax.adamw(1e-3)), variables)
        step = make_train_step(model.apply)
    else:
        model, _ = build_model({"family": family, "preset": "tiny"})
        variables = jax.eval_shape(model.init, jax.random.key(0), ids)
        state = jax.eval_shape(
            lambda v: TrainState.create({"params": v["params"]}, optax.adamw(1e-3), {STATE: v[STATE]}), variables)
        step = make_routed_train_step(model, loss_chunk=16)
    text = step.lower(state, {"input_ids": ids}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS_AT_THE_PARENT[family]
