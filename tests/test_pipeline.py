"""Pipeline-parallelism tests (pp mesh axis, GPipe collective pipeline).

The reference has no pipeline engine (SURVEY §2.8 — DiLoCo data parallelism
only); this is the TPU-native layer-stage axis. The load-bearing property:
the pipelined forward/backward computes the SAME loss and gradients as the
plain single-program model — pipelining is an execution layout, never a
semantic change.

Runs on the virtual 8-device CPU mesh (conftest).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from hypha_tpu.executor.train import TrainState
from hypha_tpu.models import GPT2, GPT2Config
from hypha_tpu.parallel import create_mesh
from hypha_tpu.parallel.pipeline import (
    make_gpt2_pp_train_step,
    merge_block_params,
    pipeline_blocks,
    split_block_params,
)


def _tiny_cfg(n_layer=4):
    return GPT2Config(
        vocab_size=64, n_positions=32, n_embd=32, n_layer=n_layer, n_head=2,
        dtype="float32",
    )


def _ref_loss(model, params, ids):
    logits = model.apply(params, ids)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:][..., None], -1)[..., 0]
    return nll.mean()


def test_pipeline_forward_matches_plain_model():
    """pipeline_blocks over pp=4 == running the same 4-layer stack inline."""
    cfg = _tiny_cfg()
    model = GPT2(cfg)
    ids = np.random.default_rng(0).integers(0, 64, (8, 16)).astype(np.int32)
    params = model.init(jax.random.key(0), ids)
    outer, stacked = split_block_params(params["params"], cfg.n_layer)

    from hypha_tpu.models.gpt2 import _Block

    blk = _Block(cfg)

    def block_apply(p, h):
        return blk.apply({"params": p}, h)

    mesh = create_mesh({"dp": 2, "pp": 4})
    from jax.sharding import PartitionSpec as P

    pipe = jax.shard_map(
        lambda s, x: pipeline_blocks(block_apply, s, x, n_micro=2),
        mesh=mesh, in_specs=(P("pp"), P("dp")), out_specs=P("dp"),
        check_vma=False,
    )
    x = (params["params"]["wte"][ids] + params["params"]["wpe"][None, :16])
    h_pipe = np.asarray(pipe(stacked, x.astype(jnp.float32)))

    h_ref = x
    for i in range(cfg.n_layer):
        h_ref = blk.apply({"params": params["params"][f"h_{i}"]}, h_ref)
    np.testing.assert_allclose(h_pipe, np.asarray(h_ref), rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # 15-27 s each
# shim but too heavy for the tier-1 wall-clock budget; `make test` minus
# the marker filter still runs them
def test_pp_train_step_matches_plain_loss_and_grads():
    cfg = _tiny_cfg()
    model = GPT2(cfg)
    ids = np.random.default_rng(1).integers(0, 64, (8, 16)).astype(np.int32)
    jids = jnp.asarray(ids)
    params = model.init(jax.random.key(0), ids)
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: _ref_loss(model, p, jids)
    )(params)

    mesh = create_mesh({"dp": 2, "pp": 4})
    outer, stacked = split_block_params(params["params"], cfg.n_layer)
    tx = optax.adamw(1e-3)
    step = make_gpt2_pp_train_step(cfg, mesh, n_micro=2)
    state = TrainState.create(jax.tree.map(jnp.copy, (outer, stacked)), tx)
    state2, metrics = step(state, {"input_ids": jids})

    assert abs(float(metrics["loss"]) - float(loss_ref)) < 1e-5
    # Grad parity via the global norm (reduction order differs across
    # microbatches, so exact equality is not expected).
    ref_norm = float(optax.global_norm(grads_ref))
    pp_norm = float(metrics["grad_norm"])
    assert abs(pp_norm - ref_norm) / ref_norm < 1e-3

    # Training makes progress under the pipeline.
    for _ in range(10):
        state2, metrics = step(state2, {"input_ids": jids})
    assert float(metrics["loss"]) < float(loss_ref)


def test_split_merge_roundtrip():
    cfg = _tiny_cfg()
    model = GPT2(cfg)
    ids = np.ones((2, 8), np.int32)
    params = model.init(jax.random.key(0), ids)
    outer, stacked = split_block_params(params["params"], cfg.n_layer)
    merged = merge_block_params(outer, stacked)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_rejects_indivisible_shapes():
    cfg = _tiny_cfg(n_layer=3)  # 3 layers, pp=4 -> error
    mesh = create_mesh({"dp": 2, "pp": 4})
    with pytest.raises(ValueError, match="divisible"):
        make_gpt2_pp_train_step(cfg, mesh, n_micro=2)


@pytest.mark.slow  # 15-27 s each
# shim but too heavy for the tier-1 wall-clock budget; `make test` minus
# the marker filter still runs them
def test_llama_pp_train_step_matches_plain_model():
    """The Llama-family pipeline (GQA + RoPE + tied-head Gemma config)
    computes the plain model's loss."""
    from hypha_tpu.models import Llama
    from hypha_tpu.models.llama import LlamaConfig
    from hypha_tpu.parallel.pipeline import make_llama_pp_train_step

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, max_seq_len=32, dtype="float32",
        rms_offset=True, embed_scale=True, mlp_act="gelu_tanh",
        tie_word_embeddings=True,
    )
    model = Llama(cfg)
    ids = np.random.default_rng(2).integers(0, 64, (8, 16)).astype(np.int32)
    jids = jnp.asarray(ids)
    params = model.init(jax.random.key(0), ids)
    loss_ref = float(_ref_loss(model, params, jids))

    mesh = create_mesh({"dp": 2, "pp": 4})
    outer, stacked = split_block_params(params["params"], cfg.num_layers, prefix="layers_")
    step = make_llama_pp_train_step(cfg, mesh, n_micro=2)
    state = TrainState.create(
        jax.tree.map(jnp.copy, (outer, stacked)), optax.adamw(1e-3)
    )
    state, metrics = step(state, {"input_ids": jids})
    assert abs(float(metrics["loss"]) - loss_ref) < 1e-5
    for _ in range(8):
        state, metrics = step(state, {"input_ids": jids})
    assert float(metrics["loss"]) < loss_ref


@pytest.mark.slow  # 15-27 s each
# shim but too heavy for the tier-1 wall-clock budget; `make test` minus
# the marker filter still runs them
def test_pp_honors_remat():
    """cfg.remat changes nothing numerically under the pipeline either —
    both builders (GPT-2 and the Llama family's RoPE-closure block)."""
    import dataclasses

    from hypha_tpu.models import Llama
    from hypha_tpu.models.llama import LlamaConfig
    from hypha_tpu.parallel.pipeline import make_llama_pp_train_step

    mesh = create_mesh({"dp": 2, "pp": 4})
    ids = np.random.default_rng(3).integers(0, 64, (8, 16)).astype(np.int32)
    jids = jnp.asarray(ids)

    cases = [
        (GPT2, _tiny_cfg(), make_gpt2_pp_train_step, "h_", "n_layer"),
        (
            Llama,
            LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_layers=4, num_heads=4, num_kv_heads=2,
                        max_seq_len=32, dtype="float32"),
            make_llama_pp_train_step, "layers_", "num_layers",
        ),
    ]
    for cls, cfg, builder, prefix, nfield in cases:
        model = cls(cfg)
        params = model.init(jax.random.key(0), ids)
        outer, stacked = split_block_params(
            params["params"], getattr(cfg, nfield), prefix=prefix
        )
        losses = []
        for flag in (False, True):
            step = builder(dataclasses.replace(cfg, remat=flag), mesh, n_micro=2)
            state = TrainState.create(
                jax.tree.map(jnp.copy, (outer, stacked)), optax.adamw(1e-3)
            )
            _, metrics = step(state, {"input_ids": jids})
            losses.append(float(metrics["loss"]))
        assert abs(losses[0] - losses[1]) < 1e-6, cls.__name__
