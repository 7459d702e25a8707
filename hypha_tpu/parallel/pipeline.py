"""Pipeline parallelism: GPipe-style stage pipeline over the ``pp`` mesh axis.

TPU-native formulation (the scaling-book collective pipeline): each pp rank
holds a contiguous stack of transformer blocks; microbatch activations flow
rank→rank over ICI via ``lax.ppermute`` inside a ``lax.scan`` of
``n_micro + n_stages - 1`` ticks, all inside one ``shard_map`` — a single
compiled program, differentiable end to end (the backward pipeline is the
scan/ppermute transpose XLA derives automatically).

The reference has no pipeline engine at all (its parallelism is DiLoCo data
parallelism over torch replicas — SURVEY §2.8); this axis exists so models
deeper than one chip's HBM train across chips without resharding every
matmul the way fsdp/tp do.

Embedding/head stay OUTSIDE the shard_map in plain jit (replicated or
dp-sharded by XLA), so only the block stack pays pipeline mechanics.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = [
    "pipeline_blocks",
    "split_block_params",
    "merge_block_params",
    "make_gpt2_pp_train_step",
    "make_llama_pp_train_step",
]


def pipeline_blocks(
    block_apply: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,  # this rank's layers, stacked on axis 0
    x: jnp.ndarray,  # [B, ...] full (per-dp-shard) batch, same on all ranks
    n_micro: int,
    axis: str = "pp",
) -> jnp.ndarray:
    """Run the stacked-block pipeline. Call INSIDE shard_map over ``axis``.

    ``block_apply(layer_params, h) -> h`` applies ONE block; this rank's
    ``stage_params`` are scanned over. Returns the full output [B, ...]
    (identical on every rank after the final psum broadcast).
    """
    n_stages = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by {n_micro} microbatches")
    mb = B // n_micro
    micro = x.reshape(n_micro, mb, *x.shape[1:])
    ticks = n_micro + n_stages - 1

    def stage_run(h):
        def body(c, layer_p):
            return block_apply(layer_p, c), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    def tick(carry, t):
        recv, acc = carry
        # Rank 0 feeds microbatch t (clamped; overshoot ticks are dead
        # work that keeps the program static); other ranks consume the
        # activation that arrived from the previous rank.
        feed = jax.lax.dynamic_index_in_dim(
            micro, jnp.clip(t, 0, n_micro - 1), keepdims=False
        )
        inp = jnp.where(stage == 0, feed, recv)
        out = stage_run(inp)
        # Last rank: microbatch t-(n_stages-1) completes at tick t.
        done = jax.lax.dynamic_update_index_in_dim(
            acc, out, jnp.clip(t - (n_stages - 1), 0, n_micro - 1), 0
        )
        acc = jnp.where((stage == n_stages - 1) & (t >= n_stages - 1), done, acc)
        # Ring-shift activations to the next rank (the wrap last->0 carries
        # dead data rank 0 never reads).
        recv = jax.lax.ppermute(
            out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return (recv, acc), None

    init = (jnp.zeros_like(micro[0]), jnp.zeros_like(micro))
    (_, acc), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
    # Broadcast the finished activations from the last rank to every rank,
    # so downstream (head, loss) is replicated and grads flow back into the
    # pipeline on the last rank only.
    acc = jax.lax.psum(jnp.where(stage == n_stages - 1, acc, 0.0), axis)
    return acc.reshape(B, *x.shape[1:])


def split_block_params(params: Any, n_layers: int, prefix: str = "h_"):
    """GPT2-style param tree -> (outer_tree, blocks stacked on axis 0)."""
    inner = params.get("params", params)
    outer = {k: v for k, v in inner.items() if not k.startswith(prefix)}
    blocks = [inner[f"{prefix}{i}"] for i in range(n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return outer, stacked


def merge_block_params(outer: Any, stacked: Any, prefix: str = "h_"):
    """Inverse of split_block_params (checkpoint interop)."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    tree = dict(outer)
    for i in range(n):
        tree[f"{prefix}{i}"] = jax.tree.map(lambda x: x[i], stacked)
    return {"params": tree}


def _make_pipe(block_apply, mesh, n_micro: int, dp_axis: str):
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        lambda stacked, x: pipeline_blocks(block_apply, stacked, x, n_micro),
        mesh=mesh,
        in_specs=(P("pp"), P(dp_axis)),
        out_specs=P(dp_axis),
        check_vma=False,
    )


def _check_divisible(n_layers: int, mesh) -> None:
    pp_size = mesh.shape["pp"]
    if n_layers % pp_size:
        raise ValueError(f"{n_layers} layers not divisible by pp={pp_size}")


def make_llama_pp_train_step(cfg, mesh, n_micro: int, dp_axis: str = "dp"):
    """Pipeline-parallel train step for the Llama family (incl. the
    Mistral/Qwen2/Gemma configs): same contract as the GPT-2 builder —
    params are (outer, stacked from :func:`split_block_params` with
    prefix="layers_"), batch shards over ``dp``, blocks over ``pp``."""
    from ..executor.train import make_train_step
    from ..models.llama import _Block, _RMSNorm
    from ..ops.rope import rope_frequencies

    _check_divisible(cfg.num_layers, mesh)
    block = _Block(cfg)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    def block_apply(layer_p, h):
        return block.apply({"params": layer_p}, h, cos, sin)

    if cfg.remat:
        # Honor gradient checkpointing in the pipeline too — the large-model
        # regime is exactly where both pp and remat matter.
        block_apply = jax.checkpoint(block_apply)

    pipe = _make_pipe(block_apply, mesh, n_micro, dp_axis)
    norm = _RMSNorm(cfg.rms_eps, cfg.rms_offset)

    def apply_fn(params, ids):
        outer, stacked = params
        dtype = jnp.dtype(cfg.dtype)
        x = outer["embed_tokens"][ids].astype(dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
        h = pipe(stacked, x)
        hn = norm.apply({"params": outer["norm"]}, h)
        head = (
            outer["embed_tokens"]
            if cfg.tie_word_embeddings
            else outer["lm_head"]
        )
        return jnp.einsum("bse,ve->bsv", hn.astype(jnp.float32), head)

    return make_train_step(apply_fn)


def make_gpt2_pp_train_step(cfg, mesh, n_micro: int, dp_axis: str = "dp"):
    """Jitted pipeline-parallel train step for the GPT-2 family.

    Params are a pair ``(outer, stacked)`` from :func:`split_block_params`:
    ``outer`` (wte/wpe/ln_f) replicated, ``stacked`` sharded layer-wise over
    ``pp``. Batch shards over ``dp``. The pipelined forward plugs into
    executor.train.make_train_step as an ordinary ``apply_fn`` — the loss,
    grads, metrics and optimizer plumbing are the SAME code every other
    layout uses (the optimizer rides on TrainState.tx).
    """
    from ..executor.train import make_train_step
    from ..models.gpt2 import _Block

    block = _Block(cfg)

    def block_apply(layer_p, h):
        return block.apply({"params": layer_p}, h)

    if cfg.remat:
        block_apply = jax.checkpoint(block_apply)

    _check_divisible(cfg.n_layer, mesh)
    pipe = _make_pipe(block_apply, mesh, n_micro, dp_axis)

    import flax.linen as nn

    # The SAME flax module GPT2 uses for its final norm — parity with the
    # plain model is structural, not re-derived math.
    ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32)

    def apply_fn(params, ids):
        outer, stacked = params
        dtype = jnp.dtype(cfg.dtype)
        S = ids.shape[1]
        x = (outer["wte"][ids] + outer["wpe"][None, :S]).astype(dtype)
        h = pipe(stacked, x)
        hn = ln_f.apply({"params": outer["ln_f"]}, h)
        return jnp.einsum("bse,ve->bsv", hn.astype(jnp.float32), outer["wte"])

    return make_train_step(apply_fn)
