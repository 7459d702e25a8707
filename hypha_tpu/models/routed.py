"""The routed expert layer that the sparse families share (``afmoe.py``,
``lfm2_moe.py``, ``nemotron_h.py``, ``keye_vl2.py``), as one rank of an expert-parallel deployment computes it,
and the selection bias that is no parameter.

``s = sigmoid(m W_r)`` in float32 over all ``num_experts``; the top
``experts_per_token`` of ``s + b`` are chosen (``b``, the selection bias,
enters the choice and not the weight); the chosen scores are normalised
(``route_norm``: ``s / (sum s + route_eps)``) and scaled (``route_scale``);
the output is the weighted sum of the chosen experts' outputs, plus a shared
expert where the family has one. The configuration is whatever dataclass the
family brings, read by these fields: ``dtype``, ``num_experts``,
``experts_per_token``, ``held``, ``expert_offset``, ``moe_intermediate_size``,
``moe_chunk``, ``num_shared_experts``, ``route_norm``, ``route_scale``,
``route_eps``, and two that a family may leave out: ``expert_form`` (``swiglu``
where absent: ``down(silu(gate x) * up x)``, three matrices; ``relu2``:
``down(relu(up x)^2)``, two matrices and no gate, for the routed experts and the
shared one alike) and ``shared_expert_intermediate_size`` (where absent the
shared expert is ``num_shared_experts`` routed experts wide), and
``residual_scale`` (where absent 1: the variance of the experts' last matrix as
a share of ``lecun_normal``'s), and ``router`` (``sigmoid`` where absent;
``softmax``: ``s = softmax(m W_r)`` over all ``num_experts``, so a chosen
expert's score depends on every other's, and ``route_norm`` divides the chosen
probabilities by their sum). afmoe has one
shared expert, ``route_eps`` 1e-20 and ``route_scale`` 2.826; lfm2_moe has no
shared expert (no ``shared_experts`` parameters and no ``shared_expert``
scope exist then), ``route_eps`` 1e-6 and ``route_scale`` 1; nemotron_h has
``relu2`` experts, a shared one twice a routed one's width, ``route_eps`` 1e-20
and ``route_scale`` 2.5; keye_vl2 has a ``softmax`` router, no shared expert,
``route_eps`` 0, ``route_scale`` 1 and a bias that never moves
(``load_balance_coeff`` 0).

The bias lives in the ``moe_state`` collection, beside ``params``: the train
step keeps it out of the gradient, of AdamW and of the pseudo-gradient, and
moves it after each optimizer step by :func:`update_bias`.

**One rank's share.** ``held`` and ``expert_offset`` say which experts are
here. The router keeps its ``num_experts`` outputs and its
``experts_per_token``; this rank computes the part of the routed sum that its
own experts give (``ops/grouped_matmul.py``: no pair is dropped). What the
experts held elsewhere would add is left out.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_experts, plan_trips, sort_pairs_weighted

__all__ = ["STATE", "chosen_scores", "update_bias"]

STATE = "moe_state"  # the variable collection of the selection biases


class _SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        act = nn.silu(dense(self.width, "gate_proj")(x)) * dense(self.width, "up_proj")(x)
        return dense(x.shape[-1], "down_proj")(act)


def _lecun(scale: float = 1.0, **kw):
    """``lecun_normal`` at ``scale`` = 1; a family that rescales its residual
    branches (``residual_scale``) draws their last matrix that much smaller."""
    return nn.initializers.variance_scaling(scale, "fan_in", "truncated_normal", **kw)


class _Relu2(nn.Module):
    """``down(relu(up x)^2)``: a feed-forward part of two matrices, no gate."""

    width: int
    dtype: jnp.dtype
    down_scale: float = 1.0  # the down projection's variance, as a share of lecun_normal's

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name, **kw: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name, **kw)
        act = jnp.square(nn.relu(dense(self.width, "up_proj")(x)))
        return dense(x.shape[-1], "down_proj", kernel_init=_lecun(self.down_scale))(act)


def chosen_scores(scores: jnp.ndarray, hot: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis(scores, idx, -1)`` by selection: ``scores`` [T, E] and
    ``hot`` [T, K, E] (``idx[..., None] == arange(E)``) -> [T, K]. One term and
    exact zeros a sum, so the same float32 value; fused elementwise passes
    forward and backward (the transpose is a dense select) where the indexed
    form gathers, and scatters back, T*K scalars at 10 ns each on a v5e."""
    return jnp.sum(jnp.where(hot, scores[:, None, :], 0), axis=-1)


class _MoE(nn.Module):
    """This rank's part of the routed sum, plus the shared expert where the
    family has one. Returns the output and the step's routing counts:
    ``chosen`` [experts] and the scalars ``pairs_routed``, ``pairs_computed``,
    ``load_max``, ``tokens_elsewhere``, and of the grouped product's walk
    ``trips``, ``combines`` and ``grad_experts`` (``ops.grouped_matmul.plan_trips``)."""

    config: Any

    @nn.compact
    def __call__(self, m):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = m.shape
        E, K, G, F = cfg.num_experts, cfg.experts_per_token, cfg.held, cfg.moe_intermediate_size
        x = m.reshape(B * S, D)
        with jax.named_scope("router"):
            w_r = self.param("router", nn.initializers.lecun_normal(), (D, E), jnp.float32)
            bias = self.variable(STATE, "expert_bias", jnp.zeros, (E,), jnp.float32).value
            squash = jax.nn.softmax if getattr(cfg, "router", "sigmoid") == "softmax" else jax.nn.sigmoid
            scores = squash(
                jnp.dot(x.astype(jnp.float32), w_r, precision=jax.lax.Precision.HIGHEST)
            )
            _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), K)
            hot = idx[..., None] == jnp.arange(E, dtype=idx.dtype)  # [T, K, E]
            w = chosen_scores(scores, hot)
            if cfg.route_norm:
                w = w / (w.sum(-1, keepdims=True) + cfg.route_eps)
            w = w * cfg.route_scale
        form = getattr(cfg, "expert_form", "swiglu")
        down_scale = getattr(cfg, "residual_scale", 1.0)
        shared = None
        if cfg.num_shared_experts:
            wide = getattr(cfg, "shared_expert_intermediate_size", None) or F * cfg.num_shared_experts
            with jax.named_scope("shared_expert"):
                if form == "relu2":
                    shared = _Relu2(wide, dtype, down_scale, name="shared_experts")(m)
                else:
                    shared = _SwiGLU(wide, dtype, name="shared_experts")(m)
        ws = tuple(
            self.param(f"experts_{name}", _lecun(down_scale if name == "down" else 1.0, batch_axis=(0,)),
                       (G, F, D) if name == "down" else (G, D, F), jnp.float32)
            for name in (("up", "down") if form == "relu2" else ("gate", "up", "down")))
        with jax.named_scope("moe_dispatch"):
            tokens, weights, sizes = sort_pairs_weighted(idx, w, cfg.expert_offset, G)
        routed = grouped_experts(
            x, tuple(w.astype(dtype) for w in ws), tokens, weights, sizes,
            form=form, chunk=cfg.moe_chunk,
        )
        with jax.named_scope("router"):
            chosen = jnp.sum(hot, axis=(0, 1), dtype=jnp.int32)
            held = (idx >= cfg.expert_offset) & (idx < cfg.expert_offset + G)
            plan = plan_trips(sizes, cfg.moe_chunk, tokens.shape[0], x.shape[0])
            stats = {
                "chosen": chosen,  # [E]: tokens each expert was chosen for
                "pairs_routed": jnp.sum(held, dtype=jnp.int32),  # by the choice
                "pairs_computed": jnp.sum(sizes),  # by what the product walked
                "load_max": jnp.max(sizes),
                "tokens_elsewhere": jnp.sum(~held.any(-1), dtype=jnp.int32),
                "trips": plan["trips"],  # of the walk, forward or backward
                "combines": plan["combines"],  # the batches their rows went onto the tokens in
                "grad_experts": plan["grad_experts"],  # whose gradient rows the backward trips added into
            }
        out = routed.reshape(B, S, D).astype(dtype)
        return (out if shared is None else shared + out), stats


def update_bias(state, chosen: jnp.ndarray, coeff: float):
    """One step of the selection bias, as torchtitan's: with ``chosen``
    [layers, experts] the tokens each expert was chosen for,
    ``d = coeff * sign(mean(c) - c)`` and ``b += d - mean(d)``, layer by
    layer. ``state`` is the ``moe_state`` collection, ``{"layers_<i>": ...}``;
    row j of ``chosen`` is the j-th expert layer's."""
    c = chosen.astype(jnp.float32)
    d = coeff * jnp.sign(c.mean(-1, keepdims=True) - c)
    d = d - d.mean(-1, keepdims=True)
    layers = sorted(state, key=lambda name: int(name.rsplit("_", 1)[1]))
    return {
        name: jax.tree.map(lambda b, row=d[j]: b + row, state[name])
        for j, name in enumerate(layers)
    }
