"""Arcee's ``afmoe`` decoder (Trinity-Mini / Nano), as one rank of an
expert-parallel deployment trains it.

The layer, as the published ``modeling_afmoe.py`` computes it:

* attention: ``q, k, v`` and a gate ``g`` projected from the normed input;
  ``q`` and ``k`` RMS-normed per head with a learned weight; a
  ``sliding_attention`` layer applies RoPE and lets position i see
  (i - window, i], a ``full_attention`` layer applies no position encoding
  and the causal mask; the attention output is multiplied by ``sigmoid(g)``
  before the output projection. No biases.
* four norms a layer: ``h += post_attn_norm(attn(input_norm(h)))``,
  ``h += post_mlp_norm(f(pre_mlp_norm(h)))``.
* ``f`` is a SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers; after them a shared expert plus the routed
  ones: ``s = sigmoid(m W_r)`` in float32 over all ``num_experts``, the top
  ``experts_per_token`` of ``s + b`` chosen (``b``, the selection bias, enters
  the choice and not the weight), the chosen scores normalised
  (``route_norm``) and scaled (``route_scale``).
* the bias is no parameter: after each optimizer step
  ``b += d - mean(d)``, ``d = load_balance_coeff * sign(mean(c) - c)``, ``c``
  the tokens each expert was chosen for in the step (:func:`update_bias`). It
  lives in the ``moe_state`` collection, beside ``params``: the train step
  keeps it out of the gradient, of AdamW and of the pseudo-gradient.

The routed layer (``_MoE``), the SwiGLU, ``STATE`` and :func:`update_bias` live
in ``routed.py`` and serve two families, this one and ``lfm2_moe.py``. The two
differ there in three numbers of their configurations: afmoe has one shared
expert, renormalises with ``route_eps`` 1e-20 and scales by ``route_scale``
2.826; lfm2_moe has no shared expert, ``route_eps`` 1e-6 and a scale of 1.

**One rank's share.** ``experts_held`` and ``expert_offset`` say which experts
are here. The router keeps its ``num_experts`` outputs and its
``experts_per_token``; this rank computes the shared expert and the part of
the routed sum that its own experts give (``ops/grouped_matmul.py``: no pair
is dropped). What the experts held elsewhere would add is left out; nothing
stands in for the other ranks or their traffic. With all experts held it is
the whole model.

Training only: there is no cached decode here yet.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.rmsnorm import rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from .llama import _RMSNorm
from .routed import STATE, _MoE, _SwiGLU, update_bias

__all__ = ["Afmoe", "AfmoeConfig", "STATE", "update_bias"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200_192
    hidden_size: int = 2048
    intermediate_size: int = 6144  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1024  # one expert's SwiGLU
    num_layers: int = 32
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128  # the router's width: every expert of the layer
    experts_per_token: int = 8
    num_shared_experts: int = 1
    # One kind per layer; empty = the published pattern, every
    # ``global_attn_every_n_layers``-th layer full and the others windowed.
    layer_types: tuple = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    route_eps: float = 1e-20  # added to the chosen scores' sum before dividing
    load_balance_coeff: float = 1e-3
    mup_enabled: bool = True  # embeddings scaled by sqrt(hidden_size)
    max_seq_len: int = 131_072
    dtype: str = "bfloat16"
    # This rank's share of each layer's experts (None = all of them).
    experts_held: int | None = None
    expert_offset: int = 0
    moe_chunk: int = 2048  # sorted pairs a trip of the grouped product

    def __post_init__(self):
        kinds = tuple(self.layer_types) or tuple(
            FULL if (i + 1) % self.global_attn_every_n_layers == 0 else SLIDING
            for i in range(self.num_layers)
        )
        object.__setattr__(self, "layer_types", kinds)  # a job's list -> hashable
        if len(kinds) != self.num_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types needs {self.num_layers} of {SLIDING!r} | {FULL!r}, got {kinds}"
            )
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError("experts_held + expert_offset exceed num_experts")

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @classmethod
    def tiny(cls) -> "AfmoeConfig":
        """CI-sized: one dense layer, then window, window, full; 8 experts."""
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_layers=4, num_dense_layers=1,
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
            experts_per_token=2, sliding_window=16, max_seq_len=128,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL), moe_chunk=32,
        )


class _Attention(nn.Module):
    config: AfmoeConfig
    kind: str
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, E = x.shape
        hd = cfg.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dtype, name=name)
        q = dense(cfg.num_heads * hd, "q_proj")(x).reshape(B, S, cfg.num_heads, hd)
        k = dense(cfg.num_kv_heads * hd, "k_proj")(x).reshape(B, S, cfg.num_kv_heads, hd)
        v = dense(cfg.num_kv_heads * hd, "v_proj")(x).reshape(B, S, cfg.num_kv_heads, hd)
        gate = dense(cfg.num_heads * hd, "gate_proj")(x)
        qn = self.param("q_norm", nn.initializers.ones, (hd,), jnp.float32)
        kn = self.param("k_norm", nn.initializers.ones, (hd,), jnp.float32)
        q = rms_norm(q, qn, cfg.rms_eps).astype(dtype)
        k = rms_norm(k, kn, cfg.rms_eps).astype(dtype)
        window = None
        if self.kind == SLIDING:  # a full layer has no position encoding
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            window = cfg.sliding_window if S > cfg.sliding_window else None
        with jax.named_scope("attention"):
            impl = self.attn_impl or dot_product_attention
            kw = {} if window is None else {"window": window}
            attn = impl(q, k, v, causal=True, **kw).reshape(B, S, cfg.num_heads * hd)
        with jax.named_scope("attention_gate"):
            attn = attn * jax.nn.sigmoid(gate)
        return dense(E, "o_proj")(attn)


class _Block(nn.Module):
    config: AfmoeConfig
    layer: int
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, h, cos, sin):
        cfg = self.config
        norm = lambda name: _RMSNorm(cfg.rms_eps, name=name)
        attn = _Attention(cfg, cfg.layer_types[self.layer], self.attn_impl, name="self_attn")(
            norm("input_layernorm")(h), cos, sin
        )
        h = h + norm("post_attention_layernorm")(attn)
        m = norm("pre_mlp_layernorm")(h)
        if self.layer < cfg.num_dense_layers:
            out, stats = _SwiGLU(cfg.intermediate_size, jnp.dtype(cfg.dtype), name="mlp")(m), None
        else:
            out, stats = _MoE(cfg, name="mlp")(m)
        return h + norm("post_mlp_layernorm")(out), stats


class Afmoe(nn.Module):
    config: AfmoeConfig = AfmoeConfig()
    attn_impl: Callable | None = None
    head_leaf: ClassVar[str] = "lm_head"  # the routed step's loss reads it
    # with_head=False returns the final hidden states for the chunked loss
    # (executor.train.chunked_causal_ce), as in llama.py.
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> tuple:
        """input_ids [B, S] -> (logits [B, S, vocab] f32 or hidden [B, S, E],
        stats). ``stats`` are the step's routing counts, stacked over the
        expert layers: ``chosen`` [layers, experts] and the scalars
        ``pairs_routed``, ``pairs_computed``, ``load_max``,
        ``tokens_elsewhere`` [layers]."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        with jax.named_scope("embed"):
            x = embed[input_ids].astype(dtype)
            if cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
        cos, sin = rope_frequencies(cfg.head_dim, input_ids.shape[1], cfg.rope_theta)
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = _Block(cfg, i, self.attn_impl, name=f"layers_{i}")(x, cos, sin)
            if stats is not None:
                per_layer.append(stats)
        stats = jax.tree.map(lambda *a: jnp.stack(a), *per_layer) if per_layer else {}
        x = _RMSNorm(cfg.rms_eps, name="norm")(x)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        if not self.with_head:
            return x, stats
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), lm_head), stats
