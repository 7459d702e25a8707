"""Liquid AI's ``lfm2_moe`` decoder (LFM2-24B-A2B, LFM2-8B-A1B), as one rank
of an expert-parallel deployment trains it.

The layer, as the published ``modeling_lfm2_moe.py`` computes it (RMSNorm
throughout, no bias anywhere):

* ``h += operator(operator_norm(h))``, then ``h += f(ffn_norm(h))``: two
  norms a layer.
* ``layer_types`` chooses the *operator*, not a kind of attention. ``conv``:
  ``[B, C, x] = split3(u W_in)``, ``y = C * conv3(B * x)`` with a depthwise
  causal convolution of ``conv_taps`` taps a channel and no activation on the
  gates (``ops/short_conv.py``), then the output projection.
  ``full_attention``: grouped-query attention, ``q`` and ``k`` RMS-normed per
  head with a learned weight, then RoPE over the whole head, causal softmax;
  no gate.
* ``f`` is a SwiGLU of ``intermediate_size`` in the first ``num_dense_layers``
  layers; after them the routed experts of ``routed.py`` with no shared
  expert: sigmoid scores, the top ``experts_per_token`` of score + bias, the
  chosen scores renormalised with ``route_eps`` 1e-6 and scaled by
  ``route_scale`` (``routed_scaling_factor``, 1).
* a final RMSNorm (the source's ``embedding_norm``) on the last layer's
  output; the head is the embedding matrix (``head_leaf``).

The source publishes no training rule for the selection bias; it is moved as
afmoe's (``routed.update_bias``, ``load_balance_coeff`` 1e-3) and kept where
afmoe's is (``routed.STATE``). One rank's share by ``experts_held`` and
``expert_offset``, as in ``afmoe.py``.

Training only: a cached decode would keep the convolution's last
``conv_taps - 1`` positions beside the key-value blocks, and does not exist.
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
from ..ops.short_conv import short_conv
from .llama import _RMSNorm
from .routed import _MoE, _SwiGLU

__all__ = ["Lfm2Moe", "Lfm2MoeConfig"]

CONV, FULL = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65_536
    hidden_size: int = 2048
    intermediate_size: int = 11_776  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1536  # one expert's SwiGLU
    num_layers: int = 40
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3  # the source's conv_L_cache
    num_experts: int = 64  # the router's width: every expert of the layer
    experts_per_token: int = 4
    # One operator kind per layer; empty = the published pattern: conv, conv,
    # full, then three conv to one full.
    layer_types: tuple = ()
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-5
    route_norm: bool = True  # norm_topk_prob
    route_scale: float = 1.0  # routed_scaling_factor
    route_eps: float = 1e-6  # added to the chosen scores' sum before dividing
    load_balance_coeff: float = 1e-3
    max_seq_len: int = 128_000
    dtype: str = "bfloat16"
    # This rank's share of each layer's experts (None = all of them).
    experts_held: int | None = None
    expert_offset: int = 0
    moe_chunk: int = 2048  # sorted pairs a trip of the grouped product

    num_shared_experts: ClassVar[int] = 0  # the family has none

    def __post_init__(self):
        kinds = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 2 else CONV for i in range(self.num_layers)
        )
        object.__setattr__(self, "layer_types", kinds)  # a job's list -> hashable
        if len(kinds) != self.num_layers or set(kinds) - {CONV, FULL}:
            raise ValueError(
                f"layer_types needs {self.num_layers} of {CONV!r} | {FULL!r}, got {kinds}"
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
    def tiny(cls) -> "Lfm2MoeConfig":
        """CI-sized: one dense conv layer, then conv, conv, full; 8 experts."""
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_layers=4, num_dense_layers=1,
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
            experts_per_token=2, max_seq_len=128,
            layer_types=(CONV, CONV, CONV, FULL), moe_chunk=32,
        )


class _ConvOperator(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        D = u.shape[-1]
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dtype, name=name)
        # fan-in of a depthwise tap is the taps of its own channel
        taps = self.param("conv_weight", nn.initializers.lecun_normal(), (cfg.conv_taps, D), jnp.float32)
        with jax.named_scope("conv_operator"):
            b, c, x = jnp.split(dense(3 * D, "in_proj")(u), 3, axis=-1)
            return dense(D, "out_proj")(short_conv(b, c, x, taps))


class _Attention(nn.Module):
    config: Lfm2MoeConfig
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
        qn = self.param("q_layernorm", nn.initializers.ones, (hd,), jnp.float32)
        kn = self.param("k_layernorm", nn.initializers.ones, (hd,), jnp.float32)
        q = apply_rope(rms_norm(q, qn, cfg.rms_eps).astype(dtype), cos, sin)
        k = apply_rope(rms_norm(k, kn, cfg.rms_eps).astype(dtype), cos, sin)
        with jax.named_scope("attention"):
            impl = self.attn_impl or dot_product_attention
            attn = impl(q, k, v, causal=True).reshape(B, S, cfg.num_heads * hd)
        return dense(E, "out_proj")(attn)


class _Block(nn.Module):
    config: Lfm2MoeConfig
    layer: int
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, h, cos, sin):
        cfg = self.config
        norm = lambda name: _RMSNorm(cfg.rms_eps, name=name)
        u = norm("operator_norm")(h)
        if cfg.layer_types[self.layer] == CONV:
            h = h + _ConvOperator(cfg, name="conv")(u)
        else:
            h = h + _Attention(cfg, self.attn_impl, name="self_attn")(u, cos, sin)
        m = norm("ffn_norm")(h)
        if self.layer < cfg.num_dense_layers:
            out, stats = _SwiGLU(cfg.intermediate_size, jnp.dtype(cfg.dtype), name="feed_forward")(m), None
        else:
            out, stats = _MoE(cfg, name="feed_forward")(m)
        return h + out, stats


class Lfm2Moe(nn.Module):
    config: Lfm2MoeConfig = Lfm2MoeConfig()
    attn_impl: Callable | None = None
    head_leaf: ClassVar[str] = "embed_tokens"  # tied: the routed step's loss reads it
    # with_head=False returns the final hidden states for the chunked loss
    # (executor.train.chunked_causal_ce), as in afmoe.py.
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> tuple:
        """input_ids [B, S] -> (logits [B, S, vocab] f32 or hidden [B, S, E],
        stats), ``stats`` as :class:`~hypha_tpu.models.afmoe.Afmoe`'s: the
        step's routing counts stacked over the expert layers."""
        cfg = self.config
        embed = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        with jax.named_scope("embed"):
            x = embed[input_ids].astype(jnp.dtype(cfg.dtype))
        cos, sin = rope_frequencies(cfg.head_dim, input_ids.shape[1], cfg.rope_theta)
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = _Block(cfg, i, self.attn_impl, name=f"layers_{i}")(x, cos, sin)
            if stats is not None:
                per_layer.append(stats)
        stats = jax.tree.map(lambda *a: jnp.stack(a), *per_layer) if per_layer else {}
        x = _RMSNorm(cfg.rms_eps, name="embedding_norm")(x)
        if not self.with_head:
            return x, stats
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), embed), stats
