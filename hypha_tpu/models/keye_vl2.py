"""Kwai Keye's ``KeyeVL2`` language model (Keye-VL-2.0-30B-A3B's decoder), as
one rank of an expert-parallel deployment trains it on text.

Every layer is of one kind: ``h += attn(RMSNorm(h))``, ``h += moe(RMSNorm(h))``.

* attention: ``q, k, v`` projected from the normed input ``x`` with no bias;
  ``q`` and ``k`` RMS-normed over the head with a learned weight, then RoPE
  (rotate-half, over the whole head: on text M-RoPE's three position ids are
  equal); query ``t`` attends to the picked set ``S_t`` alone, one set for all
  heads.
* the indexer, on ``stop_gradient(x)``: ``index_heads`` small query heads, one
  key head under a LayerNorm with bias, RoPE over both, and a learned weight a
  head; ``I[t, s] = sum_j a[t, j] * index_heads^-1/2 * index_head_dim^-1/2 *
  relu(qI[t, j] . kI[s])`` in float32; ``S_t`` the ``min(t + 1, index_topk)``
  largest of ``I[t, 0..t]``, ties to the earlier position, exactly
  (``ops/index_select.py``). The choice is hard and passes no gradient.
* the indexer's objective, a layer: the KL divergence from the main attention's
  distribution over ``S_t`` (mean over the heads, under ``stop_gradient``) to
  the softmax of ``I`` there, a mean over positions. The model returns it in
  ``stats["aux_loss"]`` [layers] and the routed step adds the layers' sum to
  the cross-entropy. By the two ``stop_gradient``s the indexer's leaves
  have gradient from it alone and every other leaf from the cross-entropy alone.
* the routed part (``routed.py``, ``router`` ``softmax``): ``p = softmax(m
  W_r)`` in float32 over all ``num_experts``, the ``experts_per_token``
  largest, their ``p`` divided by their sum; SwiGLU experts; no shared expert,
  and a selection bias that stays zero (``load_balance_coeff`` 0).

**One rank's share** by ``experts_held`` and ``expert_offset``, as in
``afmoe.py``. Training only: a cached decode would keep the indexer's keys
beside the key-value blocks and select inside paged attention, and does not
exist. Nothing of the source's vision tower is here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import index_select as sel_op
from ..ops.rmsnorm import rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from .llama import _RMSNorm
from .routed import _MoE

__all__ = ["KeyeVL2", "KeyeVL2Config"]

SPARSE, EXPERTS = "sparse_attention", "experts"


@dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151_936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000_000.0
    rms_eps: float = 1e-6
    # sa_config: the indexer and its tiling of the score matrix
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    q_chunk: int = 512
    kv_chunk: int = 512
    num_experts: int = 128  # the router's width: every expert of the layer
    experts_per_token: int = 8
    moe_intermediate_size: int = 768
    route_norm: bool = True  # norm_topk_prob
    max_seq_len: int = 262_144
    dtype: str = "bfloat16"
    # This rank's share of each layer's experts (None = all of them).
    experts_held: int | None = None
    expert_offset: int = 0
    # Sorted pairs a trip of the grouped product. Nothing dense stands beside
    # these experts and a rank's routers turn to the ones it holds (3 to 6 pairs
    # a token), so the walk is long: on a v5e the layer takes 36 ms at 3 pairs a
    # token in trips of 16384 where trips of 2048 take 75 (PERF.md 6, PR 50).
    moe_chunk: int = 16384

    # What routed.py and the routed step read and no key of this family sets.
    router: ClassVar[str] = "softmax"
    num_shared_experts: ClassVar[int] = 0
    route_scale: ClassVar[float] = 1.0
    route_eps: ClassVar[float] = 0.0  # the chosen probabilities are divided by their sum
    load_balance_coeff: ClassVar[float] = 0.0  # the selection bias stays zero

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads come in whole groups of a key head")
        if self.index_topk < 1:
            raise ValueError("index_topk >= 1")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError("experts_held + expert_offset exceed num_experts")

    @property
    def layer_types(self) -> tuple:
        return (SPARSE,) * self.num_layers + (EXPERTS,) * self.num_layers

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @classmethod
    def tiny(cls) -> "KeyeVL2Config":
        """CI-sized: two layers; a query keeps 16 keys, so most of 64 cut."""
        return cls(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, index_heads=4, index_head_dim=8, index_topk=16, q_chunk=32,
            kv_chunk=32, num_experts=8, experts_per_token=2, moe_intermediate_size=32,
            max_seq_len=512, moe_chunk=32,
        )


class _SparseAttention(nn.Module):
    """Attention over the keys the indexer picks; returns the output, the
    layer's KL objective and the share of the causal pairs that were picked."""

    config: KeyeVL2Config
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, x, cos, sin, icos, isin):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, E = x.shape
        hd, J, Di = cfg.head_dim, cfg.index_heads, cfg.index_head_dim
        dense = lambda n, name, **kw: nn.Dense(n, use_bias=False, dtype=dtype, name=name, **kw)
        q = dense(cfg.num_heads * hd, "q_proj")(x).reshape(B, S, cfg.num_heads, hd)
        k = dense(cfg.num_kv_heads * hd, "k_proj")(x).reshape(B, S, cfg.num_kv_heads, hd)
        v = dense(cfg.num_kv_heads * hd, "v_proj")(x).reshape(B, S, cfg.num_kv_heads, hd)
        qn = self.param("q_norm", nn.initializers.ones, (hd,), jnp.float32)
        kn = self.param("k_norm", nn.initializers.ones, (hd,), jnp.float32)
        q = apply_rope(rms_norm(q, qn, cfg.rms_eps).astype(dtype), cos, sin)
        k = apply_rope(rms_norm(k, kn, cfg.rms_eps).astype(dtype), cos, sin)

        xi = jax.lax.stop_gradient(x)
        qi = dense(J * Di, "index_q_proj")(xi).reshape(B, S, J, Di)
        ki = nn.LayerNorm(epsilon=cfg.rms_eps, dtype=dtype, name="index_k_norm")(
            dense(Di, "index_k_proj")(xi))
        qi = apply_rope(qi, icos, isin)
        ki = apply_rope(ki[:, :, None, :], icos, isin)[:, :, 0]
        # the head weights in float32, with both scale factors
        a = nn.Dense(J, use_bias=False, dtype=jnp.float32, name="index_weights_proj")(
            xi.astype(jnp.float32)) * (J**-0.5 * Di**-0.5)

        tiles = {"q_chunk": cfg.q_chunk, "kv_chunk": cfg.kv_chunk}
        # a row of the batch at a time: under vmap the choice's rare branch (a tie at the cut) would always run
        rows = lambda f, *xs: jax.tree.map(lambda *r: jnp.stack(r), *[f(*[x[b] for x in xs]) for b in range(B)])
        packed, lse_i = rows(
            lambda qi, ki, a: sel_op.index_select(qi, ki, a, topk=cfg.index_topk, **tiles),
            *jax.lax.stop_gradient((qi, ki, a)))
        scale = hd**-0.5
        if self.attn_impl is None:
            o, lse = sel_op.masked_attention(q, k, v, packed, scale)
        else:
            o, lse = self.attn_impl(q, k, v, causal=True, selection=packed)
        with jax.named_scope("index_kl"):
            kl = rows(
                lambda *args: sel_op.index_kl(*args, scale, cfg.q_chunk, cfg.kv_chunk),
                qi, ki, a, *jax.lax.stop_gradient((q, k, lse)), packed, lse_i)
            kl = kl.sum() / (B * S)
            picked = jnp.sum(jax.lax.population_count(packed), dtype=jnp.int32)
            share = picked.astype(jnp.float32) / (B * (S * (S + 1) // 2))
        return dense(E, "o_proj")(o.reshape(B, S, cfg.num_heads * hd)), kl, share


class _Block(nn.Module):
    config: KeyeVL2Config
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, h, ropes):
        cfg = self.config
        norm = lambda name: _RMSNorm(cfg.rms_eps, name=name)
        with jax.named_scope(SPARSE):
            attn, kl, share = _SparseAttention(cfg, self.attn_impl, name="self_attn")(
                norm("input_layernorm")(h), *ropes)
        h = h + attn
        out, stats = _MoE(cfg, name="mlp")(norm("post_attention_layernorm")(h))
        return h + out, {**stats, "aux_loss": kl, "keys_picked_share": share}


class KeyeVL2(nn.Module):
    config: KeyeVL2Config = KeyeVL2Config()
    attn_impl: Callable | None = None
    head_leaf: ClassVar[str] = "lm_head"  # the routed step's loss reads it
    aux_name: ClassVar[str] = "index_kl"  # what the step's auxiliary loss is, on the round's line
    aux_fields: ClassVar[tuple] = ("keys_picked_share",)  # per-layer stats that ride beside it, averaged
    # with_head=False returns the final hidden states for the chunked loss
    # (executor.train.chunked_causal_ce), as in afmoe.py.
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> tuple:
        """input_ids [B, S] -> (logits [B, S, vocab] f32 or hidden [B, S, E],
        stats), ``stats`` as :class:`~hypha_tpu.models.afmoe.Afmoe`'s, stacked
        over the layers, and two more: ``aux_loss`` [layers], each layer's
        indexer objective, and ``keys_picked_share`` [layers], picked pairs
        over causal pairs."""
        cfg = self.config
        # Unit variance, the scale of the blocks' unit-gain branches: at 0.02 the
        # attention's mean over keys swamps a token's own part by the second
        # layer and every token of a layer routes alike (PERF.md 6, PR 50).
        embed = self.param(
            "embed_tokens", nn.initializers.normal(1.0),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        with jax.named_scope("embed"):
            x = embed[input_ids].astype(jnp.dtype(cfg.dtype))
        S = input_ids.shape[1]
        ropes = (*rope_frequencies(cfg.head_dim, S, cfg.rope_theta),
                 *rope_frequencies(cfg.index_head_dim, S, cfg.rope_theta))
        per_layer = []
        for i in range(cfg.num_layers):
            x, stats = _Block(cfg, self.attn_impl, name=f"layers_{i}")(x, ropes)
            per_layer.append(stats)
        stats = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
        x = _RMSNorm(cfg.rms_eps, name="norm")(x)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        if not self.with_head:
            return x, stats
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), lm_head), stats
