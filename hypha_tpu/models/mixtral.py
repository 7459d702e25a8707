"""Mixtral-style sparse MoE decoder (BASELINE config 5: Mixtral-8x7B
8-replica DiLoCo).

The reference can only load Mixtral as a plain HF causal-LM inside one
Accelerate process (executors/accelerate/.../model.py:54-55) — no expert
parallelism. TPU-native design here: experts live in stacked parameter
tensors with a leading expert axis, tokens are dispatched with static-shape
one-hot capacity routing (einsum dispatch/combine — the standard TPU MoE
formulation: everything is a large batched matmul on the MXU, no dynamic
shapes), and the expert axis shards over the mesh's ``ep`` dimension so XLA
lowers dispatch/combine to all-to-alls over ICI.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import LlamaConfig, _Attention, _RMSNorm

__all__ = ["Mixtral", "MixtralConfig"]


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32_000
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    max_seq_len: int = 4096
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-5
    router_aux_coef: float = 0.02
    dtype: str = "bfloat16"
    remat: bool = False  # gradient checkpointing per block (see gpt2.py)
    # Drop-free TRAINING (serving decode is always dropless): every token
    # reaches its top-k experts at E/K x the expert FLOPs — reachable from
    # job specs via {"config": {"dropless": true}}, so the capacity-vs-
    # dropless fidelity tradeoff is an operator choice, not
    # a code edit.
    dropless: bool = False

    @classmethod
    def mixtral_8x7b(cls) -> "MixtralConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "MixtralConfig":
        return cls(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            num_experts=4,
            experts_per_token=2,
            max_seq_len=128,
        )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def as_llama(self) -> LlamaConfig:
        """Attention sublayer config (Mixtral reuses the Llama attention)."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta,
            rms_eps=self.rms_eps,
            dtype=self.dtype,
        )


class MoELayer(nn.Module):
    """Top-k routed expert MLP with static capacity dispatch.

    Returns (output, aux_loss) where aux_loss is the standard load-balancing
    loss (mean fraction-routed × mean router-prob per expert × num_experts).
    """

    config: MixtralConfig
    # Drop-free routing: every token reaches its top-k experts, no capacity
    # truncation — the SERVING semantics (decode mode uses it so cached
    # generation is exact for any router load), at E/K x the expert FLOPs.
    # Training keeps the capacity path (static shapes, bounded expert work).
    dropless: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> tuple:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        E, K = cfg.num_experts, cfg.experts_per_token
        C = max(1, math.ceil(S * K * cfg.capacity_factor / E))  # per-expert capacity

        router = nn.Dense(E, use_bias=False, dtype=jnp.float32, name="gate")
        logits = router(x.astype(jnp.float32))  # [B, S, E]
        probs = jax.nn.softmax(logits, axis=-1)

        # top-k selection; renormalize the kept weights (Mixtral semantics)
        top_w, top_idx = jax.lax.top_k(probs, K)  # [B, S, K]
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        if self.dropless:
            w_gate = self.param(
                "w_gate", nn.initializers.normal(0.02),
                (E, D, cfg.intermediate_size), jnp.float32,
            )
            w_up = self.param(
                "w_up", nn.initializers.normal(0.02),
                (E, D, cfg.intermediate_size), jnp.float32,
            )
            w_down = self.param(
                "w_down", nn.initializers.normal(0.02),
                (E, cfg.intermediate_size, D), jnp.float32,
            )
            # Every expert sees every token; combine weights zero out the
            # non-selected ones. Exact regardless of router load.
            h = nn.silu(jnp.einsum("bsd,edf->ebsf", x, w_gate.astype(dtype)))
            h = h * jnp.einsum("bsd,edf->ebsf", x, w_up.astype(dtype))
            out_all = jnp.einsum("ebsf,efd->ebsd", h, w_down.astype(dtype))
            onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B,S,K,E]
            combine_e = jnp.einsum("bsk,bske->bse", top_w, onehot).astype(dtype)
            out = jnp.einsum("bse,ebsd->bsd", combine_e, out_all)
            frac_routed = jnp.mean(onehot.sum(2), axis=(0, 1))
            mean_prob = jnp.mean(probs, axis=(0, 1))
            aux = cfg.router_aux_coef * E * jnp.sum(frac_routed * mean_prob)
            return out, aux

        # position-in-expert via cumulative count over the sequence; tokens
        # beyond capacity are dropped (static shapes — TPU-friendly)
        onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B, S, K, E]
        pos = jnp.cumsum(onehot.reshape(B, S * K, E), axis=1).reshape(B, S, K, E) - onehot
        keep = (pos < C) * onehot  # [B, S, K, E]
        # Observability for the capacity-routing fidelity question:
        # fraction of (token, expert-slot) assignments dropped
        # this step. Recorded only when callers apply with
        # mutable=["intermediates"] — zero cost in the jitted train step.
        self.sow(
            "intermediates", "drop_frac",
            1.0 - keep.sum() / jnp.maximum(onehot.sum(), 1.0),
        )
        pos_cap = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)  # [B, S, K, E, C]
        dispatch = jnp.einsum("bske,bskec->bsec", keep, pos_cap)  # [B, S, E, C]
        combine = jnp.einsum("bsk,bske,bskec->bsec", top_w, keep, pos_cap)

        # dispatch -> [B, E, C, D] expert batches; single stacked matmuls
        expert_in = jnp.einsum("bsec,bsd->becd", dispatch.astype(dtype), x)
        w_gate = self.param(
            "w_gate", nn.initializers.normal(0.02), (E, D, cfg.intermediate_size), jnp.float32
        )
        w_up = self.param(
            "w_up", nn.initializers.normal(0.02), (E, D, cfg.intermediate_size), jnp.float32
        )
        w_down = self.param(
            "w_down", nn.initializers.normal(0.02), (E, cfg.intermediate_size, D), jnp.float32
        )
        h = nn.silu(jnp.einsum("becd,edf->becf", expert_in, w_gate.astype(dtype)))
        h = h * jnp.einsum("becd,edf->becf", expert_in, w_up.astype(dtype))
        expert_out = jnp.einsum("becf,efd->becd", h, w_down.astype(dtype))
        out = jnp.einsum("bsec,becd->bsd", combine.astype(dtype), expert_out)

        # load-balancing auxiliary loss
        frac_routed = jnp.mean(keep.sum(2), axis=(0, 1))  # [E]
        mean_prob = jnp.mean(probs, axis=(0, 1))  # [E]
        aux = cfg.router_aux_coef * E * jnp.sum(frac_routed * mean_prob)
        return out, aux


class _MoEBlock(nn.Module):
    config: MixtralConfig
    attn_impl: Callable | None = None
    decode: bool = False  # KV-cached serving (the shared llama attention)
    decode_len: int = 0
    dropless: bool = False  # drop-free MoE routing (see MoELayer)
    per_row_decode: bool = False  # continuous-batching pool (executor.pool)
    kv_blocks: int = 0  # paged KV serving (executor.pool paged mode)
    kv_block_size: int = 0
    ragged_attention: bool = False  # occupancy-proportional paged attention
    kv_quant: str = ""  # int8 KV blocks ("" = full precision)

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        lcfg = cfg.as_llama()
        x = x + _Attention(
            lcfg, self.attn_impl, self.decode, self.decode_len,
            self.per_row_decode, self.kv_blocks, self.kv_block_size,
            self.ragged_attention, self.kv_quant,
            name="self_attn"
        )(_RMSNorm(cfg.rms_eps, name="input_layernorm")(x), cos, sin)
        moe_out, aux = MoELayer(
            cfg,
            dropless=self.decode or self.dropless or cfg.dropless,
            name="moe",
        )(
            _RMSNorm(cfg.rms_eps, name="post_attention_layernorm")(x)
        )
        return x + moe_out, aux


class Mixtral(nn.Module):
    config: MixtralConfig = MixtralConfig()
    attn_impl: Callable | None = None
    decode: bool = False  # serving mode: KV-cached autoregressive forward
    decode_len: int = 0
    dropless: bool = False  # drop-free routing in the plain forward too
    per_row_decode: bool = False  # continuous-batching pool (executor.pool)
    kv_blocks: int = 0  # paged KV serving (executor.pool paged mode)
    kv_block_size: int = 0
    ragged_attention: bool = False  # occupancy-proportional paged attention
    kv_quant: str = ""  # int8 KV blocks ("" = full precision)
    # with_head=False returns (hidden [B, S, E], aux) for the chunked-CE
    # training path (see llama.py / gpt2.py).
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> tuple:
        """input_ids [B, S] -> (logits [B, S, vocab] f32, aux_loss scalar),
        or (hidden, aux) when ``with_head=False``."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        from ..ops.rope import rope_frequencies

        embed = self.param(
            "embed_tokens",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        x = embed[input_ids].astype(dtype)
        table_len = max(cfg.max_seq_len, self.decode_len)
        cos, sin = rope_frequencies(cfg.head_dim, table_len, cfg.rope_theta)
        aux_total = 0.0
        block_cls = (
            nn.remat(_MoEBlock) if cfg.remat and not self.decode else _MoEBlock
        )
        for i in range(cfg.num_layers):
            x, aux = block_cls(
                cfg, self.attn_impl, self.decode, self.decode_len,
                self.dropless, self.per_row_decode, self.kv_blocks,
                self.kv_block_size, self.ragged_attention, self.kv_quant,
                name=f"layers_{i}",
            )(x, cos, sin)
            aux_total = aux_total + aux
        x = _RMSNorm(cfg.rms_eps, name="norm")(x)
        if not self.with_head:
            return x, aux_total
        lm_head = self.param(
            "lm_head",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), lm_head), aux_total
