"""Llama-2 family (BASELINE configs 3-4: Llama-2-7B DiLoCo fine-tune and
inference serving).

Native flax definition: RMSNorm, rotary embeddings, SwiGLU MLP,
grouped-query attention. Param tree names are chosen to map 1:1 onto HF
``LlamaForCausalLM`` checkpoints for conversion (registry). Long-context runs
shard the sequence axis and swap the attention core for the ring kernel
(hypha_tpu.ops.ring_attention) — the model takes an ``attn_impl`` hook so the
executor can lower attention onto the mesh without redefining the model.

The same module also hosts the Llama-ARCHITECTURE descendants the reference
reaches through torch AutoModel (model.py:48-123) but HF ships no Flax port
for: **Mistral** (sliding-window attention; otherwise weight-identical) and
**Qwen2** (q/k/v projection biases, optionally tied embeddings) — selected
via config fields, converted via models.convert.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp


def _accepts_kw(fn: Callable, name: str) -> bool:
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return name in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )

from ..ops.attention import dot_product_attention
from ..ops.rmsnorm import rms_norm
from ..ops.rope import apply_rope, rope_frequencies

__all__ = ["Llama", "LlamaConfig"]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    hidden_size: int = 4096
    intermediate_size: int = 11_008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Architecture toggles for Llama descendants:
    attn_bias: bool = False  # Qwen2: biases on q/k/v projections
    remat: bool = False  # gradient checkpointing per block (see gpt2.py)
    sliding_window: int | None = None  # Mistral: local attention window
    tie_word_embeddings: bool = False  # Qwen2-small/Gemma: head = embeddings
    head_dim_override: int | None = None  # Gemma: head_dim != hidden/heads
    mlp_act: str = "silu"  # "silu" (Llama) | "gelu_tanh" (Gemma GeGLU)
    rms_offset: bool = False  # Gemma RMSNorm: x * (1 + weight)
    embed_scale: bool = False  # Gemma: embeddings scaled by sqrt(hidden)
    # Qwen3: RMSNorm over each head's q/k vectors before RoPE (replaces
    # qwen2's projection biases as the attention-stability mechanism).
    qk_norm: bool = False
    # LoRA adapters (executor/lora.py): rank 0 = off. Applied as the
    # runtime two-matmul form y = xW + (xA)B·(α/r) — never materializing
    # W+ΔW, so a 7B fine-tune's grads/optimizer touch only the adapters.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ("q_proj", "v_proj")

    _LORA_SUPPORTED = frozenset({"q_proj", "k_proj", "v_proj", "o_proj"})

    def __post_init__(self):
        if self.lora_rank > 0:
            bad = set(self.lora_targets) - self._LORA_SUPPORTED
            if bad or not self.lora_targets:
                # A typo'd target would silently create ZERO adapters and
                # train nothing — fail at construction instead.
                raise ValueError(
                    f"lora_targets {sorted(bad) or '(empty)'} unsupported; "
                    f"choose from {sorted(self._LORA_SUPPORTED)}"
                )

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def from_hf(cls, d: dict, **overrides) -> "LlamaConfig":
        """Map an HF ``config.json`` dict (llama / mistral / qwen2) onto the
        native config, so real checkpoint dirs load without hand-mapping."""
        fields = dict(
            vocab_size=d.get("vocab_size", 32_000),
            hidden_size=d.get("hidden_size", 4096),
            intermediate_size=d.get("intermediate_size", 11_008),
            num_layers=d.get("num_hidden_layers", 32),
            num_heads=d.get("num_attention_heads", 32),
            num_kv_heads=d.get(
                "num_key_value_heads", d.get("num_attention_heads", 32)
            ),
            max_seq_len=d.get("max_position_embeddings", 4096),
            rope_theta=d.get("rope_theta", 10_000.0),
            rms_eps=d.get("rms_norm_eps", 1e-5),
            attn_bias=d.get("model_type") == "qwen2",
            qk_norm=d.get("model_type") == "qwen3",
            # Qwen2 configs ship a non-null sliding_window with
            # use_sliding_window=false — honor the switch (absent means
            # enabled, the Mistral convention).
            sliding_window=(
                d.get("sliding_window") if d.get("use_sliding_window", True) else None
            ),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            # Any Llama-family config may pin an explicit head_dim that
            # differs from hidden/heads (Gemma always; Mistral-NeMo-style
            # checkpoints too).
            head_dim_override=d.get("head_dim"),
        )
        if d.get("model_type") == "gemma":
            fields.update(
                mlp_act="gelu_tanh",
                rms_offset=True,
                embed_scale=True,
                # HF Gemma always ties (the field is often absent from
                # config.json but GemmaForCausalLM ties unconditionally).
                tie_word_embeddings=d.get("tie_word_embeddings", True),
            )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """CI-sized config for CPU tests (GQA exercised: 4 q heads, 2 kv)."""
        return cls(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=128,
        )

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_heads


class _RMSNorm(nn.Module):
    eps: float
    # Gemma convention: weights parameterize the DELTA from identity
    # (effective scale = 1 + weight, zero-init on disk).
    offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.offset else nn.initializers.ones
        w = self.param("weight", init, (x.shape[-1],), jnp.float32)
        return rms_norm(x, w + 1.0 if self.offset else w, self.eps)


class _Attention(nn.Module):
    config: LlamaConfig
    attn_impl: Callable | None = None
    decode: bool = False  # autoregressive serving: KV cache in the "cache"
    decode_len: int = 0  # static cache capacity (prompt + new tokens)
    # Continuous-batching pool mode: every row carries its own cache index
    # and left-pad start (executor.pool.DecodePool admits/releases rows at
    # token boundaries, so rows sit at different positions).
    per_row_decode: bool = False
    # Paged KV (executor.pool paged mode): kv_blocks > 0 re-layouts the
    # cache as a shared block pool addressed through a per-lane block
    # table (ops.kvcache paged mode). Attention math is unchanged — the
    # cache update hands back the same dense per-lane views.
    kv_blocks: int = 0
    kv_block_size: int = 0
    # Ragged paged attention (ops.paged_attention): skip the dense window
    # gather and attend over occupied blocks only. Default off = the
    # historical dense-gather path, bit-identical.
    ragged_attention: bool = False
    # int8 KV blocks (ops.kvcache kv_quant): "" = full-precision pools.
    kv_quant: str = ""

    def _proj(self, x, features, use_bias, dtype, name):
        """Dense projection, plus the low-rank LoRA path when enabled.

        B starts at zero so a freshly-initialized adapter is an exact
        no-op; the (xA)B form keeps autodiff low-rank — dL/dA, dL/dB
        never touch a [in, out]-shaped buffer.
        """
        cfg = self.config
        y = nn.Dense(features, use_bias=use_bias, dtype=dtype, name=name)(x)
        if cfg.lora_rank > 0 and name in cfg.lora_targets:
            r = cfg.lora_rank
            a = self.param(
                f"{name}_lora_a", nn.initializers.normal(0.02),
                (x.shape[-1], r), jnp.float32,
            )
            b = self.param(
                f"{name}_lora_b", nn.initializers.zeros, (r, features),
                jnp.float32,
            )
            y = y + ((x @ a.astype(dtype)) @ b.astype(dtype)) * (
                cfg.lora_alpha / r
            )
        return y

    @nn.compact
    def __call__(self, x, cos, sin):
        import jax

        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, E = x.shape
        hd = cfg.head_dim
        bias = cfg.attn_bias
        q = self._proj(x, cfg.num_heads * hd, bias, dtype, "q_proj")
        k = self._proj(x, cfg.num_kv_heads * hd, bias, dtype, "k_proj")
        v = self._proj(x, cfg.num_kv_heads * hd, bias, dtype, "v_proj")
        q = q.reshape(B, S, cfg.num_heads, hd)
        k = k.reshape(B, S, cfg.num_kv_heads, hd)
        v = v.reshape(B, S, cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            # Qwen3 QK-norm: per-head RMSNorm on the last (head_dim) axis,
            # BEFORE RoPE — shared by the training forward and both decode
            # paths, so cached generation matches training exactly.
            qn = self.param("q_norm", nn.initializers.ones, (hd,), jnp.float32)
            kn = self.param("k_norm", nn.initializers.ones, (hd,), jnp.float32)
            q = rms_norm(q, qn, cfg.rms_eps).astype(dtype)
            k = rms_norm(k, kn, cfg.rms_eps).astype(dtype)
        if self.decode:
            # KV-cache decoding (net-new vs the reference, which has no
            # inference path): static-shape cache + q_offset causal masking
            # — everything a lax.scan'd decode loop needs to stay one
            # compiled program. RoPE must see absolute positions, so it
            # runs against the pre-update index (read via a peek variable
            # inside update_kv_cache's offset return).
            from ..ops.kvcache import update_kv_cache

            # RoPE needs absolute positions, i.e. the cache index BEFORE
            # this step's write — the prepare hook runs against it.
            roped = {}

            if self.per_row_decode:
                # Pool rows are left-padded into their window: RoPE runs on
                # LOGICAL positions (cache index minus the row's pad
                # boundary), and attention masks keys below the boundary.
                def _rope_rows(offset, start):
                    logical = jnp.maximum(
                        offset[:, None] - start[:, None] + jnp.arange(S)[None, :],
                        0,
                    )
                    roped["q"] = apply_rope(q, cos, sin, positions=logical)
                    return (
                        apply_rope(k, cos, sin, positions=logical).astype(dtype),
                        v.astype(dtype),
                    )

                ragged = self.ragged_attention and self.kv_blocks > 0
                full_k, full_v, offset, start = update_kv_cache(
                    self, k, v, self.decode_len, prepare=_rope_rows,
                    per_row=True, blocks=self.kv_blocks,
                    block_size=self.kv_block_size,
                    kv_quant=self.kv_quant, ragged=ragged,
                )
                if ragged:
                    # full_k is the raw PagedKV pool view; attention walks
                    # the block table directly (occupancy-proportional).
                    from ..ops.paged_attention import paged_attention

                    attn = paged_attention(
                        roped["q"], full_k, blocks=self.kv_blocks,
                        block_size=self.kv_block_size, q_offset=offset,
                        k_start=start, window=cfg.sliding_window,
                    )
                else:
                    attn = dot_product_attention(
                        roped["q"], full_k, full_v, causal=True,
                        q_offset=offset, window=cfg.sliding_window,
                        k_start=start,
                    )
                attn = attn.reshape(B, S, cfg.num_heads * hd)
                return self._proj(attn, E, False, dtype, "o_proj")

            def _rope_at(offset):
                positions = jnp.broadcast_to(offset + jnp.arange(S), (B, S))
                roped["q"] = apply_rope(q, cos, sin, positions=positions)
                return (
                    apply_rope(k, cos, sin, positions=positions).astype(dtype),
                    v.astype(dtype),
                )

            full_k, full_v, offset = update_kv_cache(
                self, k, v, self.decode_len, prepare=_rope_at
            )
            q = roped["q"]
            # The window applies in decode too (positions are absolute, so
            # the band mask composes with q_offset) — cached generation must
            # match the training forward exactly for Mistral-style configs.
            attn = dot_product_attention(
                q, full_k, full_v, causal=True, q_offset=offset,
                window=cfg.sliding_window,
            )
            attn = attn.reshape(B, S, cfg.num_heads * hd)
            return self._proj(attn, E, False, dtype, "o_proj")
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        window = cfg.sliding_window
        with jax.named_scope("attention"):
            if window is not None and S > window:
                # Mistral local attention: position i sees (i-window, i].
                # The window threads through attn_impl when the kernel
                # takes one (the flash kernel does, and skips the tiles
                # outside the band); otherwise the fused-iota dense path
                # runs (the ring kernel takes none — warn, don't silently
                # alter the objective OR silently drop the installed
                # kernel).
                impl = self.attn_impl or dot_product_attention
                if _accepts_kw(impl, "window"):
                    attn = impl(q, k, v, causal=True, window=window)
                else:
                    if self.attn_impl is not None:
                        warnings.warn(
                            "sliding_window set but the installed attn_impl "
                            "takes no 'window' kwarg; using the dense windowed "
                            "path instead", stacklevel=2,
                        )
                    attn = dot_product_attention(
                        q, k, v, causal=True, window=window
                    )
            else:
                attn = (self.attn_impl or dot_product_attention)(
                    q, k, v, causal=True
                )
        attn = attn.reshape(B, S, cfg.num_heads * hd)
        return self._proj(attn, E, False, dtype, "o_proj")


class _MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        gate = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=dtype, name="gate_proj")(x)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=dtype, name="up_proj")(x)
        if cfg.mlp_act in ("gelu_tanh", "gelu"):  # Gemma GeGLU — HF ships
            # both spellings ("gelu_pytorch_tanh" maps here via from_hf;
            # older configs say "gelu" but GemmaMLP runs the tanh approx).
            act = nn.gelu(gate, approximate=True)
        elif cfg.mlp_act == "silu":
            act = nn.silu(gate)
        else:
            raise ValueError(f"unknown mlp_act {cfg.mlp_act!r} (silu | gelu_tanh)")
        return nn.Dense(x.shape[-1], use_bias=False, dtype=dtype, name="down_proj")(
            act * up
        )


class _Block(nn.Module):
    config: LlamaConfig
    attn_impl: Callable | None = None
    decode: bool = False
    decode_len: int = 0
    per_row_decode: bool = False
    kv_blocks: int = 0
    kv_block_size: int = 0
    ragged_attention: bool = False
    kv_quant: str = ""

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        x = x + _Attention(
            cfg, self.attn_impl, self.decode, self.decode_len,
            self.per_row_decode, self.kv_blocks, self.kv_block_size,
            self.ragged_attention, self.kv_quant,
            name="self_attn"
        )(_RMSNorm(cfg.rms_eps, cfg.rms_offset, name="input_layernorm")(x), cos, sin)
        x = x + _MLP(cfg, name="mlp")(
            _RMSNorm(cfg.rms_eps, cfg.rms_offset, name="post_attention_layernorm")(x)
        )
        return x


class Llama(nn.Module):
    config: LlamaConfig = LlamaConfig()
    attn_impl: Callable | None = None  # e.g. a ring-attention closure
    decode: bool = False  # serving mode: KV-cached autoregressive forward
    decode_len: int = 0
    per_row_decode: bool = False  # continuous-batching pool (executor.pool)
    # Paged KV serving (executor.pool paged mode): block-pool cache layout.
    kv_blocks: int = 0
    kv_block_size: int = 0
    # Ragged paged attention + int8 KV blocks (both default-off: the
    # dense-gather full-precision path, bit-identical to before).
    ragged_attention: bool = False
    kv_quant: str = ""
    # with_head=False returns final hidden states [B, S, E] — the
    # chunked-CE training path (executor.train.chunked_causal_ce) projects
    # to vocab inside the loss so [B, S, 32000] f32 logits never
    # materialize (0.5 GB/chip at B_local=1 S=4096; see gpt2.py). Init
    # with with_head=True so the param tree still carries lm_head.
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> jnp.ndarray:
        """input_ids [B, S] -> logits [B, S, vocab] (f32), or final hidden
        states when ``with_head=False``."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = self.param(
            "embed_tokens",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        # named_scope: the name lands in the metadata of every device event
        # of this part of the step (docs/observability.md); flax names the
        # modules (layers_N, self_attn, mlp, norm) the same way.
        with jax.named_scope("embed"):
            x = embed[input_ids].astype(dtype)
            if cfg.embed_scale:  # Gemma: inputs scaled by sqrt(hidden), in dtype
                x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
        table_len = max(cfg.max_seq_len, self.decode_len)
        cos, sin = rope_frequencies(cfg.head_dim, table_len, cfg.rope_theta)
        block_cls = nn.remat(_Block) if cfg.remat and not self.decode else _Block
        for i in range(cfg.num_layers):
            x = block_cls(
                cfg, self.attn_impl, self.decode, self.decode_len,
                self.per_row_decode, self.kv_blocks, self.kv_block_size,
                self.ragged_attention, self.kv_quant,
                name=f"layers_{i}",
            )(x, cos, sin)
        x = _RMSNorm(cfg.rms_eps, cfg.rms_offset, name="norm")(x)
        if not self.with_head:
            return x
        if cfg.tie_word_embeddings:
            lm_head = embed  # Qwen2-small convention: head shares embeddings
        else:
            lm_head = self.param(
                "lm_head",
                nn.initializers.normal(0.02),
                (cfg.vocab_size, cfg.hidden_size),
                jnp.float32,
            )
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), lm_head)
