"""Microsoft's ``phi4flash`` decoder (Phi-4-mini-flash-reasoning): SambaY, a
self-decoder of Mamba and window-attention layers, one full-attention layer,
and a cross-decoder whose layers read what two earlier layers computed
(arXiv:2507.06607), with differential attention (arXiv:2410.05258).

Every block is pre-norm residual, ``h += mixer(LN1(h))``, ``h += MLP(LN2(h))``,
LayerNorm with weight and bias; a final LayerNorm; logits against the embedding
matrix (``head_leaf``); no position encoding. ``MLP(x) = W2(u * silu(g))``,
``[g, u] = W1 x``. The kind of source layer ``i`` of ``n`` follows the source's
own rule (``Phi4FlashConfig.kind``): ``i % mb_per_layer == 0`` is a Mamba-kind
layer, any other an attention-kind layer; below ``n / 2`` they are Mamba and
window attention; layer ``n / 2`` is Mamba and exports its scan output, layer
``n / 2 + 1`` full attention and exports its keys and values; after them a
Mamba-kind layer is a GMU and an attention-kind layer cross-attention.

* **Mamba**: ``[x, z] = W_in u``; ``x = silu(conv4(x) + b)``, depthwise and
  causal (``ops/short_conv.causal_taps``, LFM2's convolution without its
  gates); ``[delta, B, C] = W_x x``; ``dt = softplus(W_dt delta + b_dt)``;
  ``A = -exp(A_log)``; the selective scan (``ops/selective_scan.py``) with
  ``D``, in float32; the export is the scan's output ``y``, before the gate;
  ``out = W_out(y * silu(z))``.
* **GMU**: ``out = W2(m * silu(W1 u))`` with ``m`` the exporting Mamba layer's
  ``y`` at the same positions.
* **Differential attention**: query and key heads are taken in adjacent pairs,
  ``(q1, q2)``, ``(k1, k2)``, and the values of a pair are one value of twice
  the head size; ``o = (softmax(q1 k1^T / sqrt(d)) - lambda softmax(q2 k2^T /
  sqrt(d))) [v1, v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` with ``i`` the source
  index; ``o = RMSNorm(o) * (1 - lambda_init)`` over the doubled head with a
  learned weight; the output projection with bias.
* **Cross-attention**: a query projection of its own; keys and values are the
  full-attention layer's, as that layer computed them.

``layers_run`` names the source layers a cut runs (empty: all of them); a
cross-decoder layer may be run only with the layer it reads from.

**Departures**: the attention kernel takes a value wider than its keys
(``ops/flash_attention.py``), so a layer is one call over the ``k1`` and ``k2``
heads of 64 with the pair's value ``[v1, v2]`` of 128 laid beside itself, and
each of the two softmax maps is computed once; the source's own flash path,
whose kernel takes one head size, makes two calls (``[v1, v1]`` then
``[v2, v2]``) and computes each map twice. The kernel gives a key head the
value head of the same index, so the value lies twice in memory, once beside
the ``k1`` heads and once beside the ``k2`` heads. The subtraction and the
norm after it keep only the kernel's output for the backward pass
(``jax.checkpoint`` on that elementwise epilogue). Weights are seeded
(``A_log``, ``D`` and ``b_dt`` as Mamba-1 publishes them, the rest the
program's initializers); no converter for published weights exists.
Training only: a cached decode would keep a scan state and a conv state
beside the key-value blocks, and does not exist.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import selective_scan as scan_op
from ..ops.attention import dot_product_attention
from ..ops.rmsnorm import rms_norm
from ..ops.short_conv import causal_taps

__all__ = ["Phi4Flash", "Phi4FlashConfig"]

WINDOW, MAMBA, FULL, GMU, CROSS = (
    "window_attention", "mamba", "full_attention", "gmu", "cross_attention")


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    hidden_size: int = 2560
    intermediate_size: int = 10_240
    num_layers: int = 32  # the source's count: it places the hinge and lambda_init
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None  # None: ceil(hidden_size / 16)
    layers_run: tuple = ()  # source indices of the layers that are run; empty: all
    max_seq_len: int = 262_144
    dtype: str = "bfloat16"

    scan_chunk: ClassVar[int] = scan_op.CHUNK  # set-up logs it; no key sets it

    @property
    def value_dim(self) -> int:
        """A pair's value, which the attention kernel is handed whole; set-up logs it."""
        return 2 * self.head_dim

    def __post_init__(self):
        run = tuple(self.layers_run) or tuple(range(self.num_layers))
        object.__setattr__(self, "layers_run", run)  # a job's list -> hashable
        if self.dt_rank is None:
            object.__setattr__(self, "dt_rank", math.ceil(self.hidden_size / 16))
        if self.num_layers % 4 or self.num_heads % 2 or self.num_kv_heads % 2:
            raise ValueError("num_layers is a multiple of 4 and heads come in pairs")
        if list(run) != sorted(set(run)) or not all(0 <= i < self.num_layers for i in run):
            raise ValueError(f"layers_run needs rising source indices below {self.num_layers}, got {run}")
        for kind, source in ((GMU, self.num_layers // 2), (CROSS, self.num_layers // 2 + 1)):
            if kind in self.layer_types and source not in run:
                raise ValueError(f"a {kind} layer reads source layer {source}, which layers_run leaves out")

    def kind(self, i: int) -> str:
        """The kind of source layer ``i``, by the source's rule."""
        hinge = self.num_layers // 2
        mamba_kind = self.mb_per_layer > 0 and i % self.mb_per_layer == 0
        if i <= hinge + 1:
            return MAMBA if mamba_kind else (FULL if i == hinge + 1 else WINDOW)
        return GMU if mamba_kind else CROSS

    @property
    def layer_types(self) -> tuple:
        return tuple(self.kind(i) for i in self.layers_run)

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @classmethod
    def tiny(cls) -> "Phi4FlashConfig":
        """CI-sized: source layers 3 to 7 of 8, every kind once."""
        return cls(
            vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=8,
            num_heads=4, num_kv_heads=2, head_dim=8, sliding_window=8, d_state=4,
            layers_run=(3, 4, 5, 6, 7), max_seq_len=128,
        )


def _dt_bias(key, shape, dtype=jnp.float32):
    """Mamba-1's: the inverse softplus of a step drawn log-uniformly in
    [0.001, 0.1] (floor 1e-4)."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype=jnp.float32):
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)


def _layer_norm(cfg, name):
    return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32, name=name)


class _Mamba(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u):
        """u [B, S, E] -> (out [B, S, E], the scan's output [B, S, d_inner] f32)."""
        cfg = self.config
        dtype, di, n = jnp.dtype(cfg.dtype), cfg.d_inner, cfg.d_state
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=dtype, name=name)
        with jax.named_scope("mamba"):
            x, z = jnp.split(dense(2 * di, "in_proj")(u), 2, axis=-1)
            # fan-in of a depthwise tap is the taps of its own channel
            taps = self.param("conv_weight", nn.initializers.lecun_normal(), (cfg.d_conv, di), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (di,), jnp.float32)
            x = jax.nn.silu(causal_taps(x, taps) + bias.astype(dtype))
            delta, b, c = jnp.split(dense(cfg.dt_rank + 2 * n, "x_proj")(x), [cfg.dt_rank, cfg.dt_rank + n], axis=-1)
            dt = nn.Dense(di, dtype=dtype, bias_init=_dt_bias, name="dt_proj")(delta)
            a_log = self.param("A_log", _a_log, (di, n), jnp.float32)
            d = self.param("D", nn.initializers.ones, (di,), jnp.float32)
            y, _ = scan_op.selective_scan(x, dt, -jnp.exp(a_log), b, c, d, dt_softplus=True)
            out = dense(u.shape[-1], "out_proj")((y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype))
        return out, y


class _Gmu(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=dtype, name=name)
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(dense(cfg.d_inner, "in_proj")(u).astype(jnp.float32))
            return dense(u.shape[-1], "out_proj")((memory * gate).astype(dtype))


class _DiffAttention(nn.Module):
    """Window, full and cross differential attention; ``source`` is the source
    layer's index (``lambda_init`` hangs on it), ``kind`` one of the three."""

    config: Phi4FlashConfig
    source: int
    kind: str
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, u, kv=None):
        """u [B, S, E], ``kv`` the exported (k, v) for a cross layer ->
        (out [B, S, E], (k, v) [B, S, kv_heads, head_dim] each)."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        batch, s, e = u.shape
        heads, kv_heads, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if self.kind == CROSS:
            q = nn.Dense(heads * hd, dtype=dtype, name="Wq")(u)
            k, v = kv
        else:
            qkv = nn.Dense((heads + 2 * kv_heads) * hd, dtype=dtype, name="Wqkv")(u)
            q, k, v = jnp.split(qkv, [heads * hd, (heads + kv_heads) * hd], axis=-1)
            k, v = (t.reshape(batch, s, kv_heads, hd) for t in (k, v))
        # adjacent pairs: [.., pair, 2, hd] -> the first of each pair, then the second
        halves = lambda t, n: t.reshape(batch, s, n // 2, 2, hd).swapaxes(2, 3).reshape(batch, s, n, hd)
        q, k12 = halves(q, heads), halves(k, kv_heads)
        # a pair's two values side by side are its one value of twice the head size
        value = v.reshape(batch, s, kv_heads // 2, 2 * hd)
        impl = self.attn_impl or dot_product_attention
        window = cfg.sliding_window if self.kind == WINDOW else None
        with jax.named_scope("attention"):
            # one call: A1 over the k1 heads and A2 over the k2 heads, each times
            # the whole [v1, v2], which is therefore laid beside itself
            o = impl(q, k12, jnp.concatenate([value, value], axis=2), causal=True, window=window)
        lam = [self.param(f"lambda_{n}", nn.initializers.normal(0.1), (hd,), jnp.float32)
               for n in ("q1", "k1", "q2", "k2")]
        subln = self.param("subln", nn.initializers.ones, (2 * hd,), jnp.float32)
        init = 0.8 - 0.6 * math.exp(-0.3 * self.source)

        def combine(o, lam, subln):
            with jax.named_scope("diff_attention"):
                full = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
                a1, a2 = jnp.split(o.astype(jnp.float32), 2, axis=2)  # [B, S, heads / 2, 2 hd] each
                o = rms_norm(a1 - full * a2, subln, cfg.layer_norm_eps) * (1.0 - init)
                return o.astype(dtype).reshape(batch, s, heads * hd)

        # The combine is elementwise over the kernel's output, which the kernel's
        # own backward pass keeps anyway: it keeps nothing else and makes its
        # float32 intermediates (20 KB a token and layer) again, as a fused
        # epilogue would.
        o = jax.checkpoint(combine)(o, lam, subln)
        return nn.Dense(e, dtype=dtype, name="out_proj")(o), (k, v)


class _Mlp(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        g, u = jnp.split(nn.Dense(2 * cfg.intermediate_size, use_bias=False, dtype=dtype, name="gate_up_proj")(x), 2, axis=-1)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=dtype, name="down_proj")(u * jax.nn.silu(g))


class _Block(nn.Module):
    config: Phi4FlashConfig
    source: int  # the layer's index in the source
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, h, memory, kv):
        """One layer; ``memory`` and ``kv`` are the two hand-overs outside the
        residual stream, passed on as they are or, by the exporting layers, set."""
        cfg = self.config
        dtype, kind = jnp.dtype(cfg.dtype), cfg.kind(self.source)
        u = _layer_norm(cfg, "input_layernorm")(h).astype(dtype)
        if kind == MAMBA:
            out, y = _Mamba(cfg, name="mamba")(u)
            if self.source == cfg.num_layers // 2:
                memory = y
        elif kind == GMU:
            out = _Gmu(cfg, name="gmu")(u, memory)
        else:
            with jax.named_scope(kind):
                out, own = _DiffAttention(cfg, self.source, kind, self.attn_impl, name="attn")(u, kv)
            if kind == FULL:
                kv = own
        h = h + out
        m = _layer_norm(cfg, "post_attention_layernorm")(h).astype(dtype)
        return h + _Mlp(cfg, name="mlp")(m), memory, kv


class Phi4Flash(nn.Module):
    config: Phi4FlashConfig = Phi4FlashConfig()
    attn_impl: Callable | None = None
    head_leaf: ClassVar[str] = "embed_tokens"  # tied: the chunked loss reads it
    # with_head=False returns the final hidden states for the chunked loss
    # (executor.train.chunked_causal_ce).
    with_head: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray) -> jnp.ndarray:
        """input_ids [B, S] -> logits [B, S, vocab] f32, or hidden [B, S, E]."""
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        with jax.named_scope("embed"):
            h = embed[input_ids].astype(dtype)
        memory = kv = None
        for n, source in enumerate(cfg.layers_run):
            h, memory, kv = _Block(cfg, source, self.attn_impl, name=f"layers_{n}")(h, memory, kv)
        h = _layer_norm(cfg, "final_layernorm")(h)
        if not self.with_head:
            return h.astype(dtype)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", h, embed)
