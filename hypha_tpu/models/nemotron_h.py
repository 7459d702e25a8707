"""NVIDIA's ``nemotron_h`` decoder (the Nemotron-H family, arXiv:2504.03624:
Mamba-2, attention and mixture-of-experts layers in one stack), as one rank of
an expert-parallel deployment trains it.

**A block is one norm and one part**: ``h += part(RMSNorm(h))``. The part of
source layer ``i`` is chosen by the ``i``-th letter of ``pattern`` (the
source's ``hybrid_override_pattern``): ``M`` a Mamba-2 mixer, ``*`` attention,
``E`` the routed experts beside a shared one. No block pairs a mixer with a
feed-forward part. Then a final RMSNorm and an untied head (``head_leaf``). No
bias anywhere but the convolution's, no position encoding anywhere (the Mamba
layers carry position).

* **``M``, Mamba-2** (arXiv:2405.21060), ``d_inner = mamba_num_heads x
  mamba_head_dim``, ``G = n_groups``, ``N = ssm_state_size``: ``[z, xBC, dt] =
  W_in u`` of widths ``d_inner``, ``d_inner + 2 G N`` and one a head; ``xBC =
  silu(conv(xBC) + b)``, depthwise and causal over ``conv_kernel`` taps
  (``ops/short_conv.causal_taps``); ``[x, B, C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, one a head; the scan
  (``ops/ssd_scan.py``) ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t =
  H_t C_t + D x_t`` in float32, a head of group ``g`` reading ``B`` and ``C`` of
  ``g``; the gated norm, the gate *before* the norm and the norm over each
  group's ``d_inner / G`` channels: ``y = w * GroupRMSNorm(y * silu(z))``; ``out
  = W_out y``.
* **``*``, attention**: ``q``, ``k``, ``v`` projected, causal softmax at scale
  ``1 / sqrt(head_dim)``, ``num_heads / num_kv_heads`` query heads to a key
  head, the output projection.
* **``E``, experts** (``routed.py``, as afmoe's with ``n_group`` 1): sigmoid
  scores in float32, the top ``experts_per_token`` of score + bias, the chosen
  scores divided by their sum + ``route_eps`` and scaled by ``route_scale``;
  every expert and the shared one ``W_2 relu(W_1 u)^2`` (``expert_form``
  ``relu2``: two matrices, no gate), the shared one
  ``shared_expert_intermediate_size`` wide.

``layers_run`` names the source layers a cut runs (empty: all of them). Each
part's output projection is drawn ``1 / sqrt(len(pattern))`` smaller (the
source's ``rescale_prenorm_residual``, by the source's depth whatever the
cut). ``A_log = log(1 ... heads)``, ``D`` ones, ``dt_bias`` the inverse softplus
of a step drawn log-uniformly in [``time_step_min``, ``time_step_max``] and
floored at ``time_step_floor``, as Mamba-2 publishes. The source publishes no
training rule for the selection bias; it is moved as afmoe's
(``routed.update_bias``, ``load_balance_coeff`` 1e-3) and kept where afmoe's
is (``routed.STATE``). One rank's share by ``experts_held`` and
``expert_offset``, as in ``afmoe.py``.

Training only: a cached decode would keep a scan state and a convolution
state beside the key-value blocks, and does not exist.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import ssd_scan as scan_op
from ..ops.attention import dot_product_attention
from ..ops.short_conv import causal_taps
from .llama import _RMSNorm
from .routed import _MoE

__all__ = ["NemotronH", "NemotronHConfig"]

MAMBA2, FULL, EXPERTS = "mamba2", "full_attention", "experts"
_KINDS = {"M": MAMBA2, "*": FULL, "E": EXPERTS}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    hidden_size: int = 2688
    # The source's hybrid_override_pattern: one letter a source layer.
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    layers_run: tuple = ()  # source indices of the layers that are run; empty: all
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8  # the groups of Mamba-2 heads that share B and C
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_experts: int = 128  # the router's width: every expert of the layer
    experts_per_token: int = 6
    moe_intermediate_size: int = 1856
    shared_expert_intermediate_size: int = 3712
    num_shared_experts: int = 1
    route_norm: bool = True  # norm_topk_prob
    route_scale: float = 2.5  # routed_scaling_factor
    route_eps: float = 1e-20
    load_balance_coeff: float = 1e-3
    rms_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: str = "bfloat16"
    # This rank's share of each layer's experts (None = all of them).
    experts_held: int | None = None
    expert_offset: int = 0
    moe_chunk: int = 2048  # sorted pairs a trip of the grouped product

    expert_form: ClassVar[str] = "relu2"  # mlp_hidden_act: two matrices, no gate
    ssd_chunk: ClassVar[int] = scan_op.CHUNK  # the source's chunk_size; set-up logs it, no key sets it

    def __post_init__(self):
        run = tuple(self.layers_run) or tuple(range(len(self.pattern)))
        object.__setattr__(self, "layers_run", run)  # a job's list -> hashable
        if not self.pattern or set(self.pattern) - set(_KINDS):
            raise ValueError(f"pattern takes the letters {''.join(_KINDS)}, one a source layer; got {self.pattern!r}")
        if list(run) != sorted(set(run)) or not all(0 <= i < len(self.pattern) for i in run):
            raise ValueError(f"layers_run needs rising source indices below {len(self.pattern)}, got {run}")
        if self.mamba_num_heads % self.n_groups or self.num_heads % self.num_kv_heads:
            raise ValueError("heads come in whole groups")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError("experts_held + expert_offset exceed num_experts")

    @property
    def layer_types(self) -> tuple:
        return tuple(_KINDS[self.pattern[i]] for i in self.layers_run)

    @property
    def num_expert_layers(self) -> int:
        return self.layer_types.count(EXPERTS)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def residual_scale(self) -> float:
        """The variance of a part's output projection, as a share of the
        program's own: ``rescale_prenorm_residual`` by the source's depth."""
        return 1.0 / len(self.pattern)

    @classmethod
    def tiny(cls) -> "NemotronHConfig":
        """CI-sized: source layers 1 to 5 of 7, ``MEM*E``."""
        return cls(
            vocab_size=256, hidden_size=64, pattern="EMEM*EM", layers_run=(1, 2, 3, 4, 5),
            num_heads=4, num_kv_heads=1, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
            ssm_state_size=16, n_groups=2, num_experts=8, experts_per_token=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=64, max_seq_len=512,
            moe_chunk=32,
        )


def _out_init(cfg):
    return nn.initializers.variance_scaling(cfg.residual_scale, "fan_in", "truncated_normal")


def _dt_bias(cfg):
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _a_log(key, shape, dtype=jnp.float32):
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(dtype)


def _gated_norm(y, z, weight, groups: int, eps: float):
    """``w * GroupRMSNorm(y * silu(z))``: the gate before the norm, the norm
    over each of ``groups`` runs of channels; float32."""
    with jax.named_scope("gated_norm"):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        parts = g.reshape(*g.shape[:-1], groups, -1)
        parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
        return parts.reshape(g.shape) * weight


class _Mamba2(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        batch, s, e = u.shape
        heads, p, n, groups, di = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups, cfg.d_inner
        wide = di + 2 * groups * n  # what the convolution runs over: x, B and C
        dense = lambda width, name, **kw: nn.Dense(width, use_bias=False, dtype=dtype, name=name, **kw)
        with jax.named_scope("mamba2"):
            z, xbc, dt = jnp.split(dense(di + wide + heads, "in_proj")(u), [di, di + wide], axis=-1)
            # fan-in of a depthwise tap is the taps of its own channel
            taps = self.param("conv_weight", nn.initializers.lecun_normal(), (cfg.conv_kernel, wide), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (wide,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias(cfg), (heads,), jnp.float32)
            a_log = self.param("A_log", _a_log, (heads,), jnp.float32)
            d = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            norm = self.param("norm", nn.initializers.ones, (di,), jnp.float32)
            xbc = jax.nn.silu(causal_taps(xbc, taps) + bias.astype(dtype))
            x, b, c = jnp.split(xbc, [di, di + groups * n], axis=-1)
            x = x.reshape(batch, s, heads, p)
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y, _ = scan_op.ssd_scan(
                x, step, -jnp.exp(a_log), b.reshape(batch, s, groups, n), c.reshape(batch, s, groups, n))
            y = (y + d[:, None] * x.astype(jnp.float32)).reshape(batch, s, di)
            # The gated norm is elementwise over the scan's output and the gate,
            # which are kept anyway: it keeps nothing else and makes its float32
            # intermediates again in the backward pass.
            y = jax.checkpoint(lambda y, z, w: _gated_norm(y, z, w, groups, cfg.rms_eps))(y, z, norm)
            return dense(e, "out_proj", kernel_init=_out_init(cfg))(y.astype(dtype))


class _Attention(nn.Module):
    config: NemotronHConfig
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        batch, s, e = u.shape
        hd = cfg.head_dim
        dense = lambda n, name, **kw: nn.Dense(n, use_bias=False, dtype=dtype, name=name, **kw)
        q = dense(cfg.num_heads * hd, "q_proj")(u).reshape(batch, s, cfg.num_heads, hd)
        k = dense(cfg.num_kv_heads * hd, "k_proj")(u).reshape(batch, s, cfg.num_kv_heads, hd)
        v = dense(cfg.num_kv_heads * hd, "v_proj")(u).reshape(batch, s, cfg.num_kv_heads, hd)
        with jax.named_scope("attention"):
            impl = self.attn_impl or dot_product_attention
            o = impl(q, k, v, causal=True).reshape(batch, s, cfg.num_heads * hd)
        return dense(e, "o_proj", kernel_init=_out_init(cfg))(o)


class _Block(nn.Module):
    config: NemotronHConfig
    source: int  # the layer's index in the source
    attn_impl: Callable | None = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        kind = _KINDS[cfg.pattern[self.source]]
        u = _RMSNorm(cfg.rms_eps, name="norm")(h)
        stats = None
        if kind == MAMBA2:
            out = _Mamba2(cfg, name="mixer")(u)
        elif kind == FULL:
            with jax.named_scope(FULL):
                out = _Attention(cfg, self.attn_impl, name="mixer")(u)
        else:
            out, stats = _MoE(cfg, name="mixer")(u)
        return h + out.astype(h.dtype), stats


class NemotronH(nn.Module):
    config: NemotronHConfig = NemotronHConfig()
    attn_impl: Callable | None = None
    head_leaf: ClassVar[str] = "lm_head"  # the routed step's loss reads it
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
        per_layer = []
        for n, source in enumerate(cfg.layers_run):
            x, stats = _Block(cfg, source, self.attn_impl, name=f"layers_{n}")(x)
            if stats is not None:
                per_layer.append(stats)
        stats = jax.tree.map(lambda *a: jnp.stack(a), *per_layer) if per_layer else {}
        x = _RMSNorm(cfg.rms_eps, name="norm_f")(x)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        if not self.with_head:
            return x, stats
        with jax.named_scope("lm_head"):
            return jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), lm_head), stats
