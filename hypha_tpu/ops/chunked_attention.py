"""Blockwise (flash-style) attention in pure XLA, with a hand-derived VJP.

Why this exists next to the pallas kernel (ops/flash_attention.py): the
pallas kernel only lowers on real TPUs, but two paths need flash's MEMORY
PROFILE — O(S·block) live scores instead of the dense O(S²) tensor — on
backends where pallas can't run:

  * AOT memory accounting: per-device peak bytes of a train step can be
    extracted from XLA's compiled-memory analysis on
    virtual CPU meshes; with dense attention the analysis would charge a
    [B,H,S,S] score buffer the TPU path never materializes.
  * CPU fallback/serving tests at long S, where dense attention OOMs.

Numerically it is ordinary softmax(QK^T)V (checked against
ops/attention.py); structurally it is the flash algorithm: the forward
scans KV blocks carrying the online-softmax state (m, l, acc) and saves
only (o, lse); the backward recomputes each block's probabilities from the
saved lse — the custom VJP is what stops autodiff from stacking per-block
carries into the full S² tensor the blocking was meant to avoid.

Algorithm per FlashAttention (Dao et al. 2022), independently implemented;
backward follows the standard identities ds = p∘(dp − Δ), Δ = Σ(do∘o).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from einops import repeat

__all__ = ["chunked_attention"]


def _split_blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """[B, S, H, D] -> [nblk, B, block, H, D] for lax.scan."""
    B, S, H, D = x.shape
    return x.reshape(B, S // block, block, H, D).swapaxes(0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attn(q, k, v, causal: bool, scale: float, block: int):
    o, _ = _attn_fwd(q, k, v, causal, scale, block)
    return o


def _blk_logits(q, k_blk, j, block, causal, scale):
    """Scores of all queries against KV block ``j`` (f32, masked)."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = j * block + jnp.arange(block)[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    return s


def _attn_fwd(q, k, v, causal: bool, scale: float, block: int):
    B, Sq, H, D = q.shape
    nblk = k.shape[1] // block
    ks, vs = _split_blocks(k, block), _split_blocks(v, block)

    def step(carry, inp):
        m, l, acc = carry
        j, k_blk, v_blk = inp
        s = _blk_logits(q, k_blk, j, block, causal, scale)
        m_new = jnp.maximum(m, s.max(-1))
        # Fully-masked (future, causal) blocks leave m_new at -inf; the
        # where() keeps exp() away from the -inf − -inf = nan path.
        p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0, m_new)[..., None])
        corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m - m_new))
        l = l * corr + p.sum(-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        acc = acc * corr.swapaxes(1, 2)[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(nblk), ks, vs)
    )
    l_safe = jnp.maximum(l, 1e-30)
    o = (acc / l_safe.swapaxes(1, 2)[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o, (q, k, v, o, lse)


def _attn_bwd(causal: bool, scale: float, block: int, res, do):
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    nblk = k.shape[1] // block
    ks, vs = _split_blocks(k, block), _split_blocks(v, block)
    # Δ_i = Σ_d do_i·o_i — the softmax-jacobian diagonal term, [B, H, Sq].
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", do.astype(jnp.float32), o.astype(jnp.float32)
    )

    def step(dq, inp):
        j, k_blk, v_blk = inp
        s = _blk_logits(q, k_blk, j, block, causal, scale)
        p = jnp.exp(s - lse[..., None])  # masked -> exp(-inf)=0
        dv_blk = jnp.einsum(
            "bhqk,bqhd->bkhd", p, do.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bqhd,bkhd->bhqk", do, v_blk, preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum(
            "bhqk,bkhd->bqhd", ds.astype(k_blk.dtype), k_blk,
            preferred_element_type=jnp.float32,
        )
        dk_blk = jnp.einsum(
            "bhqk,bqhd->bkhd", ds.astype(q.dtype), q,
            preferred_element_type=jnp.float32,
        )
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0, (jnp.arange(nblk), ks, vs)
    )
    dk = dks.swapaxes(0, 1).reshape(k.shape).astype(k.dtype)
    dv = dvs.swapaxes(0, 1).reshape(v.shape).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_attn.defvjp(_attn_fwd, _attn_bwd)


def chunked_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    *,
    causal: bool = True,
    softmax_scale: float | None = None,
    block: int = 512,
    **_,
) -> jnp.ndarray:
    """Drop-in for :func:`ops.attention.dot_product_attention` (the subset
    without mask/window/q_offset) with flash's memory profile. GQA expands
    via broadcast; XLA fuses the repeat into the block einsums, and its
    transpose sums group gradients back onto the kv heads."""
    if causal and q.shape[1] != k.shape[1]:
        # The causal mask compares query index i against absolute kv index
        # j with no offset, so Sq != Sk would silently mask the wrong
        # diagonal (e.g. a decode step attending to a prefix would see a
        # future-shifted window) instead of erroring.
        raise ValueError(
            f"causal chunked_attention requires Sq == Sk, got "
            f"{q.shape[1]} != {k.shape[1]}"
        )
    H, Hkv = q.shape[2], k.shape[2]
    if H != Hkv:
        if H % Hkv:
            raise ValueError(f"query heads {H} not a multiple of kv heads {Hkv}")
        k = repeat(k, "b s h d -> b s (h g) d", g=H // Hkv)
        v = repeat(v, "b s h d -> b s (h g) d", g=H // Hkv)
    blk = min(block, q.shape[1], k.shape[1])
    if k.shape[1] % blk:
        raise ValueError(f"kv length {k.shape[1]} not divisible by block {blk}")
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    return _attn(q, k, v, causal, scale, blk)
