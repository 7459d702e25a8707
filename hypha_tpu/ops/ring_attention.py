"""Ring attention: causal attention over a sequence sharded across devices.

Long-context is absent from the reference (SURVEY.md §5 "Long-context") —
this is the net-new TPU mechanism that lifts its sequence-length ceiling.
The sequence axis is sharded over the mesh's ``sp`` axis; each device holds a
query block and rotates key/value blocks around the ring with ``ppermute``
(one hop per step, overlapping compute with ICI transfer), accumulating
attention with a streaming (online-softmax) reduction in f32, exactly the
blockwise formulation of Ring Attention (Liu et al.) adapted to XLA
collectives.

Numerics are checked against ops.attention.dot_product_attention in tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from einops import repeat
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["make_ring_attention", "ring_attention"]

_NEG = -1e30


def _ring_body(q, k, v, *, axis_name: str, axis_size: int, causal: bool, scale: float):
    """Runs on one device inside shard_map. q,k,v: [B, S_local, H, D]."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    my = jax.lax.axis_index(axis_name)
    qpos = my * Sq + jnp.arange(Sq)  # global query positions

    q32 = q.astype(jnp.float32)

    def step(carry, t):
        o, m, l, k_cur, v_cur = carry
        kv_idx = (my - t) % axis_size
        kpos = kv_idx * Sk + jnp.arange(Sk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32)) * scale
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None], logits, _NEG)
        m_new = jnp.maximum(m, logits.max(-1))  # [B, H, Sq]
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)  # [B, H, Sq]
        l_new = l * alpha + p.sum(-1)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32)
        )
        # rotate kv one hop around the ring (overlaps with next block compute)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    seq_axis: str = "sp",
    batch_axes: tuple = ("dp", "fsdp"),
):
    """Build an attention callable with dot_product_attention's signature,
    sharded over ``mesh``: batch over ``batch_axes``, sequence over
    ``seq_axis``, heads/D replicated (combine with tp by sharding heads
    outside)."""
    axis_size = mesh.shape[seq_axis]
    spec = P(batch_axes, seq_axis, None, None)

    def attention(q, k, v, *, causal: bool = True, softmax_scale=None, **_):
        if q.shape[1] % 1:
            raise ValueError
        scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
        Hq, Hkv = q.shape[2], k.shape[2]
        if Hq != Hkv:  # GQA: expand before the ring so blocks line up
            k_x = repeat(k, "b s h d -> b s (h g) d", g=Hq // Hkv)
            v_x = repeat(v, "b s h d -> b s (h g) d", g=Hq // Hkv)
        else:
            k_x, v_x = k, v
        body = partial(
            _ring_body,
            axis_name=seq_axis,
            axis_size=axis_size,
            causal=causal,
            scale=scale,
        )
        sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return sharded(q, k_x, v_x)

    return attention


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = True, seq_axis: str = "sp"):
    """One-shot convenience wrapper."""
    return make_ring_attention(mesh, seq_axis)(q, k, v, causal=causal)
