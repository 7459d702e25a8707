"""A learned selection of keys: an indexer's scores, the exact choice of the
``topk`` largest a query, and the indexer's own objective.

For query ``t`` and key ``s <= t`` the index score is

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

over the indexer's small heads ``j`` (``w`` carries the head weights and both
scale factors), accumulated in float32. ``S_t`` is the set of the
``min(t + 1, topk)`` largest of ``I[t, 0..t]``, ties to the earlier position:
what ``jax.lax.top_k`` gives on the whole row. The [S, S] score matrix never
exists: rows are made a ``q_chunk`` of queries at a time from tiles of
``q_chunk`` x ``kv_chunk`` (:func:`score_tile`), and what leaves
:func:`index_select` is a packed mask, one bit a pair.

**The choice** is exact and is no sort: the ``topk``-th largest value of a row
is found by bisection on the integer image of its floats (an order-preserving
map of float32 onto uint32; 32 passes of compare-and-count), keys above it are
taken, and of the keys equal to it the earliest that are still wanted. Nothing
is approximate.

**The packed mask** is int32 ``[S, W]``, ``W = ceil(S / 4096) * 128``: key
``s`` is bit ``(s % 4096) // 128`` of word ``(s // 4096) * 128 + s % 128``.
A word keeps the 128-lane position of its keys, so an attention kernel that
walks key tiles of 128 x n lanes gets a tile's mask from one [rows, 128] block
by a shift and never gathers across lanes (``ops/flash_attention.py``), and
eight to thirty-two consecutive key tiles read the same block.

**The objective** (:func:`index_kl`) is the KL divergence from the main
attention's distribution over ``S_t``, heads averaged, to the softmax of the
index scores there. Its target needs the attention probabilities summed over
heads on the picked pairs, which no flash kernel emits: one more ``q k^T`` pass
from the saved log-sum-exps, tile by tile over the causal triangle, in the
same walk as the scores' log-softmax and the gradient ``softmax(I) - p`` with
its products back onto the indexer's queries, keys and head weights. The op is
a ``jax.custom_vjp`` whose forward pass makes those three gradients (the walk
has a data-dependent inner trip count and no reverse-mode rule) and whose
backward pass scales them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "score_tile", "index_select", "index_kl", "pack_mask", "unpack_mask",
    "masked_attention", "GROUP", "LANES",
]

LANES = 128  # keys a word's bit holds side by side: the TPU's lane width
GROUP = 32 * LANES  # keys a row of 128 words holds


def score_tile(qi, ki, w):
    """``qi`` [T, J, Di], ``ki`` [N, Di], ``w`` [T, J] f32 -> ``(I [T, N] f32,
    z [J, T, N] f32)``: the tile's index scores and the heads' products before
    the ReLU. A zero is +0.0 whatever its parts' signs, so equal scores are
    equal bit for bit."""
    z = jnp.einsum("tjd,nd->jtn", qi, ki, preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(z) * w.T[:, :, None], axis=0)
    return jnp.where(scores == 0, 0.0, scores), z


def _ordered(x):
    """float32 -> uint32, order-preserving: ``a < b`` iff ``u(a) < u(b)``. Every
    float, -inf included, maps above 0, which is left to mark a key that is
    not there."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def _kth_largest(u, k: int):
    """``u`` uint32 [R, N] -> the largest ``T`` [R] with ``count(u >= T) >= k``:
    the row's k-th largest, or 0 where the row has fewer than ``k`` above 0.
    Bit by bit from the top: 32 passes of compare-and-count."""

    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        count = jnp.sum(u >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, t)

    return jax.lax.fori_loop(0, 32, body, jnp.zeros(u.shape[:1], jnp.uint32))


def pack_mask(mask):
    """bool [..., T, N] -> int32 [..., T, ceil(N / 4096) * 128] (module doc)."""
    *lead, n = mask.shape
    groups = -(-n // GROUP)
    bits = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, groups * GROUP - n)])
    bits = bits.reshape(*lead, groups, 32, LANES).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(*lead, groups * LANES)


def unpack_mask(packed, n: int):
    """int32 [..., T, W] -> bool [..., T, n]: :func:`pack_mask`'s inverse."""
    *lead, w = packed.shape
    words = jax.lax.bitcast_convert_type(packed, jnp.uint32).reshape(*lead, w // LANES, 1, LANES)
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & jnp.uint32(1)
    return (bits != 0).reshape(*lead, w // LANES * GROUP)[..., :n]


def _padded(x, rows: int):
    return jnp.pad(x, [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def index_select(qi, ki, w, *, topk: int, q_chunk: int = 512, kv_chunk: int = 512):
    """One sequence's selection. ``qi`` [S, J, Di], ``ki`` [S, Di], ``w``
    [S, J] float32 -> ``(packed int32 [S, W], lse_i f32 [S])``: the mask of
    ``S_t`` a query (module doc) and the log-sum-exp of its index scores over
    ``S_t``, which the objective's log-softmax needs."""
    s = qi.shape[0]
    nq, nk = -(-s // q_chunk), -(-s // kv_chunk)
    keys = _padded(ki, nk * kv_chunk).reshape(nk, kv_chunk, -1)
    kpos = jnp.arange(nk * kv_chunk, dtype=jnp.int32)

    def rows(args):
        qc, wc, start = args
        with jax.named_scope("index_scores"):
            tiles = jax.lax.map(lambda kt: score_tile(qc, kt, wc)[0], keys)  # [nk, q_chunk, kv_chunk]
            scores = tiles.transpose(1, 0, 2).reshape(q_chunk, nk * kv_chunk)
        with jax.named_scope("index_select"):
            qpos = start + jnp.arange(q_chunk, dtype=jnp.int32)
            valid = kpos[None, :] <= qpos[:, None]  # a key past the sequence's end is past every query
            u = jnp.where(valid, _ordered(scores), jnp.uint32(0))
            kth = _kth_largest(u, topk)[:, None]
            above, equal = u > kth, valid & (u == kth)
            wanted = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)[:, None]
            # Of the keys equal to the k-th value the earliest `wanted` are taken. Nearly always
            # all of them are wanted (no tie straddles the cut) and the count along the row is skipped.
            tied = jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32)[:, None] > wanted)
            picked = above | jax.lax.cond(
                tied, lambda: equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= wanted), lambda: equal)
            top = jnp.max(jnp.where(picked, scores, -jnp.inf), axis=-1)
            lse = top + jnp.log(jnp.sum(jnp.where(picked, jnp.exp(scores - top[:, None]), 0.0), axis=-1))
            return pack_mask(picked), lse

    starts = jnp.arange(nq, dtype=jnp.int32) * q_chunk
    packed, lse = jax.lax.map(rows, (
        _padded(qi, nq * q_chunk).reshape(nq, q_chunk, *qi.shape[1:]),
        _padded(w, nq * q_chunk).reshape(nq, q_chunk, -1), starts))
    return packed.reshape(nq * q_chunk, -1)[:s], lse.reshape(-1)[:s]


def masked_attention(q, k, v, packed, scale: float):
    """The plain form of attention over a selection, for shapes no kernel
    tiles: ``q`` [B, S, H, D], ``k``, ``v`` [B, S, Hkv, D*], ``packed``
    [B, S, W] -> ``(o [B, S, H, Dv], lse f32 [B, H, S])``."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    keep = unpack_mask(packed, s)[:, None, None]  # [B, 1, 1, S, S]
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, s, hkv, h // hkv, d), k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(keep, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    p = jnp.exp(logits - lse[..., None]).astype(v.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(b, s, h, v.shape[-1])
    return o, lse.reshape(b, h, s)


def _kl_walk(qi, ki, w, q, k, lse, packed, lse_i, scale, q_chunk, kv_chunk):
    """The objective of one sequence, summed over its queries, and its
    gradient by ``qi``, ``ki`` and ``w``: one walk over the causal triangle's
    tiles. ``q`` [S, H, D], ``k`` [S, Hkv, D], ``lse`` [H, S] (the main
    attention's, over ``S_t``), ``packed`` and ``lse_i`` :func:`index_select`'s."""
    s, heads, _ = q.shape
    hkv = k.shape[1]
    nq, nk = -(-s // q_chunk), -(-s // kv_chunk)
    sq, sk = nq * q_chunk, nk * kv_chunk
    ki_p, k_p = _padded(ki, sk), _padded(k, sk)
    chunks = lambda x: _padded(x, sq).reshape(nq, q_chunk, *x.shape[1:])
    mask_rows = chunks(packed)
    low = qi.dtype

    def chunk(dki, args):
        qic, wc, qc, lsec, words, lic, start = args
        keep_row = unpack_mask(words, sk)
        qg = qc.reshape(q_chunk, hkv, heads // hkv, -1)
        lseg = lsec.reshape(q_chunk, hkv, heads // hkv).transpose(1, 2, 0)[..., None]

        def tile(j, carry):
            kl, dqi, dw, dki = carry
            at = j * kv_chunk
            kit = jax.lax.dynamic_slice_in_dim(ki_p, at, kv_chunk)
            kt = jax.lax.dynamic_slice_in_dim(k_p, at, kv_chunk)
            keep = jax.lax.dynamic_slice_in_dim(keep_row, at, kv_chunk, axis=1)
            scores, z = score_tile(qic, kit, wc)
            logits = jnp.einsum("tgrd,ngd->grtn", qg, kt, preferred_element_type=jnp.float32) * scale
            p = jnp.where(keep, jnp.exp(logits - lseg), 0.0).sum((0, 1)) / heads
            logq = scores - lic[:, None]
            kl = kl + jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - logq), 0.0), axis=-1)
            ds = jnp.where(keep, jnp.exp(logq) - p, 0.0)
            dz = (ds[None] * wc.T[:, :, None] * (z > 0)).astype(low)
            dw = dw + jnp.einsum("tn,jtn->tj", ds, jax.nn.relu(z))
            dqi = dqi + jnp.einsum("jtn,nd->jtd", dz, kit, preferred_element_type=jnp.float32).transpose(1, 0, 2)
            mine = jnp.einsum("jtn,tjd->nd", dz, qic, preferred_element_type=jnp.float32)
            old = jax.lax.dynamic_slice_in_dim(dki, at, kv_chunk)
            return kl, dqi, dw, jax.lax.dynamic_update_slice_in_dim(dki, old + mine, at, axis=0)

        last = (start + q_chunk - 1) // kv_chunk  # the last key tile a query of this chunk reaches
        kl, dqi, dw, dki = jax.lax.fori_loop(0, jnp.minimum(last, nk - 1) + 1, tile, (
            jnp.zeros((q_chunk,), jnp.float32), jnp.zeros(qic.shape, jnp.float32),
            jnp.zeros(wc.shape, jnp.float32), dki))
        return dki, (kl, dqi, dw)

    starts = jnp.arange(nq, dtype=jnp.int32) * q_chunk
    dki, (kl, dqi, dw) = jax.lax.scan(chunk, jnp.zeros(ki_p.shape, jnp.float32), (
        chunks(qi), chunks(w), chunks(q), chunks(lse.T), mask_rows, chunks(lse_i), starts))
    flat = lambda x: x.reshape(sq, *x.shape[2:])[:s]
    return flat(kl).sum(), (flat(dqi).astype(qi.dtype), dki[:s].astype(ki.dtype), flat(dw).astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def index_kl(qi, ki, w, q, k, lse, packed, lse_i, scale: float, q_chunk: int = 512, kv_chunk: int = 512):
    """One sequence's ``sum_t KL(p[t, .] || softmax_{S_t}(I[t, .]))`` with
    ``p[t, s]`` the mean over heads of the main attention's probability of key
    ``s`` on ``S_t`` (module doc). Differentiable by ``qi``, ``ki`` and ``w``
    alone: the target (``q``, ``k``, ``lse``) is a constant, as published."""
    return _kl_walk(qi, ki, w, q, k, lse, packed, lse_i, scale, q_chunk, kv_chunk)[0]


def _index_kl_fwd(qi, ki, w, q, k, lse, packed, lse_i, scale, q_chunk, kv_chunk):
    return _kl_walk(qi, ki, w, q, k, lse, packed, lse_i, scale, q_chunk, kv_chunk)


def _index_kl_bwd(scale, q_chunk, kv_chunk, grads, g):
    dqi, dki, dw = grads
    return ((g * dqi).astype(dqi.dtype), (g * dki).astype(dki.dtype), (g * dw).astype(dw.dtype),
            None, None, None, None, None)


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
