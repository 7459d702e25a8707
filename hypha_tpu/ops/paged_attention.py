"""Ragged block-sparse attention over the paged KV pool.

The paged cache (ops/kvcache.py) maps each decode lane's logical window
onto physical blocks through a per-lane table, but until now attention
consumed a DENSE per-lane gather of the whole ``decode_len`` window —
paging saved HBM, not FLOPs. This op consumes the pool + table directly
and makes the step cost proportional to *occupied* blocks:

* **CPU/XLA fallback** (:func:`ragged_block_attention`): a flash-style
  streaming softmax (the ``chunked_attention`` m/l/acc recurrence) driven
  by a ``lax.while_loop`` whose trip count is the max occupancy across
  lanes — ONE compiled program whose runtime shrinks with occupancy, so
  the pool's two-program-shapes invariant holds. Garbage/unallocated
  table entries (the ``blocks`` sentinel) are masked per entry, so the
  garbage block can never contribute to the output at any occupancy.
  When every lane is fully occupied a ``lax.cond`` takes a dense branch
  that reproduces the historical gather + ``dot_product_attention``
  expression operation-for-operation — bit-compatible with the dense
  path at full occupancy by construction.
* **Pallas TPU kernel** (:func:`_ragged_attention_tpu`): grid
  (lane, q-head, block) with the block table, occupancy counts and
  per-lane offsets scalar-prefetched (``PrefetchScalarGridSpec``), so the
  BlockSpec index maps route each grid step's K/V DMA straight to the
  lane's physical block — GQA heads share kv blocks via the index map
  (no repeat), and garbage blocks are predicated off with ``pl.when``
  (their DMA re-reads the single garbage block, which stays
  cache-resident). Stats are lane-replicated [Sq, 128] per the Mosaic
  layout rule (see flash_attention.py).

int8 KV blocks: when per-row max-abs scales ride along (kvcache
``kv_quant="int8"``), dequantization is fused into the block loop — the
pool payload stays int8 in HBM/VMEM and only one block's worth of K/V is
ever dequantized at a time.

Occupancy is derived inside the op (``sum(table != blocks, axis=1)``):
idle lanes park with all-sentinel tables and cost zero blocks. Lane
tables are prefix-packed by the pool (real blocks first, sentinel tail);
the per-entry sentinel mask keeps correctness even for holes, but the
while_loop bound assumes the packed prefix.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from einops import repeat

from .attention import dot_product_attention
from .kvcache import _physical

__all__ = ["PagedKV", "paged_attention", "ragged_block_attention"]

_LANES = 128  # TPU vector lane width (see flash_attention.py layout note)
_NEG_INF = float("-inf")


class PagedKV(NamedTuple):
    """The raw paged-cache view handed to :func:`paged_attention` when the
    model skips the dense gather (``update_kv_cache(..., ragged=True)``).
    Array leaves only — static shape facts (blocks, block_size) travel as
    kwargs so jit treats them as compile-time constants."""

    k: jnp.ndarray  # [(blocks+1)*block_size, Hkv, D] payload
    v: jnp.ndarray
    k_scale: jnp.ndarray | None  # [(blocks+1)*block_size, Hkv] f32, int8 mode
    v_scale: jnp.ndarray | None
    table: jnp.ndarray  # [B, max_blocks] int32; ``blocks`` = sentinel


def _dequant(payload, scale, out_dtype):
    """Per-row max-abs dequant (scale == 0 rows decode to exact zeros,
    matching compress/quant's not-finite/zero-chunk convention)."""
    if scale is None:
        return payload.astype(out_dtype)
    return (payload.astype(jnp.float32) * scale[..., None]).astype(out_dtype)


def _dense_branch(q, kv: PagedKV, *, blocks, block_size, q_offset, k_start,
                  window):
    """The historical dense path: gather the full window through the table
    and run the reference attention. This is byte-for-byte the expression
    ``update_kv_cache`` used before ragged mode existed, so the ragged op
    is bit-compatible with the dense gather whenever this branch runs
    (full occupancy)."""
    B = kv.table.shape[0]
    max_blocks = kv.table.shape[1]
    decode_len = max_blocks * block_size
    win = jnp.broadcast_to(jnp.arange(decode_len)[None, :], (B, decode_len))
    phys_win = _physical(kv.table, win, block_size, max_blocks, blocks)
    full_k = _dequant(kv.k[phys_win], None if kv.k_scale is None
                      else kv.k_scale[phys_win], q.dtype)
    full_v = _dequant(kv.v[phys_win], None if kv.v_scale is None
                      else kv.v_scale[phys_win], q.dtype)
    return dot_product_attention(
        q, full_k, full_v, causal=True, q_offset=q_offset,
        window=window, k_start=k_start,
    )


def _streaming_branch(q, kv: PagedKV, count, *, blocks, block_size,
                      q_offset, k_start, window, blocks_per_iter):
    """Occupancy-proportional masked-block streaming softmax: iterate
    chunks of ``blocks_per_iter`` table entries under a while_loop bounded
    by the max lane occupancy, folding each chunk into the flash (m, l,
    acc) carry (chunked_attention's recurrence, forward only)."""
    B, Sq, Hq, D = q.shape
    max_blocks = kv.table.shape[1]
    C = blocks_per_iter
    span = C * block_size
    # Pad the table with sentinels to a C multiple so dynamic_slice never
    # clamps its start (a clamped slice would re-read earlier blocks and
    # double-count them in the softmax).
    pad = (-max_blocks) % C
    table = kv.table
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=blocks)
    n_iter = jnp.ceil(jnp.max(count) / C).astype(jnp.int32)

    scale = D**-0.5
    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, Hq, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    qi = q_offset[:, None] + jnp.arange(Sq)[None, :]  # [B, Sq] positions

    def body(state):
        j, m, l, acc = state
        b0 = j * C
        blk = jax.lax.dynamic_slice(table, (0, b0), (B, C))  # [B, C]
        rows = (
            jnp.clip(blk, 0, blocks)[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]
        ).reshape(B, span)
        k_blk = _dequant(kv.k[rows], None if kv.k_scale is None
                         else kv.k_scale[rows], jnp.float32)
        v_blk = _dequant(kv.v[rows], None if kv.v_scale is None
                         else kv.v_scale[rows], jnp.float32)
        if Hq != k_blk.shape[2]:
            g = Hq // k_blk.shape[2]
            k_blk = repeat(k_blk, "b s h d -> b s (h g) d", g=g)
            v_blk = repeat(v_blk, "b s h d -> b s (h g) d", g=g)
        # Logical key positions of this chunk — chunk-relative iota plus
        # the (traced) chunk base.
        ki = b0 * block_size + jnp.arange(span)  # [span]
        keep = qi[:, :, None] >= ki[None, None, :]  # causal [B, Sq, span]
        if window is not None:
            keep = keep & (ki[None, None, :] > qi[:, :, None] - window)
        if k_start is not None:
            keep = keep & (ki[None, None, :] >= k_start[:, None, None])
        # Garbage/unallocated entries never contribute, whatever their
        # payload holds (the property test randomizes it).
        keep = keep & jnp.repeat(blk != blocks, block_size, axis=1)[:, None, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk) * scale
        s = jnp.where(keep[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m - m_new))
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
        return j + 1, m_new, l, acc

    _, _, l, acc = jax.lax.while_loop(
        lambda s: s[0] < n_iter, body, (jnp.int32(0), m0, l0, acc0)
    )
    # Fully-masked rows (idle lanes, l == 0) output zeros — the same
    # convention as dot_product_attention's nan_to_num + sum floor.
    o = acc / jnp.maximum(l, 1e-20)[..., None]
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def ragged_block_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, D], RoPE'd
    kv: PagedKV,
    *,
    blocks: int,
    block_size: int,
    q_offset: jnp.ndarray,  # int32 [B]
    k_start: jnp.ndarray | None = None,  # int32 [B]
    window: int | None = None,
    blocks_per_iter: int = 0,
) -> jnp.ndarray:
    """XLA ragged paged attention (the CPU/GPU fallback). See module doc
    for the dense-at-full-occupancy bit-compatibility contract."""
    max_blocks = kv.table.shape[1]
    count = jnp.sum(kv.table != blocks, axis=1).astype(jnp.int32)  # [B]
    if blocks_per_iter <= 0:
        # Amortize per-iteration while_loop overhead: ~256 key positions
        # per chunk keeps the einsum meaty without losing granularity.
        blocks_per_iter = max(1, min(max_blocks, 256 // max(block_size, 1)))
    dense = functools.partial(
        _dense_branch, blocks=blocks, block_size=block_size,
        q_offset=q_offset, k_start=k_start, window=window,
    )
    streaming = functools.partial(
        _streaming_branch, blocks=blocks, block_size=block_size,
        q_offset=q_offset, k_start=k_start, window=window,
        blocks_per_iter=blocks_per_iter,
    )
    return jax.lax.cond(
        jnp.all(count == max_blocks),
        lambda: dense(q, kv),
        lambda: streaming(q, kv, count),
    )


# --------------------------------------------------------------- TPU kernel


def _ragged_kernel(
    # scalar-prefetch refs
    table_ref, count_ref, qoff_ref, kstart_ref,
    # tensor refs (ks_ref/vs_ref present only in int8 mode)
    *refs,
    block_size, max_blocks, blocks, scale, window, quant,
):
    import jax.experimental.pallas as pl

    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    Sq = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip garbage/unallocated blocks AND blocks entirely above this
    # lane's causal frontier — the FLOPs (and int8 dequant) run only for
    # occupied, attendable blocks.
    live = (j < count_ref[b]) & (table_ref[b, j] != blocks)
    live &= j * block_size <= qoff_ref[b] + Sq - 1

    @pl.when(live)
    def _body():
        q = q_ref[0]  # [Sq, D]
        k = k_ref[0]  # [block_size, D]
        v = v_ref[0]
        if quant:
            # Fused per-row dequant: one block's K/V leaves int8 at a time.
            k = k.astype(jnp.float32) * ks_ref[0]
            v = v.astype(jnp.float32) * vs_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        ki = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (Sq, block_size), 1
        )
        qi = qoff_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (Sq, block_size), 0
        )
        mask = (qi >= ki) & (ki >= kstart_ref[b])
        if window is not None:
            mask = mask & (ki > qi - window)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_scr[...]  # [Sq, 128] lane-replicated
        m_new = jnp.maximum(m, s.max(axis=-1)[:, None])
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[:, :1]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(j == max_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def _ragged_attention_tpu(
    q, kv: PagedKV, *, blocks, block_size, q_offset, k_start, window,
    interpret,
):
    """Pallas ragged paged attention: grid (lane, q-head, block) with the
    table/occupancy/offsets scalar-prefetched so index maps address each
    lane's physical blocks directly. The pool is re-laid head-major
    ([Hkv*(blocks+1), block_size, D]) for Mosaic's last-two-dims block
    rule; a production deployment would keep the pool head-major to make
    this a free view (kernel contract in docs/serving.md)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hq, D = q.shape
    Hkv = kv.k.shape[1]
    max_blocks = kv.table.shape[1]
    bs = block_size
    count = jnp.sum(kv.table != blocks, axis=1).astype(jnp.int32)
    kstart = (jnp.zeros((B,), jnp.int32) if k_start is None
              else k_start.astype(jnp.int32))
    quant = kv.k_scale is not None

    # [rows, Hkv, D] -> [Hkv*(blocks+1), bs, D], head-major.
    def _head_major(pool):
        return (pool.reshape(blocks + 1, bs, Hkv, -1)
                .transpose(2, 0, 1, 3)
                .reshape(Hkv * (blocks + 1), bs, -1))

    kp = _head_major(kv.k)
    vp = _head_major(kv.v)
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)

    g = Hq // Hkv

    def _kv_block(b, h, j, table, *_):
        # GQA: query head h reads kv head h // g; sentinel entries clamp
        # into the garbage block (predicated off in the kernel).
        return ((h // g) * (blocks + 1) + jnp.clip(table[b, j], 0, blocks),
                0, 0)

    in_specs = [
        pl.BlockSpec((1, Sq, D), lambda b, h, j, *_: (b * Hq + h, 0, 0)),
        pl.BlockSpec((1, bs, D), _kv_block),
        pl.BlockSpec((1, bs, D), _kv_block),
    ]
    operands = [qt, kp, vp]
    if quant:
        # Scales ride as [Hkv*(blocks+1), bs, 1] so the block's last two
        # dims equal the array's (Mosaic layout rule).
        ks = _head_major(kv.k_scale[..., None])
        vs = _head_major(kv.v_scale[..., None])
        in_specs += [
            pl.BlockSpec((1, bs, 1), _kv_block),
            pl.BlockSpec((1, bs, 1), _kv_block),
        ]
        operands += [ks, vs]

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hq, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, Sq, D), lambda b, h, j, *_: (b * Hq + h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Sq, _LANES), jnp.float32),
            pltpu.VMEM((Sq, _LANES), jnp.float32),
            pltpu.VMEM((Sq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            block_size=bs, max_blocks=max_blocks, blocks=blocks,
            scale=D**-0.5, window=window, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        interpret=interpret,
        **kwargs,
    )(kv.table.astype(jnp.int32), count, q_offset.astype(jnp.int32),
      kstart, *operands)
    return out.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


def paged_attention(
    q: jnp.ndarray,
    kv: PagedKV,
    *,
    blocks: int,
    block_size: int,
    q_offset: jnp.ndarray,
    k_start: jnp.ndarray | None = None,
    window: int | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Ragged paged attention dispatcher: the compiled Pallas kernel on the
    TPU, the masked-block XLA fallback elsewhere. ``use_kernel`` forces the
    choice (tests run the kernel in interpret mode)."""
    if use_kernel is None:
        from ..hw import is_accelerator

        # Chosen from the platform: compiled, never interpreted.
        use_kernel, interpret = is_accelerator(), False
    if use_kernel:
        if interpret is None:
            from ..hw import interpret_default

            interpret = interpret_default()
        return _ragged_attention_tpu(
            q, kv, blocks=blocks, block_size=block_size, q_offset=q_offset,
            k_start=k_start, window=window, interpret=interpret,
        )
    return ragged_block_attention(
        q, kv, blocks=blocks, block_size=block_size, q_offset=q_offset,
        k_start=k_start, window=window,
    )
