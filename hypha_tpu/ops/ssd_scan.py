"""Mamba-2's scan (state-space duality, arXiv:2405.21060), forward and
backward, chunk by chunk: two Pallas kernels on the chip, ``einsum``s elsewhere.

A head's state is a matrix ``[P, N]`` (head size by state size) with one scalar
decay a position: ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t
C_t``, in float32; ``B`` and ``C`` are shared by the heads of a group. Written
out over a chunk of ``L`` positions with ``c`` the running sum of ``dt A``
inside the chunk, the work is four products and no recurrence over positions:

* ``C B^T`` of the chunk, ``[L, L]`` a group, under the decay mask ``exp(c_i -
  c_j)`` for ``j <= i`` (0 above the diagonal) and times ``dt_j``;
* that matrix times the chunk's inputs ``[L, P]``: what the chunk's own
  positions give;
* the chunk's state ``sum_j exp(c_L - c_j) dt_j x_j B_j^T``, ``[P, N]`` a head;
* ``exp(c_i) C_i`` against the state the chunk starts from: what came before.

Between chunks the state is carried, ``H <- exp(c_L) H + the chunk's state``.
Every exponent is a sum of ``dt A <= 0`` over a span inside one chunk, formed
as a difference of running sums that are masked *before* the exponential, so
nothing overflows whatever the decay, and no running sum is longer than a chunk
(a difference of sums over 8192 positions would lose the small decays to
rounding). The MXU's operands are in the inputs' type (bfloat16 in training)
with float32 sums; the running sums, the exponentials and the carried state are
float32 whatever the inputs. Both forms keep for the backward pass their inputs
and the state each chunk starts from (``S / L`` states of ``heads x P x N``
float32: 64 x 2 MB a sequence at 64 heads of 64 by 128) and nothing else.

**On the chip: two Pallas kernels** under a ``custom_vjp`` (PR 54). The grid
is (batch, ``GROUPS_A_PROGRAM`` groups of heads, chunk); the chunks of a group
run in order and the group's state, transposed to ``[N, R P]`` float32 (``R``
heads a group side by side on the lanes: 256 KB at 8 heads of 64 by 128), lives
in VMEM scratch across them, so nothing crosses a chunk boundary through HBM
but the one copy of each start state the backward pass reads. A program loads
its chunk's ``x`` ``[L, R P]``, ``B`` and ``C`` ``[L, N]`` and the steps ``dt``
and ``dt A`` ``[R, L]`` (time on the lanes: a tile a group) and makes on the
chip what the ``einsum`` form sends through HBM: the running sum (a product
with a triangle of ones on the MXU, each float32 operand as three bfloat16
pieces, so every bit is kept: 1.30 ms a layer forward against 1.48 for seven
shifted adds on the vector unit, ``benchmarks/ssd_scan_probe.py``), the same
small vectors with time on the sublanes (a product with the identity),
``C B^T`` once a group, each head's mask, ``y`` and the state's advance. The
heads whose lanes fill a tile of 128 (two of 64) go through one product, their
masks side by side and their inputs one under the other with the other head's
lanes zeroed, so every load, store and result is a whole tile (a head's 64
lanes alone cost a third more: 1.75 and 4.95 ms against 1.50 and 3.21).

The backward kernel walks the chunks in reverse with the state's gradient
``[N, R P]`` in VMEM. It makes the masks and ``C B^T`` again from the kept
inputs, every ``[L, L]`` tile transposed (an input's position on the rows) so
that no product needs a tile turned; the running sum's gradient comes from two
identities that need no ``[L, L]`` reduction along the lanes (every term of
``y_i`` carries ``exp(c_i)``, everything an input ``j`` gives carries
``exp(-c_j)``, the chunk's end state ``exp(c_L)``), and goes back through the
running sum as a product with the other triangle. ``dB`` and ``dC`` are summed
over a group's heads inside the program (a program holds the whole group);
``dA`` is reduced outside, by ``jax``'s own rule for ``dt A`` (the kernels
take ``dt`` and ``dt A``, which adds 2 MB a layer to what is kept).

VMEM a grid step, at 2 groups of 8 heads of 64, state 128, chunk 128: the
blocks, double-buffered, 4.9 MB forward (``x`` 256 KB, ``B`` and ``C`` 64 KB
each, ``y`` 512 KB, the start states in and out and the one kept 512 KB each)
and 5.6 MB backward; scratch 0.6 and 0.8 MB; the largest values alive at once
are a slab's two masks (64 KB float32 each and their bfloat16 copies) beside
``C B^T`` and the two ``[L, R P]`` float32 products (256 KB each): under the
16 MB a kernel is given by default. Four and eight groups a step need that
raised and read no faster (3.13 and 3.08 ms a layer forward and backward
against 3.03 at two and 3.21 at one).

**Elsewhere** (the CPU's tests and rehearsals, shapes that fill no tile): the
same four products as batched ``einsum``s under a ``custom_vjp`` whose backward
pass is ``jax.vjp`` of the two chunk-local halves and the boundary recurrence
in reverse by hand. On the chip this form read 2.65 ms forward and 8.06 forward
and backward a layer where the kernels read 1.30 and 2.96 (``PERF.md`` 6, PR
54): its masks, ``[64, 8, 8, 128, 128]`` float32, and the chunks' states go
through HBM.

``CHUNK`` is the source's ``chunk_size``, and ``GROUPS_A_PROGRAM`` was chosen
on the chip; no job key or environment variable sets them, and the result does
not depend on them beyond rounding (``tests/test_ssd_scan.py``). A sequence
that is no multiple of the chunk is padded with steps of 0, which leave the
state as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _tpu_kwargs  # (parallel, parallel, arbitrary) on the chip, nothing interpreted

__all__ = ["ssd_scan", "CHUNK"]

CHUNK = 128  # positions a chunk, and between two kept states: the lane width
GROUPS_A_PROGRAM = 2  # groups of heads a grid step walks one after the other: the chip's reading, above

_F32 = jnp.float32


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------


def _nn(p, q):  # p [M, K], q [K, N] -> p q, summed in float32
    return jnp.dot(p, q, preferred_element_type=_F32)


def _nt(p, q):  # p [M, K], q [N, K] -> p q^T
    return jax.lax.dot_general(p, q, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _tn(p, q):  # p [K, M], q [K, N] -> p^T q
    return jax.lax.dot_general(p, q, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _grid(length):
    """Row and column index of a ``[length, length]`` tile."""
    shape = (length, length)
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0), jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _exactly(product, t, ones):
    """``product`` of float32 ``t`` with a matrix ``ones`` of zeros and ones,
    every bit of ``t`` kept: ``t`` is the sum of three bfloat16 pieces, each
    product with a piece is exact on the MXU, and the three are summed in
    float32 (half the passes a float32 product at the highest precision takes,
    which splits the ones too)."""
    ones, out = ones.astype(jnp.bfloat16), None
    for _ in range(3):
        piece = t.astype(jnp.bfloat16)
        t = t - piece.astype(_F32)
        part = product(piece, ones)
        out = part if out is None else out + part
    return out


def _running(da):
    """``da`` [R, L], time on the lanes -> its running sum along the lanes: a
    product with the upper triangle of ones."""
    j, i = _grid(da.shape[1])
    return _exactly(_nn, da, j <= i)


def _columns(rows):
    """``rows`` [K, L] -> [L, K]: the same numbers with time on the sublanes,
    moved by the MXU (a product with the identity)."""
    i, j = _grid(rows.shape[1])
    return _exactly(lambda t, ones: _nt(ones, t), rows, i == j)


def _own_lanes(heads, p):
    """[R, R P]: whether lane ``l`` is head ``r``'s."""
    shape = (heads, heads * p)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    first = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * p
    return (lane >= first) & (lane < first + p)


def _by_head(per_head, heads, p):
    """``per_head`` [R, 1] -> [1, R P]: head ``r``'s number on its ``p`` lanes."""
    return jnp.sum(jnp.where(_own_lanes(heads, p), per_head, 0.0), axis=0, keepdims=True)


def _across_heads(t, heads, p):
    """``t`` [1, R P] -> [R, 1]: the sum over each head's ``p`` lanes."""
    return jnp.sum(jnp.where(_own_lanes(heads, p), t, 0.0), axis=1, keepdims=True)


def _heads_a_slab(r: int, p: int) -> int:
    """The heads whose lanes are handled together: as many of a group's as 128
    lanes hold, so that a load, a store and a product's result are whole tiles."""
    return max(k for k in range(1, r + 1) if r % k == 0 and (k == 1 or k * p <= 128))


def _groups_a_program(groups: int) -> int:
    return max(k for k in range(1, GROUPS_A_PROGRAM + 1) if groups % k == 0)


def _slab_masks(length, per, p):
    """For each head of a slab, which of the slab's lanes are its own."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (length, per * p), 1)
    return [(lane >= k * p) & (lane < (k + 1) * p) for k in range(per)]


def _only(t, masks, k):
    """``t`` [L, W] with the other heads' lanes zeroed."""
    return t if len(masks) == 1 else jnp.where(masks[k], t, jnp.zeros_like(t))


def _spread(cols, first, masks):
    """Columns ``first ...`` of ``cols`` [L, K], one a head of the slab, each
    on its head's lanes: [L, W] (or [L, 1] where a slab is one head)."""
    out = cols[:, first:first + 1]
    for k in range(1, len(masks)):
        out = jnp.where(masks[k], cols[:, first + k:first + k + 1], out)
    return out


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, h0_ref, y_ref, hs_ref, last_ref, h_scr, xw_scr,
                *, groups: int, heads: int, p: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = h0_ref[0]

    length, n = dt_ref.shape[3], h_scr.shape[1]
    every = groups * heads  # the program's heads: the small vectors of all of them are made at once
    dt, da = dt_ref[0].reshape(every, length), da_ref[0].reshape(every, length)  # [G R, L]
    per = _heads_a_slab(heads, p)
    width, masks = per * p, _slab_masks(length, per, p)
    s = _running(da)
    s_end = s[:, length - 1:]
    cols = _columns(jnp.concatenate([s, jnp.exp(s), jnp.exp(s_end - s) * dt], axis=0))  # [L, 3 G R]
    i, j = _grid(length)
    for g in range(groups):
        h = h_scr[g]  # [N, R P]: the group's state, transposed, as the chunk starts
        hs_ref[0, 0, g] = h  # the backward pass's
        bm, cm = b_ref[0, :, g * n:(g + 1) * n], c_ref[0, :, g * n:(g + 1) * n]  # [L, N]
        dtype = bm.dtype
        scores = _nt(cm, bm)  # [L, L], the group's
        before = _nn(cm, h.astype(dtype))  # [L, R P]
        for first in range(0, heads, per):
            lanes = slice(first * p, first * p + width)  # of the group's
            at = slice((g * heads + first) * p, (g * heads + first) * p + width)  # of the program's
            x = x_ref[0, :, at]
            mixing, inputs = [], []
            for k in range(per):  # one product a slab: the heads' masks side by side, their inputs one under the other
                r = g * heads + first + k
                span = jnp.where(i >= j, cols[:, r:r + 1] - s[r:r + 1, :], -jnp.inf)
                mixing.append((scores * jnp.exp(span) * dt[r:r + 1, :]).astype(dtype))
                inputs.append(_only(x, masks, k))
            own = _nn(jnp.concatenate(mixing, axis=1), jnp.concatenate(inputs, axis=0))
            y_ref[0, :, at] = own + before[:, lanes] * _spread(cols, every + g * heads + first, masks)
            xw_scr[:, lanes] = (x.astype(_F32) * _spread(cols, 2 * every + g * heads + first, masks)).astype(dtype)
        h = _by_head(jnp.exp(s_end[g * heads:(g + 1) * heads]), heads, p) * h + _tn(bm, xw_scr[...])
        h_scr[g] = h
        last_ref[0, g] = h


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, hs_ref, dy_ref, dlast_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dda_ref, dh0_ref, dh_scr, xw_scr, dyw_scr,
                *, groups: int, heads: int, p: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the last chunk: the walk is in reverse
    def _start():
        dh_scr[...] = dlast_ref[0]

    length, n = dt_ref.shape[3], dh_scr.shape[1]
    every = groups * heads
    dt, da = dt_ref[0].reshape(every, length), da_ref[0].reshape(every, length)
    per = _heads_a_slab(heads, p)
    width, masks = per * p, _slab_masks(length, per, p)
    s = _running(da)
    s_end = s[:, length - 1:]
    cols = _columns(jnp.concatenate([s, jnp.exp(s), jnp.exp(s_end - s), dt], axis=0))  # [L, 4 G R]
    j, i = _grid(length)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (every, length), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (length, every), 1)
    ds_rows = jnp.zeros((every, length), _F32)  # the running sum's gradient, by the output's position
    ds_cols = jnp.zeros((length, every), _F32)  # and what the lanes of a head sum to, time on the sublanes
    ddt_cols = jnp.zeros((length, every), _F32)
    ds_end = []
    for g in range(groups):
        dh = dh_scr[g]  # [N, R P]: the gradient of the state the chunk ends in
        h = hs_ref[0, 0, g]  # the state it starts from
        bm, cm = b_ref[0, :, g * n:(g + 1) * n], c_ref[0, :, g * n:(g + 1) * n]
        dtype = bm.dtype
        # every [L, L] tile here lies transposed, an input's position j on the rows
        # and an output's i on the lanes: the products it enters take it as it lies
        scores = _nt(bm, cm)
        before = _nn(cm, h.astype(dtype))  # [L, R P]: C h
        carried = _nn(bm, dh.astype(dtype))  # [L, R P]: B dh
        dscores = jnp.zeros((length, length), _F32)
        for first in range(0, heads, per):
            lanes = slice(first * p, first * p + width)
            at = slice((g * heads + first) * p, (g * heads + first) * p + width)
            x, dy = x_ref[0, :, at].astype(_F32), dy_ref[0, :, at]
            grow_c, from_c, dt_c = (_spread(cols, k * every + g * heads + first, masks) for k in (1, 2, 3))
            xdt, dyw = x * dt_c, dy * grow_c
            xdtb, dyb = xdt.astype(dtype), dy.astype(dtype)
            held, theirs = [], []
            for k in range(per):
                r = g * heads + first + k
                decay = jnp.exp(jnp.where(j <= i, s[r:r + 1, :] - cols[:, r:r + 1], -jnp.inf))
                held_r = scores * decay  # C B^T under the mask; the step stays with the inputs
                dheld = _nt(_only(xdtb, masks, k), dyb)
                dscores = dscores + dheld * decay
                # every term of y_i carries exp(s_i): a sum down the sublanes
                ds_rows = jnp.where(sublane == r, jnp.sum(dheld * held_r, axis=0, keepdims=True), ds_rows)
                held.append(held_r.astype(dtype))
                theirs.append(_only(dyb, masks, k))
            # the gradient of dt x
            dxdt = _nn(jnp.concatenate(held, axis=1), jnp.concatenate(theirs, axis=0)) + carried[:, lanes] * from_c
            dx_ref[0, :, at] = (dxdt * dt_c).astype(dx_ref.dtype)
            through_step = x * dxdt
            # the carried state's share of y_i carries exp(s_i) too, and all an input j gives carries exp(-s_j)
            through_sum = dyw * before[:, lanes] - xdt * dxdt
            for k in range(per):
                r = g * heads + first + k
                ddt_cols = jnp.where(lane == r, jnp.sum(_only(through_step, masks, k), axis=1, keepdims=True), ddt_cols)
                ds_cols = jnp.where(lane == r, jnp.sum(_only(through_sum, masks, k), axis=1, keepdims=True), ds_cols)
            xw_scr[:, lanes] = (xdt * from_c).astype(dtype)
            dyw_scr[:, lanes] = dyw.astype(dtype)
        xw, dyw, dsc = xw_scr[...], dyw_scr[...], dscores.astype(dtype)
        db_ref[0, :, g * n:(g + 1) * n] = (_nn(dsc, cm) + _nt(xw, dh.astype(dtype))).astype(db_ref.dtype)
        dc_ref[0, :, g * n:(g + 1) * n] = (_tn(dsc, bm) + _nt(dyw, h.astype(dtype))).astype(dc_ref.dtype)
        whole = _by_head(jnp.exp(s_end[g * heads:(g + 1) * heads]), heads, p)  # [1, R P]: the chunk's whole decay
        end = whole * h + _tn(bm, xw)  # the state the chunk ends in, every term of it under exp(s_L)
        ds_end.append(_across_heads(jnp.sum(dh * end, axis=0, keepdims=True), heads, p))  # [R, 1]
        dh = whole * dh + _tn(cm, dyw)
        dh_scr[g] = dh
        dh0_ref[0, g] = dh  # the same block for every chunk: the first chunk's stays
    ddt_ref[0] = _exactly(_tn, ddt_cols, j == i).reshape(groups, heads, length)
    # s is a running sum: a position's gradient goes to every step up to it
    dda = _exactly(_nn, ds_rows, j >= i) + _exactly(_tn, ds_cols, j >= i) + jnp.concatenate(ds_end, axis=0)
    dda_ref[0] = dda.reshape(groups, heads, length)


def _launch(kernel, x, dt, b, chunk, at, interpret):
    """What both calls share: the grid, the kernel with its sizes, and the
    blocks of a grid step, whose chunk is ``at(ci)``: ``rows`` ``[L, G' R P]``
    of ``x``'s kind, ``shared`` ``[L, G' N]``, ``steps`` ``[G', R, L]``, a
    ``state`` ``[G', N, R P]`` that stays for a group's walk, and the one
    ``kept`` a chunk."""
    import jax.experimental.pallas as pl

    batch, groups, heads, s = dt.shape
    n, width, held = b.shape[2] // groups, x.shape[2] // groups, _groups_a_program(groups)
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, groups=held, heads=heads, p=width // heads),
        grid=(batch, groups // held, s // chunk), interpret=interpret, **_tpu_kwargs(interpret))
    return call, {
        "rows": pl.BlockSpec((1, chunk, held * width), lambda bi, g, ci: (bi, at(ci), g)),
        "shared": pl.BlockSpec((1, chunk, held * n), lambda bi, g, ci: (bi, at(ci), g)),
        "steps": pl.BlockSpec((1, held, heads, chunk), lambda bi, g, ci: (bi, g, 0, at(ci))),
        "state": pl.BlockSpec((1, held, n, width), lambda bi, g, ci: (bi, g, 0, 0)),
        "kept": pl.BlockSpec((1, 1, held, n, width), lambda bi, g, ci: (bi, at(ci), g, 0, 0)),
    }, (held, n, width)


def _fwd_call(x, dt, da, b, c, h0, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    call, blocks, state = _launch(_fwd_kernel, x, dt, b, chunk, lambda ci: ci, interpret)
    rows, shared, steps, carried, kept = (blocks[k] for k in ("rows", "shared", "steps", "state", "kept"))
    batch, groups, chunks = x.shape[0], dt.shape[1], dt.shape[3] // chunk
    return call(
        in_specs=[rows, shared, shared, steps, steps, carried],
        out_specs=[rows, kept, carried],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((batch, chunks, groups, *state[1:]), _F32),
                   jax.ShapeDtypeStruct(h0.shape, _F32)],
        scratch_shapes=[pltpu.VMEM(state, _F32), pltpu.VMEM((chunk, state[2]), x.dtype)],
    )(x, b, c, dt, da, h0)


def _bwd_call(x, dt, da, b, c, hs, dy, dlast, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    chunks = dt.shape[3] // chunk
    call, blocks, state = _launch(_bwd_kernel, x, dt, b, chunk, lambda ci: chunks - 1 - ci, interpret)
    rows, shared, steps, carried, kept = (blocks[k] for k in ("rows", "shared", "steps", "state", "kept"))
    return call(
        in_specs=[rows, shared, shared, steps, steps, kept, rows, carried],
        out_specs=[rows, shared, shared, steps, steps, carried],
        out_shape=[jax.ShapeDtypeStruct(t.shape, dtype) for t, dtype in (
            (x, x.dtype), (b, b.dtype), (c, c.dtype), (dt, _F32), (da, _F32), (dlast, _F32))],
        scratch_shapes=[pltpu.VMEM(state, _F32)] + [pltpu.VMEM((chunk, state[2]), x.dtype)] * 2,
    )(x, b, c, dt, da, hs, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernels(x, dt, da, b, c, h0, chunk, interpret):
    y, _, last = _fwd_call(x, dt, da, b, c, h0, chunk, interpret)
    return y, last


def _kernels_fwd(x, dt, da, b, c, h0, chunk, interpret):
    y, hs, last = _fwd_call(x, dt, da, b, c, h0, chunk, interpret)
    return (y, last), (x, dt, da, b, c, hs)


def _kernels_bwd(chunk, interpret, kept, grads):
    x, dt, da, b, c, hs = kept
    dy, dlast = grads
    with jax.named_scope("ssd_scan_bwd"):
        dx, db, dc, ddt, dda, dh0 = _bwd_call(x, dt, da, b, c, hs, dy, dlast, chunk, interpret)
    return dx, ddt, dda, db, dc, dh0


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _on_chip(x, dt, a, b, c, h0, chunk, interpret):
    """The kernels' layouts around the kernels: a group's heads side by side on
    the lanes, the steps with time on the lanes, the state transposed."""
    batch, s, g, r, p = x.shape
    n = b.shape[-1]
    steps = dt.transpose(0, 2, 3, 1)  # [B, G, R, S]
    y, last = _kernels(
        x.reshape(batch, s, g * r * p), steps, steps * a[:, :, None], b.reshape(batch, s, g * n),
        c.reshape(batch, s, g * n), h0.transpose(0, 1, 4, 2, 3).reshape(batch, g, n, r * p), chunk, interpret)
    return y.reshape(batch, s, g, r, p), last.reshape(batch, g, n, r, p).transpose(0, 1, 3, 4, 2)


def _tiles(r: int, p: int, n: int, chunk: int) -> bool:
    """Whether the compiled kernels' blocks are whole tiles of the chip."""
    return chunk % 128 == 0 and n % 128 == 0 and (_heads_a_slab(r, p) * p) % 128 == 0 and r % 8 == 0


# --------------------------------------------------------------------------
# The einsum form
# --------------------------------------------------------------------------


def _sums(dt, a, chunk):
    """``dt`` [B, S, G, R] float32 and ``a`` [G, R] -> ``dt`` and the running
    sum of ``dt a`` inside each chunk, both [B, chunks, G, R, L]."""
    batch, s = dt.shape[:2]
    dt = dt.reshape(batch, s // chunk, chunk, *a.shape).transpose(0, 1, 3, 4, 2)
    return dt, jnp.cumsum(dt * a[..., None], axis=-1)


def _chunk_states(x, dt, a, b, chunk):
    """What each chunk adds to the state, ``[B, chunks, G, R, P, N]`` float32,
    and the decay over the whole chunk, ``[B, chunks, G, R]``."""
    batch, s, g, r, p = x.shape
    dt, cum = _sums(dt, a, chunk)
    to_end = (jnp.exp(cum[..., -1:] - cum) * dt).transpose(0, 1, 4, 2, 3)  # [B, c, L, G, R]
    xs = x.reshape(batch, s // chunk, chunk, g, r, p)
    weighted = (xs.astype(jnp.float32) * to_end[..., None]).astype(x.dtype)
    states = jnp.einsum(
        "bcjgrp,bcjgn->bcgrpn", weighted, b.reshape(batch, s // chunk, chunk, g, -1),
        preferred_element_type=jnp.float32)
    return states, jnp.exp(cum[..., -1])


def _chunk_outputs(x, dt, a, b, c, starts, chunk):
    """``y`` [B, S, G, R, P] float32 from the chunk's own positions and from
    ``starts`` [B, chunks, G, R, P, N], the state each chunk starts from."""
    batch, s, g, r, p = x.shape
    n = s // chunk
    dt, cum = _sums(dt, a, chunk)
    xs, bs, cs = (t.reshape(batch, n, chunk, *t.shape[2:]) for t in (x, b, c))
    scores = jnp.einsum("bcign,bcjgn->bcgij", cs, bs, preferred_element_type=jnp.float32)
    rows = jnp.arange(chunk)
    span = jnp.where(rows[:, None] >= rows[None, :], cum[..., :, None] - cum[..., None, :], -jnp.inf)
    mixing = scores[:, :, :, None] * jnp.exp(span) * dt[..., None, :]  # [B, c, G, R, i, j]
    own = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixing.astype(x.dtype), xs,
                     preferred_element_type=jnp.float32)
    before = jnp.einsum("bcign,bcgrpn->bcigrp", cs, starts.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    y = own + before * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(batch, s, g, r, p)


def _carry(states, decay, h0):
    """The recurrence over chunk boundaries: the state each chunk starts from,
    stacked on axis 1, and the state after the last."""

    def boundary(h, t):
        state, d = t
        return d[..., None, None] * h + state, h

    last, starts = jax.lax.scan(boundary, h0, (states.swapaxes(0, 1), decay.swapaxes(0, 1)))
    return starts.swapaxes(0, 1), last


def _scan_fwd(x, dt, a, b, c, h0, chunk):
    starts, last = _carry(*_chunk_states(x, dt, a, b, chunk), h0)
    return (_chunk_outputs(x, dt, a, b, c, starts, chunk), last), (x, dt, a, b, c, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, h0, chunk):
    return _scan_fwd(x, dt, a, b, c, h0, chunk)[0]


def _scan_bwd(chunk, kept, grads):
    x, dt, a, b, c, starts = kept
    dy, dlast = grads
    _, outputs_vjp = jax.vjp(lambda *t: _chunk_outputs(*t, chunk), x, dt, a, b, c, starts)
    dx, ddt, da, db, dc, dstarts = outputs_vjp(dy)
    (_, decay), states_vjp = jax.vjp(lambda *t: _chunk_states(*t, chunk), x, dt, a, b)

    def boundary(g, t):  # h' = d h + state, and h was handed to its chunk
        d, h, dstart = t
        return d[..., None, None] * g + dstart, (g, jnp.sum(g * h, axis=(-2, -1)))

    dh0, (dstates, ddecay) = jax.lax.scan(
        boundary, dlast, (decay.swapaxes(0, 1), starts.swapaxes(0, 1), dstarts.swapaxes(0, 1)),
        reverse=True)
    more = states_vjp((dstates.swapaxes(0, 1), ddecay.swapaxes(0, 1)))
    dx, ddt, da, db = (one + two for one, two in zip((dx, ddt, da, db), more))
    return dx, ddt, da, db, dc, dh0


_scan.defvjp(_scan_fwd, _scan_bwd)


# --------------------------------------------------------------------------


def ssd_scan(
    x: jnp.ndarray,  # [B, S, H, P] the heads' inputs
    dt: jnp.ndarray,  # [B, S, H] the step, positive (after its softplus)
    a: jnp.ndarray,  # [H] negative
    b: jnp.ndarray,  # [B, S, G, N], a group of H / G heads shares one
    c: jnp.ndarray,  # [B, S, G, N]
    *,
    state: jnp.ndarray | None = None,  # [B, H, P, N] float32: where the sequence starts from
    chunk: int = CHUNK,
    interpret: bool | None = None,
) -> tuple:
    """``y`` [B, S, H, P] float32 and the state after the last position
    [B, H, P, N] float32. The ``D x`` term and the gate are the caller's.

    ``interpret=None`` is the program's call: the kernels, compiled, on an
    accelerator whose tiles the shapes fill, and the einsum form elsewhere.
    ``False`` and ``True`` ask for the kernels, compiled or interpreted (the
    tests')."""
    batch, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    with jax.named_scope("ssd_scan"):
        if interpret is None:
            from ..hw import is_accelerator

            kernels = is_accelerator() and _tiles(r, p, n, chunk)
        else:
            kernels = True
            if not interpret and not _tiles(r, p, n, chunk):
                raise ValueError(f"the compiled kernels tile a chunk and a state that are multiples of 128 and groups "
                                 f"of 8 k heads over a multiple of 128 lanes, not {chunk}, {n} and {r} heads of {p}")
        pad = (-s) % chunk
        if pad:  # a step of 0 neither decays the state nor adds to it
            x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, b, c))
        h0 = jnp.zeros((batch, h, p, n), jnp.float32) if state is None else state.astype(jnp.float32)
        args = (x.reshape(batch, s + pad, g, r, p), dt.astype(jnp.float32).reshape(batch, s + pad, g, r),
                a.astype(jnp.float32).reshape(g, r), b, c.astype(b.dtype), h0.reshape(batch, g, r, p, n))
        y, last = _on_chip(*args, chunk, bool(interpret)) if kernels else _scan(*args, chunk)
        return y.reshape(batch, s + pad, h, p)[:, :s], last.reshape(batch, h, p, n)
