"""Mamba-1's selective scan, forward and backward, in chunks.

``h_t = exp(dt_t A) * h_{t-1} + dt_t B_t x_t``, ``y_t = C_t . h_t + D * x_t``:
a state of ``[d_inner, d_state]`` a sequence, carried through every position,
in float32. Materialised whole the states of one 8192-token sequence at
``d_inner`` 5120 and state 16 are 2.7 GB a layer, so nothing but chunk
boundaries is kept, and the backward pass makes the states inside a chunk
again. Nothing is exponentiated but ``dt * A <= 0``, so no product overflows
whatever a chunk's length. The operator is bound by the sequential dependency
and the vector unit, not by the MXU.

**On the chip: two Pallas kernels** under a ``custom_vjp``. The grid is
(batch, block of ``BLOCK_D`` channels, chunk of ``CHUNK`` positions); the
chunks of one block run in order and the state ``[d_state, BLOCK_D]`` lives in
VMEM scratch across them: ``d_inner`` lies on the lanes and the 16 states on
the sublanes (with ``d_state`` minor a tile would be seven eighths padding),
and a position's ``B_t`` and ``C_t`` are a column of a ``[d_state, CHUNK]``
tile (time on the lanes, so ``CHUNK`` is the lane width, 128, and the steps
of a chunk are unrolled with static columns). The forward kernel also writes
the state each chunk starts from (``S / CHUNK`` states, 21 MB at the sizes
above). The backward kernel walks the chunks in reverse: from a chunk's start
state it makes the chunk's states again into VMEM, then goes back through its
positions carrying ``dh``, and accumulates ``dA`` in VMEM; the gradients of
``B`` and ``C`` come out as one part a channel block and are summed outside.
A whole scan is one device event forward and one backward.

**Elsewhere** (the CPU's tests and rehearsals, widths that are no multiple of
128): plain XLA. A ``lax.scan`` over chunks of ``XLA_CHUNK`` positions carries
``h``, its body under ``jax.checkpoint``; inside a chunk the sub-chunks of
``XLA_STEP`` positions advance side by side, first each from a zero state,
then a short scan over their ends with each sub-chunk's whole decay gives the
state each starts from, then the same steps again from the true states, which
emit ``y``. On the chip this form reads 8.8 ms forward and 31 ms forward and
backward at the sizes above where the kernels read 3.1 and 12.6, and it is a
hundred thousand device events a step, which no profile of a round survives
(PERF.md 6, PR 44).

``CHUNK``, ``BLOCK_D``, ``XLA_CHUNK`` and ``XLA_STEP`` are constants chosen on
the chip; no job key or environment variable sets them, and the result does
not depend on them beyond rounding (tests/test_selective_scan.py). A sequence
that is no multiple of the chunk is padded with a step of 0, which leaves the
state as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _tpu_kwargs  # (parallel, parallel, arbitrary) on the chip, nothing interpreted

__all__ = ["selective_scan", "CHUNK", "BLOCK_D"]

CHUNK = 128  # positions a grid step, and between two kept states: the lane width
BLOCK_D = 512  # channels a grid step: the state tile is [d_state, BLOCK_D] f32, 8 vregs at 16 states
XLA_CHUNK = 32  # the XLA form's positions between two kept states
XLA_STEP = 8  # its sequential steps a chunk: XLA_CHUNK // XLA_STEP sub-chunks advance side by side


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------


def _softplus(t):
    return jnp.where(t > 20.0, t, jnp.log1p(jnp.exp(jnp.minimum(t, 20.0))))


def _fwd_kernel(x_ref, dt_ref, bt_ref, ct_ref, a_ref, h0_ref, y_ref, hs_ref, last_ref,
                h_scr, dt_scr, dtx_scr, *, softplus: bool):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = h0_ref[0]

    hs_ref[0, 0] = h_scr[...]  # the state this chunk starts from: the backward pass's
    dt = dt_ref[0].astype(jnp.float32)
    dt_scr[...] = _softplus(dt) if softplus else dt
    dtx_scr[...] = dt_scr[...] * x_ref[0].astype(jnp.float32)
    a, bt, ct = a_ref[...], bt_ref[0], ct_ref[0]  # [N, D], [N, L], [N, L]
    h = h_scr[...]
    for t in range(dt_scr.shape[0]):
        h = jnp.exp(dt_scr[t:t + 1, :] * a) * h + bt[:, t:t + 1] * dtx_scr[t:t + 1, :]
        y_ref[0, t:t + 1, :] = jnp.sum(h * ct[:, t:t + 1], axis=0, keepdims=True)
    h_scr[...] = h
    last_ref[0] = h


def _bwd_kernel(x_ref, dt_ref, bt_ref, ct_ref, a_ref, hs_ref, dy_ref, dlast_ref,
                dx_ref, ddt_ref, dbt_ref, dct_ref, da_ref, dh0_ref,
                dh_scr, da_scr, before, dt_scr, x_scr, dx_scr, ddt_scr, *, softplus: bool):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the last chunk: the walk is in reverse
    def _start():
        dh_scr[...] = dlast_ref[0]
        da_scr[...] = jnp.zeros_like(da_scr)

    raw = dt_ref[0].astype(jnp.float32)
    dt_scr[...] = _softplus(raw) if softplus else raw
    x_scr[...] = x_ref[0].astype(jnp.float32)
    a, bt, ct = a_ref[...], bt_ref[0], ct_ref[0]
    length = dt_scr.shape[0]
    h = hs_ref[0, 0]
    for t in range(length):  # the chunk's states again: before[t] is the state before position t
        before[t] = h
        dt_t = dt_scr[t:t + 1, :]
        h = jnp.exp(dt_t * a) * h + bt[:, t:t + 1] * (dt_t * x_scr[t:t + 1, :])
    dh, da = dh_scr[...], da_scr[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    dbt, dct = jnp.zeros_like(bt), jnp.zeros_like(ct)
    for t in reversed(range(length)):
        dt_t, x_t, dy_t = dt_scr[t:t + 1, :], x_scr[t:t + 1, :], dy_ref[0, t:t + 1, :]
        b_t, c_t, h_prev = bt[:, t:t + 1], ct[:, t:t + 1], before[t]
        decay, dtx = jnp.exp(dt_t * a), dt_t * x_t
        h_t = decay * h_prev + b_t * dtx
        dh = dh + c_t * dy_t
        dct = jnp.where(lane == t, jnp.sum(h_t * dy_t, axis=1, keepdims=True), dct)
        dbt = jnp.where(lane == t, jnp.sum(dh * dtx, axis=1, keepdims=True), dbt)
        through_input = jnp.sum(dh * b_t, axis=0, keepdims=True)  # d(dt_t x_t)
        through_decay = dh * h_prev * decay  # d(dt_t A), elementwise
        ddt_scr[t:t + 1, :] = jnp.sum(through_decay * a, axis=0, keepdims=True) + through_input * x_t
        dx_scr[t:t + 1, :] = through_input * dt_t
        da = da + through_decay * dt_t
        dh = dh * decay
    dh_scr[...], da_scr[...] = dh, da
    ddt = ddt_scr[...] * jax.nn.sigmoid(raw) if softplus else ddt_scr[...]
    ddt_ref[0] = ddt.astype(ddt_ref.dtype)
    dx_ref[0] = dx_scr[...].astype(dx_ref.dtype)
    dbt_ref[0, 0], dct_ref[0, 0] = dbt, dct
    da_ref[0], dh0_ref[0] = da, dh  # the same block for every chunk: the first chunk's stays


def _fwd_call(x, dt, bt, ct, a_t, h0, softplus, block_d, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, d = x.shape
    n, chunks = a_t.shape[0], s // CHUNK
    rows = pl.BlockSpec((1, CHUNK, block_d), lambda b, j, c: (b, c, j))
    cols = pl.BlockSpec((1, n, CHUNK), lambda b, j, c: (b, 0, c))
    state = pl.BlockSpec((1, n, block_d), lambda b, j, c: (b, 0, j))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, softplus=softplus),
        grid=(batch, d // block_d, chunks),
        in_specs=[rows, rows, cols, cols, pl.BlockSpec((n, block_d), lambda b, j, c: (0, j)), state],
        out_specs=[rows, pl.BlockSpec((1, 1, n, block_d), lambda b, j, c: (b, c, 0, j)), state],
        out_shape=[jax.ShapeDtypeStruct((batch, s, d), f32),
                   jax.ShapeDtypeStruct((batch, chunks, n, d), f32),
                   jax.ShapeDtypeStruct((batch, n, d), f32)],
        scratch_shapes=[pltpu.VMEM((n, block_d), f32), pltpu.VMEM((CHUNK, block_d), f32),
                        pltpu.VMEM((CHUNK, block_d), f32)],
        interpret=interpret, **_tpu_kwargs(interpret),
    )(x, dt, bt, ct, a_t, h0)


def _bwd_call(x, dt, bt, ct, a_t, hs, dy, dlast, softplus, block_d, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, d = x.shape
    n, chunks, blocks = a_t.shape[0], s // CHUNK, d // block_d
    back = lambda c: chunks - 1 - c
    rows = pl.BlockSpec((1, CHUNK, block_d), lambda b, j, c: (b, back(c), j))
    cols = pl.BlockSpec((1, n, CHUNK), lambda b, j, c: (b, 0, back(c)))
    state = pl.BlockSpec((1, n, block_d), lambda b, j, c: (b, 0, j))
    parts = pl.BlockSpec((1, 1, n, CHUNK), lambda b, j, c: (b, j, 0, back(c)))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, softplus=softplus),
        grid=(batch, blocks, chunks),
        in_specs=[rows, rows, cols, cols, pl.BlockSpec((n, block_d), lambda b, j, c: (0, j)),
                  pl.BlockSpec((1, 1, n, block_d), lambda b, j, c: (b, back(c), 0, j)), rows, state],
        out_specs=[rows, rows, parts, parts, state, state],
        out_shape=[jax.ShapeDtypeStruct((batch, s, d), x.dtype), jax.ShapeDtypeStruct((batch, s, d), dt.dtype),
                   jax.ShapeDtypeStruct((batch, blocks, n, s), f32), jax.ShapeDtypeStruct((batch, blocks, n, s), f32),
                   jax.ShapeDtypeStruct((batch, n, d), f32), jax.ShapeDtypeStruct((batch, n, d), f32)],
        scratch_shapes=[pltpu.VMEM((n, block_d), f32), pltpu.VMEM((n, block_d), f32),
                        pltpu.VMEM((CHUNK, n, block_d), f32)]
        + [pltpu.VMEM((CHUNK, block_d), f32)] * 4,
        interpret=interpret, **_tpu_kwargs(interpret),
    )(x, dt, bt, ct, a_t, hs, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kernels(x, dt, bt, ct, a_t, h0, softplus, block_d, interpret):
    y, _, last = _fwd_call(x, dt, bt, ct, a_t, h0, softplus, block_d, interpret)
    return y, last


def _kernels_fwd(x, dt, bt, ct, a_t, h0, softplus, block_d, interpret):
    y, hs, last = _fwd_call(x, dt, bt, ct, a_t, h0, softplus, block_d, interpret)
    return (y, last), (x, dt, bt, ct, a_t, hs)


def _kernels_bwd(softplus, block_d, interpret, kept, grads):
    x, dt, bt, ct, a_t, hs = kept
    dy, dlast = grads
    with jax.named_scope("selective_scan_bwd"):
        dx, ddt, dbt, dct, da, dh0 = _bwd_call(
            x, dt, bt, ct, a_t, hs, dy, dlast, softplus, block_d, interpret)
    return dx, ddt, dbt.sum(1), dct.sum(1), da.sum(0), dh0


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _block_d(width: int) -> int | None:
    return next((b for b in (BLOCK_D, 256, 128) if width % b == 0), None)


# --------------------------------------------------------------------------
# The XLA form
# --------------------------------------------------------------------------


def _chunk(a, h0, x, dt, b, c, step: int, softplus: bool):
    """One chunk: ``a`` [N, D], ``h0`` [B, N, D], ``x`` and ``dt`` [B, L, D],
    ``b`` and ``c`` [B, L, N], in whatever type the caller keeps them ->
    (the state after the chunk, ``y`` [B, L, D]), float32."""
    batch, length, d = x.shape
    m = length // step
    x, dt, b, c = (t.astype(jnp.float32) for t in (x, dt, b, c))
    if softplus:
        dt = jax.nn.softplus(dt)

    def by_step(t):  # [B, L, F] -> [step, B, m, F]: position j of every sub-chunk
        return t.reshape(batch, m, step, t.shape[-1]).transpose(2, 0, 1, 3)

    x, dt, b, c = by_step(x), by_step(dt), by_step(b), by_step(c)

    def advance(h, x_t, dt_t, b_t):  # h [B, m, N, D]
        decay = jnp.exp(dt_t[:, :, None, :] * a)
        return decay * h + b_t[..., None] * (dt_t * x_t)[:, :, None, :]

    ends, _ = jax.lax.scan(
        lambda h, t: (advance(h, *t), None),
        jnp.zeros((batch, m, *a.shape), jnp.float32), (x, dt, b))
    whole = jnp.exp(dt.sum(0)[:, :, None, :] * a)  # a sub-chunk's decay, start to end

    def carry(h, sub):  # h [B, N, D]: the state a sub-chunk starts from
        end, decay = sub
        return decay * h + end, h

    last, starts = jax.lax.scan(carry, h0, (ends.swapaxes(0, 1), whole.swapaxes(0, 1)))

    def emit(h, t):
        x_t, dt_t, b_t, c_t = t
        h = advance(h, x_t, dt_t, b_t)
        return h, jnp.sum(h * c_t[..., None], axis=2)  # [B, m, D]

    _, y = jax.lax.scan(emit, starts.swapaxes(0, 1), (x, dt, b, c))
    return last, y.transpose(1, 2, 0, 3).reshape(batch, length, d)


def _xla(x, dt, b, c, a_t, h0, softplus: bool, chunk: int, step: int):
    batch, s, width = x.shape
    n = s // chunk

    def chunks(t):  # [B, S, F] -> [n, B, chunk, F]
        return t.reshape(batch, n, chunk, t.shape[-1]).swapaxes(0, 1)

    body = jax.checkpoint(lambda h, t: _chunk(a_t, h, *t, step, softplus))
    last, y = jax.lax.scan(body, h0, tuple(map(chunks, (x, dt, b, c))))
    return y.swapaxes(0, 1).reshape(batch, s, width), last


# --------------------------------------------------------------------------


def selective_scan(x, dt, a, b, c, d, h0=None, *, dt_softplus: bool = False,
                   interpret: bool | None = None, chunk: int = XLA_CHUNK, step: int = XLA_STEP):
    """``x`` and ``dt`` [B, S, D], ``a`` [D, N] (negative), ``b`` and ``c``
    [B, S, N], ``d`` [D], ``h0`` [B, D, N] or None for zeros -> (``y``
    [B, S, D], the state after the last position [B, D, N]), both float32.
    ``dt`` is the step itself, or with ``dt_softplus`` what its softplus is
    taken of, chunk by chunk: ``x`` and ``dt`` are kept in the caller's type
    and made float32 inside a chunk, so the backward pass keeps no float32
    copy of a whole sequence.

    ``interpret=None`` is the program's call: the kernels, compiled, on an
    accelerator whose width they tile, and the XLA form elsewhere. ``False``
    and ``True`` ask for the kernels, compiled or interpreted (the tests');
    ``chunk`` and ``step`` are the XLA form's, for the tests alone."""
    with jax.named_scope("selective_scan"):
        batch, s, width = x.shape
        block_d = _block_d(width)
        if interpret is None:
            from ..hw import is_accelerator

            kernels = is_accelerator() and block_d is not None
        else:
            kernels = True
            if block_d is None:
                raise ValueError(f"the kernels tile a width that is a multiple of 128, not {width}")
        chunk = CHUNK if kernels else min(chunk, -(-s // step) * step)
        pad = (-s) % chunk
        inputs = (x, dt, b, c)
        if pad:  # a step of 0 (softplus of -inf): a decay of one and nothing added
            fill = (0.0, -jnp.inf if dt_softplus else 0.0, 0.0, 0.0)
            inputs = tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0)), constant_values=f)
                           for t, f in zip(inputs, fill))
        a_t = a.astype(jnp.float32).T
        h0 = (jnp.zeros((batch, *a_t.shape), jnp.float32) if h0 is None
              else h0.astype(jnp.float32).swapaxes(1, 2))
        if kernels:
            xp, dtp, bp, cp = inputs
            bt, ct = (t.astype(jnp.float32).swapaxes(1, 2) for t in (bp, cp))  # time on the lanes
            y, last = _kernels(xp, dtp, bt, ct, a_t, h0, dt_softplus, block_d, bool(interpret))
        else:
            y, last = _xla(*inputs, a_t, h0, dt_softplus, chunk, step)
        y = y[:, :s] + d.astype(jnp.float32) * x.astype(jnp.float32)
        return y, last.swapaxes(1, 2)
