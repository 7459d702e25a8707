"""Mamba-2's scan (state-space duality, arXiv:2405.21060), forward and
backward, as matrix products over chunks.

A head's state is a matrix ``[P, N]`` (head size by state size) with one scalar
decay a position: ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t
C_t``, in float32; ``B`` and ``C`` are shared by the heads of a group. Written
out over a chunk of ``L`` positions with ``c`` the running sum of ``dt A``
inside the chunk, the work is four batched products and no recurrence over
positions:

* ``C B^T`` of the chunk, ``[L, L]`` a group, under the decay mask ``exp(c_i -
  c_j)`` for ``j <= i`` (0 above the diagonal) and times ``dt_j``;
* that matrix times the chunk's inputs ``[L, P]``: what the chunk's own
  positions give;
* the chunk's state ``sum_j exp(c_L - c_j) dt_j x_j B_j^T``, ``[P, N]`` a head;
* ``exp(c_i) C_i`` against the state the chunk starts from: what came before.

Between chunks a recurrence of ``S / L`` steps carries the state, ``H <-
exp(c_L) H + the chunk's state``: elementwise on ``[batch, heads, P, N]``
float32. Every exponent is a sum of ``dt A <= 0`` over a span inside one
chunk, formed as a difference of running sums that are masked *before* the
exponential, so nothing overflows whatever the decay, and no running sum is
longer than a chunk (a difference of sums over 8192 positions would lose the
small decays to rounding).

**Backward** (``jax.custom_vjp``): the forward pass keeps its inputs and the
state each chunk starts from (``S / L`` states of ``heads x P x N`` float32: 64
x 2 MB a sequence at 64 heads of 64 by 128) and nothing else. The backward pass
makes the masks and products of every chunk again from them (``jax.vjp`` of the
two chunk-local halves) and runs the boundary recurrence in reverse by hand.

The products are plain ``einsum``s, which XLA lowers to the MXU with the
operands in the inputs' type (bfloat16 in training) and float32 sums; the
running sums, the exponentials and the carried state are float32 whatever the
inputs. ``CHUNK`` is the source's ``chunk_size``; no job key or environment
variable sets it, and the result does not depend on it beyond rounding
(``tests/test_ssd_scan.py``). A sequence that is no multiple of the chunk is
padded with steps of 0, which leave the state as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "CHUNK"]

CHUNK = 128  # positions a chunk, and between two kept states


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


def ssd_scan(
    x: jnp.ndarray,  # [B, S, H, P] the heads' inputs
    dt: jnp.ndarray,  # [B, S, H] the step, positive (after its softplus)
    a: jnp.ndarray,  # [H] negative
    b: jnp.ndarray,  # [B, S, G, N], a group of H / G heads shares one
    c: jnp.ndarray,  # [B, S, G, N]
    *,
    state: jnp.ndarray | None = None,  # [B, H, P, N] float32: where the sequence starts from
    chunk: int = CHUNK,
) -> tuple:
    """``y`` [B, S, H, P] float32 and the state after the last position
    [B, H, P, N] float32. The ``D x`` term and the gate are the caller's."""
    batch, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    with jax.named_scope("ssd_scan"):
        pad = (-s) % chunk
        if pad:  # a step of 0 neither decays the state nor adds to it
            x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, b, c))
        h0 = jnp.zeros((batch, h, p, n), jnp.float32) if state is None else state.astype(jnp.float32)
        y, last = _scan(
            x.reshape(batch, s + pad, g, r, p), dt.astype(jnp.float32).reshape(batch, s + pad, g, r),
            a.astype(jnp.float32).reshape(g, r), b, c.astype(b.dtype), h0.reshape(batch, g, r, p, n), chunk)
        return y.reshape(batch, s + pad, h, p)[:, :s], last.reshape(batch, h, p, n)
