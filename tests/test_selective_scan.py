"""Mamba-1's selective scan in chunks (ops/selective_scan.py) against the plain
recurrence over positions: forward and gradients in float32, whatever the
chunk and step lengths, with a carried state, and finite where the decay is
steep over a long sequence; the XLA form, which the CPU runs, and the chip's
two kernels, interpreted."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.ops import selective_scan as op
from hypha_tpu.ops.selective_scan import selective_scan


def plain(x, dt, a, b, c, d, h0=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t . h_t + D x_t``,
    one position after the other."""
    h = jnp.zeros((x.shape[0], *a.shape)) if h0 is None else h0

    def position(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + d * x_t

    h, y = jax.lax.scan(position, h, tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1), h


def inputs(s=50, width=8, state=4, batch=2, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (
        jax.random.normal(k[0], (batch, s, width)),
        jax.nn.softplus(jax.random.normal(k[1], (batch, s, width))),
        -jnp.exp(jax.random.normal(k[2], (width, state))),
        jax.random.normal(k[3], (batch, s, state)),
        jax.random.normal(k[4], (batch, s, state)),
        jax.random.normal(k[5], (width,)),
    )


# (chunk, step): the program's own, three more chunk lengths, a chunk of one
# sub-chunk, and steps that do not divide the sequence of 50
LENGTHS = [(op.XLA_CHUNK, op.XLA_STEP), (16, 4), (8, 8), (12, 2), (64, 16), (32, 1)]


@pytest.mark.parametrize("chunk,step", LENGTHS)
def test_the_scan_is_the_plain_recurrence_at_every_chunk_length(chunk, step):
    args = inputs()
    want, want_h = plain(*args)
    y, h = selective_scan(*args, chunk=chunk, step=step)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want, atol=5e-6)
    np.testing.assert_allclose(h, want_h, atol=5e-6)


@pytest.mark.parametrize("chunk,step", LENGTHS[:4])
def test_every_inputs_gradient_is_the_plain_recurrences(chunk, step):
    args = inputs()
    loss = lambda f: lambda *t: (f(*t)[0] ** 2).sum() + f(*t)[1].sum()
    want = jax.grad(loss(plain), argnums=tuple(range(6)))(*args)
    got = jax.grad(loss(lambda *t: selective_scan(*t, chunk=chunk, step=step)), argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= 2e-5 * float(jnp.abs(w).max())


@pytest.mark.parametrize("s", [1, 7, 50, 130])
def test_a_sequence_that_is_no_multiple_of_the_chunk_reads_the_same(s):
    args = inputs(s=s)
    want, want_h = plain(*args)
    y, h = selective_scan(*args, chunk=32, step=4)
    assert y.shape == want.shape
    np.testing.assert_allclose(y, want, atol=5e-6)
    np.testing.assert_allclose(h, want_h, atol=5e-6)  # the padding left the state alone


def test_the_softplus_taken_inside_is_the_softplus_taken_outside():
    x, dt, a, b, c, d = inputs(s=37)
    raw = jax.random.normal(jax.random.key(9), dt.shape)
    want = jax.grad(lambda r: selective_scan(x, jax.nn.softplus(r), a, b, c, d, chunk=16, step=4)[0].sum())(raw)
    got = jax.grad(lambda r: selective_scan(x, r, a, b, c, d, dt_softplus=True, chunk=16, step=4)[0].sum())(raw)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert bool(jnp.isfinite(got).all())  # the padded positions' -inf gave no nan


def test_the_carried_state_of_a_sequence_split_in_two_is_the_wholes():
    x, dt, a, b, c, d = inputs(s=60)
    whole, whole_h = selective_scan(x, dt, a, b, c, d, chunk=16, step=4)
    cut = 23
    first, h = selective_scan(x[:, :cut], dt[:, :cut], a, b[:, :cut], c[:, :cut], d, chunk=16, step=4)
    second, h = selective_scan(x[:, cut:], dt[:, cut:], a, b[:, cut:], c[:, cut:], d, h, chunk=16, step=4)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=5e-6)
    np.testing.assert_allclose(h, whole_h, atol=5e-6)


def test_gradients_are_finite_where_the_decay_is_steep_over_8192_positions():
    """A step near 0.1 and A down to -16: a decay of exp(-1.6) a position, which
    a cumulative form would raise to exp(+1.6 x chunk) and overflow."""
    s, width, state = 8192, 4, 16
    k = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(k[0], (1, s, width))
    dt = jnp.full((1, s, width), 0.1) + 0.01 * jax.random.uniform(k[1], (1, s, width))
    a = -jnp.broadcast_to(jnp.arange(1.0, state + 1), (width, state))
    b, c = jax.random.normal(k[2], (1, s, state)), jax.random.normal(k[3], (1, s, state))
    value, grads = jax.value_and_grad(
        lambda *t: (selective_scan(*t, jnp.ones(width))[0] ** 2).mean(), argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    assert np.isfinite(float(value)) and float(value) > 0
    assert all(bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0 for g in grads)


def test_the_scan_keeps_nothing_but_chunk_boundaries_for_the_backward_pass():
    """What the backward pass is handed: the inputs and one state a chunk, not
    the 64 x 8 x 4 states of every position."""
    x, dt, a, b, c, d = inputs(s=64, batch=1)
    _, vjp = jax.vjp(lambda *t: selective_scan(*t, d, chunk=16, step=4)[0], x, dt, a, b, c)
    kept = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(vjp) if hasattr(t, "shape"))
    inputs_, boundaries, every_position = 64 * (2 * 8 + 2 * 4), 4 * 8 * 4, 64 * 8 * 4
    assert inputs_ + boundaries <= kept < inputs_ + every_position // 2


# --------------------------------------------------------------------------
# The chip's kernels, interpreted: one shape, compiled once for the module
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kernels():
    """8142 positions (no multiple of the 128 a chunk holds) of 128 channels
    and 16 states, a step near 0.1 and A down to -16, bf16 ``x`` and step with
    the softplus taken inside, a state carried in and out: the kernels' loss
    and every gradient beside the plain recurrence's on the same numbers."""
    s, width, state = 8142, 128, 16
    k = jax.random.split(jax.random.key(2), 6)
    x = jax.random.normal(k[0], (1, s, width)).astype(jnp.bfloat16)
    raw = (jnp.log(jnp.expm1(0.1)) + 0.3 * jax.random.normal(k[1], (1, s, width))).astype(jnp.bfloat16)
    a = -jnp.broadcast_to(jnp.arange(1.0, state + 1), (width, state)) * (1 + 0.1 * jax.random.uniform(k[2], (width, state)))
    b, c = jax.random.normal(k[3], (1, s, state)), jax.random.normal(k[4], (1, s, state))
    d, h0 = jnp.ones(width), jax.random.normal(k[5], (1, width, state))
    weigh = jax.random.normal(jax.random.key(3), (1, s, width))

    def loss(scan):
        def f(x, raw, a, b, c, d, h0):
            y, last = scan(x, raw, a, b, c, d, h0)
            return (y * weigh).mean() + (last ** 2).mean(), (y, last)
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(7)), has_aux=True))

    f32 = lambda t: t.astype(jnp.float32)
    want = loss(lambda x, raw, a, b, c, d, h0: plain(f32(x), jax.nn.softplus(f32(raw)), a, b, c, d, h0))(x, raw, a, b, c, d, h0)
    got = loss(lambda *t: selective_scan(*t, dt_softplus=True, interpret=True))(x, raw, a, b, c, d, h0)
    return want, got


def test_the_kernels_are_the_plain_recurrence_forward(kernels):
    ((want, (want_y, want_last)), _), ((got, (y, last)), _) = kernels
    assert y.shape == want_y.shape == (1, 8142, 128) and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(last, want_last, atol=2e-5)  # the padding left the carried state alone
    assert abs(float(got) - float(want)) < 1e-6


@pytest.mark.parametrize("argument", range(7), ids="x dt a b c d h0".split())
def test_the_kernels_gradients_are_the_plain_recurrences(kernels, argument):
    (_, want), (_, got) = kernels
    w, g = want[argument].astype(jnp.float32), got[argument].astype(jnp.float32)
    assert g.shape == w.shape and bool(jnp.isfinite(g).all()) and float(jnp.abs(w).max()) > 0
    tolerance = 1e-2 if argument < 2 else 2e-4  # x's and the step's come back in bfloat16
    assert float(jnp.abs(g - w).max()) <= tolerance * float(jnp.abs(w).max()), argument


def test_the_program_takes_the_xla_form_off_the_chip_and_the_kernels_only_where_they_tile():
    args = inputs(s=20, width=8)
    with pytest.raises(ValueError, match="multiple of 128"):
        selective_scan(*args, interpret=True)
    y, _ = selective_scan(*args)  # interpret=None: the program's call, here the XLA form
    np.testing.assert_allclose(y, plain(*args)[0], atol=5e-6)
    assert (op.CHUNK, op._block_d(5120), op._block_d(384), op._block_d(100)) == (128, op.BLOCK_D, 128, None)
