"""Mamba-2's scan as matrix products over chunks (ops/ssd_scan.py) against the
plain recurrence over positions: forward and every input's gradient in float32
at three chunk lengths and at sequences that are no multiple of the chunk, a
sequence split in two with the state carried, the groups' sharing of ``B`` and
``C``, and finite values and gradients where the decay is steep over a long
sequence."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.ops import ssd_scan as op
from hypha_tpu.ops.ssd_scan import ssd_scan


def plain(x, dt, a, b, c, h0=None):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t``, one
    position after the other; head ``h`` reads group ``h // (H / G)``."""
    batch, _, heads, p = x.shape
    g, n = b.shape[2:]
    b, c = (jnp.repeat(t, heads // g, axis=2) for t in (b, c))  # [B, S, H, N]
    h = jnp.zeros((batch, heads, p, n)) if h0 is None else h0

    def position(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t * a)[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h, y = jax.lax.scan(position, h, tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1), h


def inputs(s=150, heads=4, p=8, groups=2, n=16, batch=2, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    return (
        jax.random.normal(k[0], (batch, s, heads, p)),
        jax.nn.softplus(jax.random.normal(k[1], (batch, s, heads)) - 2.0),
        -jnp.exp(jax.random.normal(k[2], (heads,))),
        jax.random.normal(k[3], (batch, s, groups, n)),
        jax.random.normal(k[4], (batch, s, groups, n)),
    )


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# three chunk lengths at three sequence lengths: a multiple of every chunk, of none, and shorter than one
@pytest.mark.parametrize("s", [256, 150, 20])
@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_the_scan_is_the_plain_recurrence_at_every_chunk_length(chunk, s):
    args = inputs(s)
    want, want_h = plain(*args)
    y, h = ssd_scan(*args, chunk=chunk)
    assert y.dtype == h.dtype == jnp.float32 and y.shape == want.shape
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=2e-5)


def test_the_programs_chunk_is_the_sources():
    assert op.CHUNK == 128
    args = inputs(300)
    np.testing.assert_array_equal(ssd_scan(*args)[0], ssd_scan(*args, chunk=128)[0])


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_every_inputs_gradient_is_the_plain_recurrences(chunk):
    args = inputs(150)
    probe_y = jax.random.normal(jax.random.key(9), (2, 150, 4, 8))
    probe_h = jax.random.normal(jax.random.key(10), (2, 4, 8, 16))

    def loss(fn):
        def f(*args):
            y, h = fn(*args)
            return jnp.sum(y * probe_y) + jnp.sum(h * probe_h)
        return f

    want = jax.grad(loss(plain), argnums=range(5))(*args)
    got = jax.grad(loss(lambda *t: ssd_scan(*t, chunk=chunk)), argnums=range(5))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert g.shape == w.shape and float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_a_sequence_split_in_two_with_the_state_carried_is_the_whole():
    args = inputs(200)
    x, dt, a, b, c = args
    whole, last = ssd_scan(*args, chunk=32)
    first, mid = ssd_scan(x[:, :90], dt[:, :90], a, b[:, :90], c[:, :90], chunk=32)
    second, end = ssd_scan(x[:, 90:], dt[:, 90:], a, b[:, 90:], c[:, 90:], state=mid, chunk=32)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(end, last, rtol=1e-5, atol=2e-5)
    # and the state's own gradient is the plain recurrence's
    got = jax.grad(lambda h: jnp.sum(ssd_scan(x[:, 90:], dt[:, 90:], a, b[:, 90:], c[:, 90:], state=h, chunk=32)[0]))(mid)
    want = jax.grad(lambda h: jnp.sum(plain(x[:, 90:], dt[:, 90:], a, b[:, 90:], c[:, 90:], h)[0]))(mid)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


def test_a_head_reads_its_own_groups_b_and_c():
    """Changing group 1's ``B`` and ``C`` moves heads 2 and 3 and leaves heads 0 and 1."""
    x, dt, a, b, c = inputs(64)
    y, _ = ssd_scan(x, dt, a, b, c)
    moved, _ = ssd_scan(x, dt, a, b.at[:, :, 1].add(1.0), c.at[:, :, 1].add(1.0))
    np.testing.assert_array_equal(moved[:, :, :2], y[:, :, :2])
    assert float(jnp.abs(moved[:, :, 2:] - y[:, :, 2:]).max()) > 0.1


def test_values_and_gradients_are_finite_at_a_steep_decay_over_a_long_sequence():
    """``dt A`` near -16 a position over 8192 positions: every running sum is a
    chunk's (at most -2048), masked before its exponential."""
    s, heads, p, n = 8192, 2, 4, 8
    k = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(k[0], (1, s, heads, p))
    dt = jnp.full((1, s, heads), 16.0) + 0.1 * jax.random.normal(k[1], (1, s, heads))
    a = jnp.asarray([-1.0, -0.001])  # one head forgets at once, one hardly at all
    b, c = jax.random.normal(k[2], (1, s, 1, n)), jax.random.normal(k[3], (1, s, 1, n))

    def loss(x, dt, a, b, c):
        y, h = ssd_scan(x, dt, a, b, c)
        return jnp.sum(y * y) * 1e-6 + jnp.sum(h)

    y, h = ssd_scan(x, dt, a, b, c)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    # the forgetful head's output is its own position's input alone
    np.testing.assert_allclose(y[0, :, 0], (dt[0, :, 0, None] * x[0, :, 0]) * jnp.sum(b[0, :, 0] * c[0, :, 0], -1)[:, None],
                               rtol=1e-4, atol=1e-4)
    for name, g in zip(("x", "dt", "a", "b", "c"), jax.grad(loss, argnums=range(5))(x, dt, a, b, c)):
        assert bool(jnp.isfinite(g).all()), name


def test_bfloat16_inputs_give_float32_outputs_near_the_float32_result():
    args = inputs(256)
    x, dt, a, b, c = args
    want, _ = ssd_scan(*args)
    y, h = ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16), c.astype(jnp.bfloat16))
    assert y.dtype == h.dtype == jnp.float32
    assert float(jnp.abs(y - want).max()) < 0.05 * float(jnp.abs(want).max())
    grads = jax.grad(lambda x, b: jnp.sum(ssd_scan(x, dt, a, b, b)[0]), argnums=(0, 1))(
        x.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.bfloat16]


def test_the_scope_is_in_the_jaxpr():
    text = str(jax.make_jaxpr(lambda *t: ssd_scan(*t))(*inputs(128)).pretty_print(name_stack=True))
    assert "ssd_scan" in text


# --------------------------------------------------------------------------
# The kernels, interpreted (``interpret=True``): what the chip runs compiled
# --------------------------------------------------------------------------

# Tolerances. Float32 inputs: the kernels sum a chunk's products in another
# order than the ``einsum``s (a slab of heads a product, the running sum as a
# product with a triangle of ones), so values agree to float32 rounding over a
# chunk's 128 terms (2e-5 of values of order one, as the einsum form's own tests
# above) and gradients to 2e-4 of each gradient's largest entry. bfloat16
# inputs: both forms round the masked ``C B^T`` and the state to bfloat16 before
# the MXU, at points that differ (the kernels' backward pass leaves the step with
# the inputs, the ``einsum``s' with the mask), so they agree as the file's
# existing bfloat16 case does with float32: within a few roundings of 2^-8.
KERNEL_CASES = {
    # a sequence that is no multiple of the chunk: padded with steps of 0
    "no_multiple_of_the_chunk": dict(s=300, heads=4, groups=2, chunk=128),
    "shorter_than_a_chunk": dict(s=20, heads=4, groups=2, chunk=128),
    "one_group": dict(s=128, heads=8, groups=1, chunk=64),
    "eight_groups": dict(s=128, heads=8, groups=8, chunk=64),
    "two_heads_a_slab_of_128_lanes": dict(s=256, heads=4, groups=2, chunk=128, p=64, n=128, batch=1),
    "a_starting_state": dict(s=150, heads=4, groups=2, chunk=32, state=True),
    "a_strong_decay": dict(s=512, heads=2, groups=1, chunk=128, steep=True),
}


def kernel_case(name):
    case = dict(KERNEL_CASES[name])
    chunk, state, steep = case.pop("chunk"), case.pop("state", False), case.pop("steep", False)
    x, dt, a, b, c = inputs(**case)
    if steep:  # dt A near -16 a position: an unmasked exponent of a chunk's span would be e^2048
        dt = jnp.full(dt.shape, 16.0) + 0.1 * dt
        a = jnp.asarray([-1.0, -0.001])
    batch, _, heads, p = x.shape
    h0 = jax.random.normal(jax.random.key(5), (batch, heads, p, b.shape[-1])) if state else None
    return (x, dt, a, b, c), h0, chunk


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_the_kernels_give_the_other_forms_values(name):
    args, h0, chunk = kernel_case(name)
    y, h = jax.jit(lambda *t: ssd_scan(*t, state=h0, chunk=chunk, interpret=True))(*args)
    assert y.dtype == h.dtype == jnp.float32
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    for want, want_h in (plain(*args, h0), ssd_scan(*args, state=h0, chunk=chunk)):
        assert y.shape == want.shape
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=2e-5 * max(1.0, float(jnp.abs(want).max())))
        np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=2e-5 * max(1.0, float(jnp.abs(want_h).max())))


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_the_kernels_give_the_other_forms_six_gradients(name):
    args, h0, chunk = kernel_case(name)
    x = args[0]
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], args[3].shape[-1]))
    probe_y = jax.random.normal(jax.random.key(9), x.shape)
    probe_h = jax.random.normal(jax.random.key(10), h0.shape)

    def grads(fn):
        def loss(*t):
            y, h = fn(*t)
            return jnp.sum(y * probe_y) + jnp.sum(h * probe_h)
        return jax.jit(jax.grad(loss, argnums=range(6)))(*args, h0)

    got = grads(lambda *t: ssd_scan(*t[:5], state=t[5], chunk=chunk, interpret=True))
    for other in (plain, lambda *t: ssd_scan(*t[:5], state=t[5], chunk=chunk)):
        for which, g, w in zip(("x", "dt", "a", "b", "c", "state"), got, grads(other)):
            assert g.shape == w.shape and bool(jnp.isfinite(g).all()), which
            np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()) + 1e-30, err_msg=which)


def test_the_kernels_chain_two_sequences_through_the_state_into_the_whole():
    args = inputs(200)
    x, dt, a, b, c = args
    whole, last = ssd_scan(*args, chunk=32, interpret=True)
    first, mid = ssd_scan(x[:, :90], dt[:, :90], a, b[:, :90], c[:, :90], chunk=32, interpret=True)
    second, end = ssd_scan(x[:, 90:], dt[:, 90:], a, b[:, 90:], c[:, 90:], state=mid, chunk=32, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(end, last, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_the_kernels_take_bfloat16_inputs_and_sum_in_float32(what):
    x, dt, a, b, c = inputs(256, heads=4, groups=2, p=64, n=128, batch=1)
    xb, bb, cb = (t.astype(jnp.bfloat16) for t in (x, b, c))
    if what == "values":
        want, want_h = ssd_scan(xb, dt, a, bb, cb)
        y, h = ssd_scan(xb, dt, a, bb, cb, interpret=True)
        assert y.dtype == h.dtype == jnp.float32
        assert float(jnp.abs(y - want).max()) < 0.02 * float(jnp.abs(want).max())
        assert float(jnp.abs(h - want_h).max()) < 0.02 * float(jnp.abs(want_h).max())
        return
    probe = jax.random.normal(jax.random.key(9), x.shape)
    loss = lambda interpret: lambda *t: jnp.sum(ssd_scan(*t, interpret=interpret)[0] * probe)
    want = jax.grad(loss(None), argnums=range(5))(xb, dt, a, bb, cb)
    got = jax.grad(loss(True), argnums=range(5))(xb, dt, a, bb, cb)
    assert [g.dtype for g in got] == [w.dtype for w in want] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16]
    for which, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(g - w).max()) < 0.04 * float(jnp.abs(w).max()), which


def test_off_the_chip_the_programs_call_lowers_to_no_pallas_call():
    """``interpret=None`` is the program's call: on the CPU the ``einsum`` form, and
    the kernels only where a test asks for them."""
    args = inputs(128)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda *t: ssd_scan(*t))(*args))
    assert "pallas_call" in str(jax.make_jaxpr(lambda *t: ssd_scan(*t, interpret=True))(*args))


def test_the_compiled_kernels_refuse_shapes_that_fill_no_tile():
    with pytest.raises(ValueError, match="tile"):
        ssd_scan(*inputs(128), interpret=False)
    assert op._tiles(8, 64, 128, 128) and not op._tiles(8, 64, 128, 64) and not op._tiles(2, 8, 16, 128)
