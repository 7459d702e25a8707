"""The flash kernel takes a value of a width of its own (``Dv``): ``v`` is
``[B, Sk, Hkv, Dv]`` beside keys ``[B, Sk, Hkv, D]``, the output and the
value's cotangent are ``Dv`` wide, the scale stays ``D ** -0.5``. Here the
kernels in interpret mode against the XLA reference for a value twice and half
as wide as the keys, forward and the three gradients; a value over 128 lanes
takes the fall-back; and phi4flash's differential attention hands the kernel
one call a layer with the pair's whole value, where it made two."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.models.phi4flash import CROSS, FULL, WINDOW, Phi4FlashConfig, _DiffAttention
from hypha_tpu.ops.attention import dot_product_attention
from hypha_tpu.ops.flash_attention import flash_attention
from test_tpu_compile import _pallas_calls

D = 16
# (sequence, query heads, key heads, window): several tiles of 128 a side, so
# the recurrence runs over k tiles and the window leaves tiles out.
CASES = {
    "causal_full": (256, 2, 2, None),
    "window_that_cuts": (384, 2, 2, 96),
    "two_query_heads_to_a_key_head": (256, 4, 2, None),
}


def _inputs(case, dv, dtype=jnp.float32):
    s, h, hkv, window = CASES[case]
    kq, kk, kv_, kw = jax.random.split(jax.random.key(len(case) + dv), 4)
    q = jax.random.normal(kq, (2, s, h, D), dtype)
    k = jax.random.normal(kk, (2, s, hkv, D), dtype)
    v = jax.random.normal(kv_, (2, s, hkv, dv), dtype)
    weigh = jax.random.normal(kw, (2, s, h, dv), jnp.float32)  # a cotangent that is not all ones
    return q, k, v, weigh, window


def _kernel(q, k, v, window):
    return flash_attention(q, k, v, causal=True, window=window, interpret=True,
                           block_q=128, block_k=128)


@pytest.mark.parametrize("dv", [2 * D, D // 2], ids=["value_twice_the_keys", "value_half_the_keys"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_forward_kernel_agrees_with_the_reference(case, dv):
    q, k, v, _, window = _inputs(case, dv)
    got = _kernel(q, k, v, window)
    assert got.shape == q.shape[:3] + (dv,) and got.dtype == q.dtype
    np.testing.assert_allclose(got, dot_product_attention(q, k, v, causal=True, window=window), atol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("dv", [2 * D, D // 2], ids=["value_twice_the_keys", "value_half_the_keys"])
@pytest.mark.parametrize("case", list(CASES))
def test_each_gradient_agrees_with_the_reference(case, dv, wrt):
    q, k, v, weigh, window = _inputs(case, dv)
    loss = lambda fn: lambda *qkv: (fn(*qkv).astype(jnp.float32) * weigh).sum()
    got = jax.grad(loss(lambda q, k, v: _kernel(q, k, v, window)), argnums=wrt)(q, k, v)
    want = jax.grad(loss(lambda q, k, v: dot_product_attention(q, k, v, causal=True, window=window)),
                    argnums=wrt)(q, k, v)
    assert got.shape == (q, k, v)[wrt].shape
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_scale_is_the_keys_width_whatever_the_values():
    """A value of 128 beside keys of 16: were the scale taken from the value,
    the scores would be 2.8 times flatter."""
    q, k, v, _, _ = _inputs("causal_full", 128)
    np.testing.assert_allclose(_kernel(q, k, v, None),
                               dot_product_attention(q, k, v, causal=True, softmax_scale=D ** -0.5), atol=2e-5)


def test_bf16_operands_keep_their_precision_with_a_wide_value():
    q, k, v, _, _ = _inputs("two_query_heads_to_a_key_head", 2 * D, jnp.bfloat16)
    got = _kernel(q, k, v, None)
    assert got.dtype == jnp.bfloat16
    want = dot_product_attention(*(t.astype(jnp.float32) for t in (q, k, v)), causal=True)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=3e-2)


@pytest.mark.parametrize("d,dv", [(16, 256), (256, 16)], ids=["value_over_128", "keys_over_128"])
def test_a_width_over_128_lanes_takes_the_fall_back_and_still_agrees(monkeypatch, d, dv):
    kq, kk, kv_ = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (1, 256, 2, d))
    k = jax.random.normal(kk, (1, 256, 2, d))
    v = jax.random.normal(kv_, (1, 256, 2, dv))

    def no_kernel(*a, **kw):
        raise AssertionError("the kernel was built for a width over 128 lanes")

    # the package's name ``flash_attention`` is the function: the module by its full name
    monkeypatch.setattr(sys.modules["hypha_tpu.ops.flash_attention"], "_flash", no_kernel)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.shape == (1, 256, 2, dv)
    np.testing.assert_allclose(got, dot_product_attention(q, k, v, causal=True), atol=1e-6)
    grads = jax.grad(lambda *qkv: flash_attention(*qkv, causal=True, interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


@pytest.mark.parametrize("dv", [128, 64], ids=["value_128", "value_64"])
def test_the_calls_blocks_are_as_wide_as_what_they_hold(dv):
    """Phi-4's widths at a short sequence, as the three calls are built: q, k,
    dq and dk blocks 64 wide; v, o, do, dv and the forward accumulator ``dv``."""
    q = jax.ShapeDtypeStruct((1, 1024, 4, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1024, 2, dv), jnp.bfloat16)
    loss = lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert [a.shape for a in jaxpr.out_avals] == [q.shape, k.shape, v.shape]

    widths = [[m.block_shape[-1].block_size for m in eqn.params["grid_mapping"].block_mappings]
              for eqn in _pallas_calls(jaxpr.jaxpr)]
    assert widths == [
        [64, 64, dv, dv, 128],               # forward: q, k, v -> o, lse
        [64, 64, dv, dv, dv, 128, 64],       # dq: q, k, v, o, do, lse -> dq
        [64, 64, dv, dv, dv, 128, 64, dv],   # dk, dv: q, k, v, o, do, lse -> dk, dv
    ]


# --------------------------------------------------------------------------
# Differential attention: one call a layer
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind,source", [(WINDOW, 3), (FULL, 5), (CROSS, 7)])
def test_differential_attention_hands_the_kernel_one_call_with_the_pairs_whole_value(kind, source):
    cfg = Phi4FlashConfig.tiny()
    seen = []

    def recording(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw))
        return dot_product_attention(q, k, v, **kw)

    s, hd = 24, cfg.head_dim
    u = jax.random.normal(jax.random.key(1), (2, s, cfg.hidden_size), jnp.bfloat16)
    kv = None
    if kind == CROSS:
        kv = tuple(jax.random.normal(jax.random.key(i), (2, s, cfg.num_kv_heads, hd), jnp.bfloat16) for i in (2, 3))
    op = _DiffAttention(cfg, source, kind, recording)
    variables = op.init(jax.random.key(0), u, kv)
    seen.clear()
    out, _ = op.apply(variables, u, kv)
    assert seen == [((2, s, cfg.num_heads, hd), (2, s, cfg.num_kv_heads, hd),
                     (2, s, cfg.num_kv_heads, 2 * hd),
                     {"causal": True, "window": cfg.sliding_window if kind == WINDOW else None})]
    assert cfg.value_dim == 2 * hd
    # ... and computes what the layer computes without an implementation handed in
    plain, _ = _DiffAttention(cfg, source, kind).apply(variables, u, kv)
    np.testing.assert_array_equal(out, plain)
    # the backward pass makes no further call of the implementation: the combine
    # is checkpointed, the kernel is not
    seen.clear()
    jax.grad(lambda v: op.apply(v, u, kv)[0].astype(jnp.float32).sum())(variables)
    assert len(seen) == 1


def test_a_layer_through_the_kernel_is_the_layer_through_the_reference():
    """The whole differential layer with the interpreted kernel as its
    implementation, forward and parameter gradients, window and full."""
    import functools

    cfg = Phi4FlashConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=8, sliding_window=40, d_state=4, layers_run=(3, 4, 5, 6, 7),
        max_seq_len=128, dtype="float32")
    kernel = functools.partial(flash_attention, interpret=True, block_q=64, block_k=64)
    u = jax.random.normal(jax.random.key(1), (1, 128, cfg.hidden_size))
    for kind, source in ((WINDOW, 3), (FULL, 5)):
        a, b = _DiffAttention(cfg, source, kind, kernel), _DiffAttention(cfg, source, kind)
        variables = b.init(jax.random.key(0), u)
        np.testing.assert_allclose(a.apply(variables, u)[0], b.apply(variables, u)[0], atol=2e-5)
        ga, gb = (jax.grad(lambda v: (op.apply(v, u)[0] ** 2).sum())(variables) for op in (a, b))
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(x, y, atol=2e-4, rtol=2e-4)
