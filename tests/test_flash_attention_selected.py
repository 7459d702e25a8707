"""The flash kernels under a selection of keys a query (a mask that is data,
``ops/index_select.py``'s packed bits): forward, the three gradients and the
log-sum-exp against the masked softmax; a selection that keeps every causal
key is the causal call bit for bit; what a selection needs and where the call
falls back to the plain form."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.ops.flash_attention import flash_attention
from hypha_tpu.ops.index_select import masked_attention, pack_mask, unpack_mask


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _case(s=256, b=2, h=4, hkv=2, d=32, dv=None, topk=40, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    dv = dv or d
    q, k = jax.random.normal(ks[0], (b, s, h, d)), jax.random.normal(ks[1], (b, s, hkv, d))
    v, ct = jax.random.normal(ks[2], (b, s, hkv, dv)), jax.random.normal(ks[3], (b, s, h, dv))
    t = jnp.arange(s)
    causal = t[None, :] <= t[:, None]
    scores = jax.random.normal(ks[4], (b, s, s))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    keep = jnp.zeros((b, s, s), bool).at[jnp.arange(b)[:, None, None], t[None, :, None], idx].set(True) & causal
    return q, k, v, ct, pack_mask(keep), keep


def _both(impl, q, k, v, ct):
    def f(q, k, v):
        o, lse = impl(q, k, v)
        return (o * ct).sum(), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)
    return o, lse, grads


# (forward tiles, backward tiles): equal, a backward key tile of two runs of 128 lanes, small query tiles
TILES = [((128, 128), (128, 128)), ((64, 128), (128, 256)), ((256, 256), (64, 128))]


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("heads", [(4, 2), (4, 4), (8, 1)])
def test_forward_the_three_gradients_and_the_lse_are_the_masked_softmaxs(tiles, heads):
    q, k, v, ct, packed, _ = _case(h=heads[0], hkv=heads[1])
    (bq, bk), (bqb, bkb) = tiles
    kernel = lambda q, k, v: flash_attention(
        q, k, v, selection=packed, block_q=bq, block_k=bk, block_q_bwd=bqb, block_k_bwd=bkb, interpret=True)
    plain = lambda q, k, v: masked_attention(q, k, v, packed, q.shape[-1] ** -0.5)
    o, lse, grads = _both(kernel, q, k, v, ct)
    o2, lse2, grads2 = _both(plain, q, k, v, ct)
    np.testing.assert_allclose(o, o2, atol=2e-5)
    np.testing.assert_allclose(lse, lse2, atol=2e-5)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1]) and lse.dtype == jnp.float32
    for name, mine, theirs in zip("qkv", grads, grads2):
        np.testing.assert_allclose(mine, theirs, atol=5e-5, err_msg=f"d{name}")


def test_a_value_wider_than_its_keys_goes_under_a_selection_too():
    q, k, v, ct, packed, _ = _case(d=32, dv=64)
    o, lse, grads = _both(lambda q, k, v: flash_attention(
        q, k, v, selection=packed, block_q=128, block_k=128, interpret=True), q, k, v, ct)
    o2, lse2, grads2 = _both(lambda q, k, v: masked_attention(q, k, v, packed, 32**-0.5), q, k, v, ct)
    np.testing.assert_allclose(o, o2, atol=2e-5)
    for mine, theirs in zip(grads, grads2):
        np.testing.assert_allclose(mine, theirs, atol=5e-5)


def test_a_selection_that_keeps_every_causal_key_is_the_causal_call_bit_for_bit():
    q, k, v, ct, _, _ = _case()
    s = q.shape[1]
    full = pack_mask(jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (q.shape[0], s, s)))
    tiles = dict(block_q=128, block_k=128, interpret=True)
    sel = jax.value_and_grad(lambda q, k, v: (flash_attention(q, k, v, selection=full, **tiles)[0] * ct).sum(), (0, 1, 2))
    causal = jax.value_and_grad(lambda q, k, v: (flash_attention(q, k, v, **tiles) * ct).sum(), (0, 1, 2))
    for a, b in zip(jax.tree.leaves(sel(q, k, v)), jax.tree.leaves(causal(q, k, v))):
        np.testing.assert_array_equal(a, b)


def test_a_selection_changes_what_a_query_sees_and_only_after_topk_queries():
    q, k, v, _, packed, keep = _case(topk=40)
    tiles = dict(block_q=128, block_k=128, interpret=True)
    cut, _ = flash_attention(q, k, v, selection=packed, **tiles)
    full = flash_attention(q, k, v, **tiles)
    np.testing.assert_allclose(cut[:, :40], full[:, :40], atol=1e-6)  # the first topk queries keep every causal key
    assert float(jnp.abs(cut[:, 40:] - full[:, 40:]).max()) > 1e-2
    assert int(keep.sum(-1).max()) == 40


def test_the_lse_passes_no_gradient():
    q, k, v, _, packed, _ = _case()
    g = jax.grad(lambda q: flash_attention(q, k, v, selection=packed, block_q=128, block_k=128, interpret=True)[1].sum())(q)
    assert float(jnp.abs(g).max()) == 0.0


def test_a_selection_needs_causal_self_attention_without_a_window():
    q, k, v, _, packed, _ = _case()
    for bad in ({"causal": False}, {"window": 64}):
        with pytest.raises(ValueError, match="selection"):
            flash_attention(q, k, v, selection=packed, interpret=True, **bad)
    with pytest.raises(ValueError, match="block_k_bwd"):  # a backward key tile that cuts a run of 128 lanes
        flash_attention(q, k, v, selection=packed, block_q=128, block_k=128, block_k_bwd=64, interpret=True)


@pytest.mark.parametrize("s", [96, 192])
def test_a_length_no_kernel_tiles_falls_back_to_the_plain_form(s):
    """No key tile of whole runs of 128 lanes divides these: the masked softmax,
    the same two results."""
    q, k, v, ct, packed, _ = _case(s=s, topk=24)
    o, lse = flash_attention(q, k, v, selection=packed, interpret=True)
    o2, lse2 = masked_attention(q, k, v, packed, q.shape[-1] ** -0.5)
    np.testing.assert_array_equal(o, o2)
    np.testing.assert_array_equal(lse, lse2)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, selection=packed, interpret=True))(q, k, v))
    assert "pallas_call" not in text


def test_the_scopes_of_a_selected_call_say_so():
    q, k, v, _, packed, _ = _case()
    f = lambda q, k, v: flash_attention(q, k, v, selection=packed, block_q=128, block_k=128, interpret=True)[0].sum()
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v).pretty_print(name_stack=True))
    assert "flash_attention_sel" in text and "flash_attention_bwd_sel" in text
    assert text.count("pallas_call") == 3  # one set of three kernels
    assert not re.search(r"flash_attention(_bwd)?/pallas_call", text)  # none under the unselected scopes


def test_one_row_of_the_selection_serves_all_heads_of_a_query():
    q, k, v, _, packed, keep = _case(b=1, h=8, hkv=2)
    o, _ = flash_attention(q, k, v, selection=packed, block_q=128, block_k=128, interpret=True)
    assert packed.shape == (1, 256, 128)  # [B, S, W]: no head axis
    np.testing.assert_array_equal(unpack_mask(packed, 256), keep)
    one_head, _ = flash_attention(q[:, :, 5:6], k[:, :, 1:2], v[:, :, 1:2], selection=packed,
                                  block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(o[:, :, 5:6], one_head, atol=1e-6)
