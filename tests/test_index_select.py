"""``ops/index_select.py``: the exact choice of the ``topk`` largest index
scores a query against ``lax.top_k`` on the whole row, at several tile sizes,
at a length that is no multiple of the tile and with planted ties; the packed
mask; and the indexer's objective with its three gradients against a plain
form."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.ops import index_select as op

S, J, DI, H, HKV, D, TOPK = 200, 4, 8, 4, 2, 16, 24  # 200 is a multiple of none of the tiles below


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seed=0, s=S):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (s, J, DI)), jax.random.normal(ks[1], (s, DI)),
            jax.random.normal(ks[2], (s, J)) * 0.3)


def whole_row_scores(qi, ki, w):
    z = jnp.einsum("tjd,nd->jtn", qi, ki)
    sc = jnp.sum(jax.nn.relu(z) * w.T[:, :, None], 0)
    return jnp.where(sc == 0, 0.0, sc)


def by_top_k(scores, topk):
    """``S_t`` by ``lax.top_k`` on the whole row: bool [S, S]."""
    s = scores.shape[0]
    t = jnp.arange(s)
    causal = t[None, :] <= t[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))
    return jnp.zeros((s, s), bool).at[t[:, None], idx].set(True) & causal


@pytest.mark.parametrize("tiles", [(32, 64), (64, 32), (48, 48), (256, 256)])
def test_the_picked_set_is_lax_top_ks_on_the_whole_row(tiles):
    qi, ki, w = _inputs()
    packed, lse = op.index_select(qi, ki, w, topk=TOPK, q_chunk=tiles[0], kv_chunk=tiles[1])
    want = by_top_k(whole_row_scores(qi, ki, w), TOPK)
    got = op.unpack_mask(packed, S)
    np.testing.assert_array_equal(got, want)
    assert packed.shape == (S, 128) and packed.dtype == jnp.int32
    rows = np.asarray(got.sum(-1))
    np.testing.assert_array_equal(rows, np.minimum(np.arange(S) + 1, TOPK))  # min(t + 1, topk), exactly
    scores = whole_row_scores(qi, ki, w)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1), atol=1e-5)


@pytest.mark.parametrize("tiles", [(32, 64), (48, 48)])
def test_planted_ties_go_to_the_earlier_position(tiles):
    """Index keys repeated in runs: every score comes several times, so the cut
    falls inside a run of equal scores in most rows."""
    qi, ki, w = _inputs(1)
    ki = jnp.repeat(ki[::5], 5, axis=0)[:S]
    scores = whole_row_scores(qi, ki, w)
    assert float(jnp.mean(scores[:, 0::5][:, :30] == scores[:, 1::5][:, :30])) == 1.0  # the ties are exact
    packed, _ = op.index_select(qi, ki, w, topk=TOPK, q_chunk=tiles[0], kv_chunk=tiles[1])
    want = by_top_k(scores, TOPK)
    got = op.unpack_mask(packed, S)
    np.testing.assert_array_equal(got, want)
    cut_inside_a_run = int(jnp.sum(jnp.any(want[:, :-1] & ~want[:, 1:] & (scores[:, :-1] == scores[:, 1:]), axis=-1)))
    assert cut_inside_a_run > S // 4


@pytest.mark.parametrize("sign", [1.0, -1.0, 0.0])
def test_scores_of_one_sign_are_chosen_and_stay_finite(sign):
    """Head weights all positive, all negative, or all zero (every score +0.0:
    the earliest ``topk`` keys by the tie rule)."""
    qi, ki, w = _inputs(2)
    w = sign * jnp.abs(w)
    packed, lse = op.index_select(qi, ki, w, topk=TOPK, q_chunk=64, kv_chunk=64)
    np.testing.assert_array_equal(op.unpack_mask(packed, S), by_top_k(whole_row_scores(qi, ki, w), TOPK))
    assert bool(jnp.isfinite(lse).all())
    if sign == 0.0:
        assert bool(op.unpack_mask(packed, S)[S - 1, :TOPK].all())


def test_a_topk_no_query_reaches_picks_every_causal_key():
    qi, ki, w = _inputs(3, s=96)
    packed, _ = op.index_select(qi, ki, w, topk=4096, q_chunk=32, kv_chunk=32)
    np.testing.assert_array_equal(op.unpack_mask(packed, 96), np.tril(np.ones((96, 96), bool)))


def test_the_order_preserving_image_and_the_bisection():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf], jnp.float32)
    u = np.asarray(op._ordered(x)).astype(np.uint64)
    assert (np.diff(u[[0, 1, 2, 3, 5, 6, 7]].astype(np.int64)) > 0).all() and u.min() > 0
    rows = jnp.asarray(np.random.default_rng(0).normal(size=(7, 333)), jnp.float32)
    kth = op._kth_largest(op._ordered(rows), 40)
    np.testing.assert_array_equal(kth, op._ordered(jnp.sort(rows, axis=-1)[:, -40]))
    assert int(op._kth_largest(op._ordered(rows), 334)[0]) == 0  # fewer than k in the row


@pytest.mark.parametrize("n", [1, 127, 128, 4096, 4097, 9000])
def test_the_packed_mask_round_trips(n):
    mask = jnp.asarray(np.random.default_rng(n).random((3, n)) < 0.3)
    packed = op.pack_mask(mask)
    assert packed.shape == (3, -(-n // 4096) * 128) and packed.dtype == jnp.int32
    np.testing.assert_array_equal(op.unpack_mask(packed, n), mask)
    # key s is bit (s % 4096) // 128 of word (s // 4096) * 128 + s % 128
    s = n - 1
    word = np.asarray(packed)[:, (s // 4096) * 128 + s % 128].astype(np.uint32)
    np.testing.assert_array_equal((word >> ((s % 4096) // 128)) & 1, np.asarray(mask[:, s]))


@pytest.mark.parametrize("tiles", [(48, 48), (64, 32), (256, 256)])
def test_the_objective_and_its_three_gradients_are_the_plain_forms(tiles):
    qi, ki, w = _inputs(4)
    ks = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(ks[0], (S, H, D)), jax.random.normal(ks[1], (S, HKV, D)),
               jax.random.normal(ks[2], (S, HKV, D)))
    packed, lse_i = op.index_select(qi, ki, w, topk=TOPK, q_chunk=64, kv_chunk=64)
    keep = op.unpack_mask(packed, S)
    scale = D**-0.5
    _, lse = op.masked_attention(q[None], k[None], v[None], packed[None], scale)

    def plain(qi, ki, w):
        logits = jnp.einsum("tgrd,ngd->grtn", q.reshape(S, HKV, H // HKV, D), k) * scale
        p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), -1).mean((0, 1))
        logq = jax.nn.log_softmax(jnp.where(keep, whole_row_scores(qi, ki, w), -jnp.inf), -1)
        on = keep & (p > 0)
        return jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0)) - jnp.where(on, logq, 0.0)), 0.0))

    mine = lambda qi, ki, w: op.index_kl(qi, ki, w, q, k, lse[0], packed, lse_i, scale, *tiles)
    a, ga = jax.value_and_grad(mine, (0, 1, 2))(qi, ki, w)
    b, gb = jax.value_and_grad(plain, (0, 1, 2))(qi, ki, w)
    assert abs(float(a) - float(b)) < 1e-4 * float(b) and float(b) > 1.0
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=2e-5)
    assert float(mine(qi, ki, w)) == float(a)  # the primal alone, without the gradients' residuals
    # the target is a constant: no gradient reaches the main attention's queries, keys or log-sum-exps
    g = jax.grad(lambda q, k, lse: op.index_kl(qi, ki, w, q, k, lse, packed, lse_i, scale, *tiles), (0, 1, 2))(q, k, lse[0])
    assert all(float(jnp.abs(x).max()) == 0.0 for x in g)
