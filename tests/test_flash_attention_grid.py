"""A causal flash call's grid lists the tiles it needs and no other
(``ops/flash_attention.py``'s ``_needed_tiles``): (a) the list alone, and the
grids of the three kernels as traced, at every shape the cells call the kernel
with: steps equal the tiles ``_block_needed`` admits, an outer tile's steps are
adjacent and its walk starts and ends on its first and last needed tile, and
the counts are the ones ISSUE 56 states; (b) the kernels interpreted on such
grids, forward and the three gradients against the XLA reference, over what
changes the list: a window below, across and of one tile's size, query heads
that share a key head (dk/dv accumulate over them within a k tile's steps),
backward tiles of their own, a wide value, a selection, keys past the last
query, and a non-causal call, which keeps the dense grid."""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.ops.attention import dot_product_attention
from hypha_tpu.ops.flash_attention import flash_attention
from hypha_tpu.ops.index_select import masked_attention, pack_mask
from test_tpu_compile import FLASH_SHAPES, KEYE, _pallas_calls

fa = importlib.import_module("hypha_tpu.ops.flash_attention")  # the module: the package's name of it is the function

# batch, sequence, query heads, key heads, head size, window (FLASH_SHAPES' first six), and Keye's under a selection
SHAPES = {name: dims[:6] for name, dims in FLASH_SHAPES.items()}
SHAPES["keye_selected"] = (1, KEYE["S"], KEYE["H"], KEYE["Hkv"], KEYE["D"], None)
# ISSUE 56: steps a query head of forward + dq + dk/dv at the default tiles
STATED = {"trinity_full": 136 + 72 + 72, "trinity_window": 70 + 42 + 42, "phi4_window": 31 + 23 + 23,
          "keye_selected": 528 + 272 + 272}
assert sum(STATED.values()) == 280 + 154 + 77 + 1072


def _tiles(seq):
    """(forward, backward) tiles as ``flash_attention`` picks them by default."""
    return (fa._pick_block(seq, 512),) * 2, (fa._pick_block(seq, 1024), fa._pick_block(seq, 512))


def _admitted(seq, bq, bk, window):
    return fa._block_needed(*np.indices((seq // bq, seq // bk)), bq, bk, window)


@pytest.mark.parametrize("reps", [None, 1, 4], ids=["q_major", "k_major", "k_major_four_heads"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_list_is_the_admitted_tiles_in_the_order_the_dense_grid_walked_them(shape, reps):
    _, seq, _, _, _, window = SHAPES[shape]
    for bq, bk in _tiles(seq):
        num_q, num_k = seq // bq, seq // bk
        need = _admitted(seq, bq, bk, window)
        outer, inner = (np.asarray(x) for x in fa._needed_tiles(num_q, num_k, bq, bk, window, reps))
        assert outer.dtype == inner.dtype == np.int32
        if reps is None:
            want = [(i, j) for i in range(num_q) for j in range(num_k) if need[i, j]]
        else:
            want = [(j, r) for j in range(num_k) for r in range(reps * num_q) if need[r % num_q, j]]
        assert list(zip(outer.tolist(), inner.tolist())) == want
        assert len(want) == need.sum() * (reps or 1)
        # every outer tile is there, once: its steps are adjacent
        starts = np.flatnonzero(np.r_[True, outer[1:] != outer[:-1]])
        assert outer[starts].tolist() == list(range(num_k if reps else num_q))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_each_kernel_makes_as_many_steps_as_tiles_are_needed(shape):
    """The grids of the program as traced at the cell's own shape: (batch·head,
    needed tiles) forward and in dq, (batch·key head, needed tiles x the query
    heads of a key head) in dk/dv."""
    b, seq, h, hkv, d, window = SHAPES[shape]
    sds = lambda heads: jax.ShapeDtypeStruct((b, seq, heads, d), jnp.bfloat16)
    sel = (jax.ShapeDtypeStruct((b, seq, seq // 32), jnp.int32),) if shape == "keye_selected" else ()

    def loss(q, k, v, *sel):
        o = flash_attention(q, k, v, window=window, interpret=False, **({"selection": sel[0]} if sel else {}))
        return (o[0] if sel else o).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(sds(h), sds(hkv), sds(hkv), *sel)
    grids = [call.params["grid_mapping"].grid for call in _pallas_calls(traced.jaxpr)]
    (fq, fk), (bq, bk) = _tiles(seq)
    forward, backward = int(_admitted(seq, fq, fk, window).sum()), int(_admitted(seq, bq, bk, window).sum())
    assert grids == [(b * h, forward), (b * h, backward), (b * hkv, backward * (h // hkv))]
    steps_a_head = sum(math.prod(grid) for grid in grids) // (b * h)
    assert steps_a_head == forward + 2 * backward == STATED.get(shape, steps_a_head)
    dense = (seq // fq) * (seq // fk) + 2 * (seq // bq) * (seq // bk)
    assert steps_a_head < dense and (seq, dense) in {(1024, 4 + 2 * 2), (4096, 64 + 2 * 32), (8192, 512), (16384, 2048)}


@pytest.mark.parametrize("reps", [None, 2], ids=["q_major", "k_major"])
@pytest.mark.parametrize("window", [None, 100, 24, 1])
def test_a_walk_starts_on_its_outer_tiles_first_needed_tile_and_ends_on_its_last(window, reps):
    """``_step`` as a kernel reads it, a step a row of the output: the two
    tiles, and whether ``_init`` and ``_finalize`` fire there."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    seq, bq, bk = 512, 128, 64
    num_q, num_k = seq // bq, seq // bk
    lists = fa._needed_tiles(num_q, num_k, bq, bk, window, reps)

    def kernel(*refs):
        i, j, starts, ends, (out,) = fa._step(refs, True, None)
        out[0, 0], out[0, 1], out[0, 2], out[0, 3] = i, j, starts().astype(jnp.int32), ends().astype(jnp.int32)

    steps = lists[0].shape[0]
    got = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((steps, 4), jnp.int32), interpret=True,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1, steps), in_specs=[],
            out_specs=pl.BlockSpec((1, 4), lambda b, t, outer, inner: (t, 0))),
    )(*lists))
    need = _admitted(seq, bq, bk, window)
    for outer in range(num_k if reps else num_q):
        rows = got[got[:, 0] == outer]
        needed = np.flatnonzero(need[:, outer] if reps else need[outer])
        if reps:  # every query head of the key head, then its q tiles
            needed = np.concatenate([rep * num_q + needed for rep in range(reps)])
        assert rows[:, 1].tolist() == needed.tolist()
        assert rows[:, 2].tolist() == [1] + [0] * (len(rows) - 1)
        assert rows[:, 3].tolist() == [0] * (len(rows) - 1) + [1]


# --------------------------------------------------------------------------
# (b) the kernels on such grids, interpreted, against the reference
# --------------------------------------------------------------------------

# sequence of queries (and of keys where it is another), query heads, key heads, value's width or None for
# the keys' 16, window, causal, under a selection, tiles (forward q, k; backward q, k or None for the forward's)
CASES = {
    "full": (256, 2, 2, None, None, True, False, (64, 64, None, None)),
    "window_narrower_than_a_tile": (256, 2, 2, None, 24, True, False, (64, 64, None, None)),
    "window_no_multiple_of_a_tile": (256, 2, 2, None, 100, True, False, (64, 64, None, None)),
    "window_of_one": (256, 2, 2, None, 1, True, False, (64, 64, None, None)),
    "four_query_heads_a_key_head_under_a_window": (256, 4, 1, None, 100, True, False, (64, 64, None, None)),
    "sixteen_query_heads_a_key_head": (256, 16, 1, None, None, True, False, (64, 64, None, None)),
    "backward_tiles_of_their_own": (256, 4, 2, None, 100, True, False, (64, 64, 128, 64)),
    "value_wider_than_the_keys": (256, 2, 1, 32, None, True, False, (64, 64, 128, 64)),
    "selection": (256, 4, 2, None, None, True, True, (64, 128, 128, 128)),
    "keys_past_the_last_query": ((128, 256), 2, 1, None, None, True, False, (64, 64, None, None)),
    "non_causal": (256, 2, 1, None, None, False, False, (64, 64, 128, 64)),
}
D = 16


@functools.cache
def _both(case):
    """(kernel's, reference's) ``(o, dq, dk, dv)`` of one case, computed once."""
    seq, h, hkv, dv, window, causal, selected, (bq, bk, bqb, bkb) = CASES[case]
    sq, sk = seq if isinstance(seq, tuple) else (seq, seq)
    ks = jax.random.split(jax.random.key(len(case)), 5)
    q = jax.random.normal(ks[0], (1, sq, h, D))
    k = jax.random.normal(ks[1], (1, sk, hkv, D))
    v = jax.random.normal(ks[2], (1, sk, hkv, dv or D))
    weigh = jax.random.normal(ks[3], (1, sq, h, dv or D))  # a cotangent that is not all ones
    tiles = dict(block_q=bq, block_k=bk, block_q_bwd=bqb, block_k_bwd=bkb, interpret=True)
    if selected:
        t = jnp.arange(sq)
        keep = (jax.random.uniform(ks[4], (1, sq, sq)) < 0.3) & (t[None, :] <= t[:, None])
        packed = pack_mask(keep | jnp.eye(sq, dtype=bool))
        kernel = lambda q, k, v: flash_attention(q, k, v, selection=packed, **tiles)[0]
        plain = lambda q, k, v: masked_attention(q, k, v, packed, D ** -0.5)[0]
    else:
        kernel = lambda q, k, v: flash_attention(q, k, v, causal=causal, window=window, **tiles)
        plain = lambda q, k, v: dot_product_attention(q, k, v, causal=causal, window=window)

    def all_four(fn):
        o, pull = jax.vjp(fn, q, k, v)
        return (o, *pull(weigh))

    with jax.default_matmul_precision("highest"):
        traced = jax.make_jaxpr(jax.grad(lambda *qkv: kernel(*qkv).sum(), argnums=(0, 1, 2)))(q, k, v)
        return all_four(kernel), all_four(plain), [c.params["grid_mapping"].grid for c in _pallas_calls(traced.jaxpr)]


@pytest.mark.parametrize("which", [0, 1, 2, 3], ids=["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_on_a_listed_grid_agree_with_the_reference(case, which):
    got, want, _ = _both(case)
    assert got[which].shape == want[which].shape
    np.testing.assert_allclose(got[which], want[which], atol=2e-5 if which == 0 else 1e-4)


@pytest.mark.parametrize("case", list(CASES))
def test_a_cases_grids_are_its_needed_tiles_or_dense_where_every_tile_is(case):
    seq, h, hkv, _, window, causal, _, (bq, bk, bqb, bkb) = CASES[case]
    sq, sk = seq if isinstance(seq, tuple) else (seq, seq)
    bqb, bkb = bqb or bq, bkb or bk
    grids = _both(case)[2]
    if not causal:
        assert grids == [(h, sq // bq, sk // bk), (h, sq // bqb, sk // bkb), (hkv, sk // bkb, h // hkv * (sq // bqb))]
        return

    def needed(bq, bk):
        need = fa._block_needed(*np.indices((sq // bq, sk // bk)), bq, bk, window)
        return int(need.sum() + (~need.any(axis=0)).sum())  # a k tile no query reaches is listed once

    assert grids == [(h, needed(bq, bk)), (h, needed(bqb, bkb)), (hkv, needed(bqb, bkb) * (h // hkv))]
    assert needed(bq, bk) < (sq // bq) * (sk // bk)
