"""Expert compute without drops: a grouped product over the experts held here.

One rank of an expert-parallel layer routes every token over all experts and
computes the part of the result that its own experts give. The pairs (token,
chosen expert) whose expert is held here are sorted by expert
(:func:`sort_pairs`) and run through the expert's grouped products
(``jax.lax.ragged_dot``, which the TPU compiler lowers to a tiled
grouped-matmul kernel), then weighted and scatter-added back onto their
tokens (:func:`grouped_experts`). The expert's ``form`` is the family's, a
static argument: ``swiglu``, three matrices (gate, up, down) with SiLU on the
gate, or ``relu2``, two matrices with a squared ReLU between them and no gate.

Nothing is dropped and nothing is padded to a capacity: the sorted pairs are
walked in chunks of ``chunk`` rows by a loop whose trip count is
``ceil(pairs held / chunk)``, so the work follows the pairs actually routed
here (on average ``tokens * k * held / experts``) and the temporaries are one
chunk's whatever the imbalance. The worst case, every choice of every token
held here, is ``tokens * k / chunk`` trips of the same body: slow, never
wrong. A loop with a data-dependent trip count has no reverse-mode rule, so
the backward pass is written here (``jax.custom_vjp``): the same walk, each
chunk recomputing its first products and taking ``jax.vjp`` of the
chunk function. The residuals are the layer's inputs and the sorted indices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["sort_pairs", "grouped_experts", "FORMS"]


def sort_pairs(top_idx: jnp.ndarray, expert_offset: int, experts_held: int):
    """``top_idx`` [T, K] (expert of each of a token's K choices) -> ``order``
    [T*K], the pair ids (``token * K + slot``) sorted by held expert with the
    pairs of experts held elsewhere last, and ``group_sizes`` [experts_held]."""
    local = top_idx.reshape(-1).astype(jnp.int32) - expert_offset
    key = jnp.where((local >= 0) & (local < experts_held), local, experts_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    held = jnp.arange(experts_held, dtype=jnp.int32)
    group_sizes = jnp.sum(key[:, None] == held[None, :], axis=0, dtype=jnp.int32)
    return order, group_sizes


FORMS = ("swiglu", "relu2")  # an expert's form: how many matrices, and which activation


def _chunk_out(xc, wts, ws, sizes, form):
    """One chunk of sorted pairs through its experts: [C, D] -> [C, D] f32,
    weighted. ``ws`` are the expert's matrices by ``form``: ``swiglu`` gate, up
    and down (``down(silu(gate x) * up x)``), ``relu2`` up and down
    (``down(relu(up x)^2)``, no gate). Rows past ``sum(sizes)`` belong to no
    group; the caller masks them, before and after."""
    with jax.named_scope("moe_experts"):
        dot = functools.partial(
            jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=jnp.float32
        )
        if form == "swiglu":
            w_gate, w_up, w_down = ws
            act = (jax.nn.silu(dot(xc, w_gate)) * dot(xc, w_up)).astype(xc.dtype)
        else:
            w_up, w_down = ws
            act = jnp.square(jax.nn.relu(dot(xc, w_up))).astype(xc.dtype)
        return dot(act, w_down) * wts[:, None]


def _walk(pair_token, pair_weight, group_sizes, chunk):
    """The chunk loop's bookkeeping: the trip count, and ``meta(i)`` giving a
    chunk's token rows, weights, row mask and group sizes."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    total = ends[-1]
    offsets = jnp.arange(chunk, dtype=jnp.int32)

    def meta(i):
        base = i * chunk
        rows = jax.lax.dynamic_slice(pair_token, (base,), (chunk,))
        wts = jax.lax.dynamic_slice(pair_weight, (base,), (chunk,))
        valid = base + offsets < total
        sizes = jnp.clip(ends - base, 0, chunk) - jnp.clip(starts - base, 0, chunk)
        return base, rows, jnp.where(valid, wts, 0.0), valid, sizes

    return (total + chunk - 1) // chunk, meta


def _gather(x, rows, valid):
    with jax.named_scope("moe_dispatch"):
        return jnp.where(valid[:, None], x[rows], 0)


def _scatter_add(acc, rows, valid, update):
    with jax.named_scope("moe_combine"):
        return acc.at[rows].add(jnp.where(valid[:, None], update, 0).astype(acc.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, ws, pair_token, pair_weight, group_sizes, chunk, form):
    trips, meta = _walk(pair_token, pair_weight, group_sizes, chunk)

    def body(i, y):
        _, rows, wts, valid, sizes = meta(i)
        out = _chunk_out(_gather(x, rows, valid), wts, ws, sizes, form)
        return _scatter_add(y, rows, valid, out)

    return jax.lax.fori_loop(0, trips, body, jnp.zeros(x.shape, jnp.float32))


def _grouped_fwd(x, ws, pair_token, pair_weight, group_sizes, chunk, form):
    y = _grouped(x, ws, pair_token, pair_weight, group_sizes, chunk, form)
    return y, (x, ws, pair_token, pair_weight, group_sizes)


def _grouped_bwd(chunk, form, res, dy):
    x, ws, pair_token, pair_weight, group_sizes = res
    trips, meta = _walk(pair_token, pair_weight, group_sizes, chunk)

    def body(i, carry):
        dx, dwt, dws = carry
        base, rows, wts, valid, sizes = meta(i)
        xc = _gather(x, rows, valid)
        _, vjp = jax.vjp(lambda xc, wts, ws: _chunk_out(xc, wts, ws, sizes, form), xc, wts, ws)
        dxc, dwts, dwsc = vjp(_gather(dy, rows, valid))
        dwt = jax.lax.dynamic_update_slice(dwt, jnp.where(valid, dwts, 0.0), (base,))
        return (
            _scatter_add(dx, rows, valid, dxc), dwt,
            tuple(d + dc.astype(jnp.float32) for d, dc in zip(dws, dwsc)),
        )

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dwt, dws = jax.lax.fori_loop(
        0, trips, body, (zeros(x), zeros(pair_weight), tuple(zeros(w) for w in ws)),
    )
    return (
        dx.astype(x.dtype), tuple(d.astype(w.dtype) for d, w in zip(dws, ws)),
        None, dwt.astype(pair_weight.dtype), None,
    )


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_experts(
    x: jnp.ndarray,  # [T, D] tokens
    ws: tuple,  # the held experts' matrices by ``form``, in x's dtype: [G, D, F] ..., [G, F, D]
    pair_token: jnp.ndarray,  # [N] int32: the token of each pair, sorted by expert
    pair_weight: jnp.ndarray,  # [N] f32: its routing weight, in the same order
    group_sizes: jnp.ndarray,  # [G] int32: pairs of each held expert
    *,
    form: str = "swiglu",
    chunk: int = 2048,
) -> jnp.ndarray:
    """``y[t] = sum over t's pairs held here of weight * expert(x[t])``,
    [T, D] float32, the expert of the family's ``form``. Pairs past
    ``sum(group_sizes)`` are ignored. Differentiable in ``x``, the matrices
    and ``pair_weight``."""
    if form not in FORMS or len(ws) != (3 if form == "swiglu" else 2):
        raise ValueError(f"an expert of form {form!r} (one of {FORMS}) does not have {len(ws)} matrices")
    n = pair_token.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:  # a chunk is sliced whole; the tail is masked like any other row
        pair_token = jnp.pad(pair_token, (0, pad))
        pair_weight = jnp.pad(pair_weight, (0, pad))
    return _grouped(
        x, tuple(ws), pair_token.astype(jnp.int32),
        pair_weight.astype(jnp.float32), group_sizes.astype(jnp.int32), chunk, form,
    )

