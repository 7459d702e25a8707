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
chunk recomputing its first products and taking ``jax.vjp`` of the chunk
function in its rows and weights. The residuals are the layer's inputs and
the sorted indices.

**What a backward trip costs.** The matrices' gradient is a float32 sum over
the trips, [held, D, F] a matrix. The sorted rows of a chunk belong to
contiguous experts (:func:`plan_trips` says which), so a trip whose rows lie
within ``_window(held)`` of them takes each matrix's cotangent over that
window alone (the transpose of the grouped product, over the window's group
sizes) and adds it into that slice of the sum in place: its cost follows its
rows, not ``held``. A trip whose chunk spans more experts than a window holds,
nearly empty ones, takes the cotangent dense over all held experts and adds it
to the whole sum, as every trip did before (0.39 ms a matrix and trip at
LFM2-24B-A2B's widths, a quarter of the loop's time: ``PERF.md`` §6, PR 51).
Nothing else of a trip differs between the two kinds, and each kind has a loop
of its own: trips of one kind come in runs, the two loops take turns inside an
outer one, and no conditional stands in a loop's body, where the compiler
copies the sums in and out of it every trip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["sort_pairs", "plan_trips", "grouped_experts", "FORMS"]


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


def _chunk_out(xc, wts, ws, sizes, form, taps=None):
    """One chunk of sorted pairs through its experts: [C, D] -> [C, D] f32,
    weighted. ``ws`` are the expert's matrices by ``form``: ``swiglu`` gate, up
    and down (``down(silu(gate x) * up x)``), ``relu2`` up and down
    (``down(relu(up x)^2)``, no gate). Rows past ``sum(sizes)`` belong to no
    group; the caller masks them, before and after. ``taps``, zeros, one for
    each matrix, are added to that matrix's product: the backward pass reads a
    product's cotangent off its tap, and then gets the down product's input
    beside the output."""
    with jax.named_scope("moe_experts"):
        dot = functools.partial(
            jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=jnp.float32
        )
        tap = (lambda i, p: p) if taps is None else (lambda i, p: p + taps[i])
        if form == "swiglu":
            w_gate, w_up, w_down = ws
            act = (jax.nn.silu(tap(0, dot(xc, w_gate))) * tap(1, dot(xc, w_up))).astype(xc.dtype)
        else:
            w_up, w_down = ws
            act = jnp.square(jax.nn.relu(tap(0, dot(xc, w_up)))).astype(xc.dtype)
        out = tap(-1, dot(act, w_down)) * wts[:, None]
        return out if taps is None else (out, act)


def _weight_grad(lhs, cot, sizes):
    """The cotangent of ``w`` in ``ragged_dot(lhs, w, sizes)``, of as many
    experts as ``sizes`` has: [C, A], [C, B] -> [len(sizes), A, B] in lhs's dtype."""
    with jax.named_scope("moe_experts"):
        product = lambda w: jax.lax.ragged_dot(lhs, w, sizes, preferred_element_type=jnp.float32)
        like = jax.ShapeDtypeStruct((sizes.shape[0], lhs.shape[1], cot.shape[1]), lhs.dtype)
        return jax.linear_transpose(product, like)(cot)[0]


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


def _window(held: int) -> int:
    """How many contiguous experts a trip's weight gradient is taken over."""
    return min(held, 4)


def plan_trips(group_sizes, chunk: int, pairs: int):
    """The walk of ``pairs`` sorted rows in chunks of ``chunk``, trip by trip
    (``pairs`` and ``chunk`` static, as :func:`grouped_experts` is handed
    them): ``trips``, how many the walk makes; for each of the
    ``ceil(pairs / chunk)`` it could make, ``windowed`` (its rows belong to at
    most ``_window`` contiguous experts) and ``start`` (that window's first
    expert, clipped so that the window lies inside the held ones); and
    ``grad_experts``, the experts whose gradient rows the backward walk's
    trips add into: the window's a windowed trip, every held one a trip whose
    rows span more."""
    held = group_sizes.shape[0]
    width = _window(held)
    chunk = min(chunk, pairs)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    total = ends[-1]
    base = jnp.arange(-(-pairs // chunk), dtype=jnp.int32) * chunk
    last = jnp.minimum(base + chunk, total) - 1
    # the expert a row belongs to: as many experts' rows end at or before it
    lo = jnp.sum(ends[None, :] <= base[:, None], axis=1, dtype=jnp.int32)
    hi = jnp.sum(ends[None, :] <= last[:, None], axis=1, dtype=jnp.int32)
    windowed = hi - lo < width
    return {
        "trips": (total + chunk - 1) // chunk,
        "windowed": windowed,
        "start": jnp.minimum(lo, held - width),
        "grad_experts": jnp.sum(jnp.where(base < total, jnp.where(windowed, width, held), 0)),
    }


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
    held = group_sizes.shape[0]
    width = _window(held)
    plan = plan_trips(group_sizes, chunk, pair_token.shape[0])
    taps = tuple(jnp.zeros((chunk, w.shape[2]), jnp.float32) for w in ws)

    def trip(i, carry, windowed):
        dx, dwt, dws = carry
        base, rows, wts, valid, sizes = meta(i)
        xc = _gather(x, rows, valid)
        _, vjp, act = jax.vjp(
            lambda xc, wts, taps: _chunk_out(xc, wts, ws, sizes, form, taps), xc, wts, taps,
            has_aux=True,
        )
        dxc, dwts, cots = vjp(_gather(dy, rows, valid))
        lhs = (xc,) * (len(ws) - 1) + (act,)  # what each matrix multiplies
        if windowed:
            # The chunk's experts lie in [start, start + width): those of the
            # window before the first one count no row here, so the window's
            # groups begin where the chunk does.
            start = plan["start"][i]
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, width)
            dws = tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    d, cut(d) + _weight_grad(a, c, cut(sizes)).astype(jnp.float32), start, 0)
                for d, a, c in zip(dws, lhs, cots)
            )
        else:
            dws = tuple(
                d + _weight_grad(a, c, sizes).astype(jnp.float32) for d, a, c in zip(dws, lhs, cots)
            )
        dwt = jax.lax.dynamic_update_slice(dwt, jnp.where(valid, dwts, 0.0), (base,))
        return _scatter_add(dx, rows, valid, dxc), dwt, dws

    def run(windowed):
        """The trips from ``i`` on for as long as they are of this kind."""
        more = lambda state: (state[0] < trips) & (plan["windowed"][state[0]] == windowed)
        return lambda state: jax.lax.while_loop(
            more, lambda state: (state[0] + 1, trip(*state, windowed)), state)

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    carry = (zeros(x), zeros(pair_weight), tuple(zeros(w) for w in ws))
    if width == held:  # every trip adds into every held expert
        dx, dwt, dws = jax.lax.fori_loop(0, trips, lambda i, carry: trip(i, carry, False), carry)
    else:
        # Trips of one kind come in runs (even loads: all windowed; most
        # experts nearly empty: none), so each kind has a loop of its own and
        # the two take turns: no conditional in a body (the module's docstring).
        _, (dx, dwt, dws) = jax.lax.while_loop(
            lambda state: state[0] < trips, lambda state: run(False)(run(True)(state)),
            (jnp.int32(0), carry),
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

