"""Expert compute without drops: a grouped product over the experts held here.

One rank of an expert-parallel layer routes every token over all experts and
computes the part of the result that its own experts give. The pairs (token,
chosen expert) whose expert is held here are sorted by expert
(:func:`sort_pairs`) and run through the expert's grouped products
(``jax.lax.ragged_dot``, which the TPU compiler lowers to a tiled
grouped-matmul kernel), then weighted and added back onto their tokens in
batches (:func:`grouped_experts`). The expert's ``form`` is the family's, a
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

**What a combine costs.** Adding rows onto their tokens is one indexed
operation, ``acc.at[rows].add(...)``, and on a v5e its cost follows its batch,
not its valid rows (``benchmarks/scatter_batch_probe.py``; ``PERF.md`` §6, PR
52). The TPU compiler has two lowerings: rows as they come, for a batch of at
most an eighth of ``acc``'s rows (0.28 µs a row of 8 KB), and rows ascending
(it sorts them itself unless told they are), which walks the whole of ``acc``
once a call: 0.066 µs a token plus 0.067 µs a row, whatever the mask. A trip's
2048 rows into 8192 tokens paid for the tokens every trip. So the rows of
several trips go onto the tokens at once: a trip writes its [chunk, D] result
into its place among ``_combine_rows`` held rows (contiguous, in place), and a
batch is combined when its trips are done: a loop level around the trips, not a
branch in their body. The order each batch goes in (:func:`_by_token`: by
token, the masked rows last with an index out of range, which the scatter
drops) is sorted once a layer, outside the loops, for the forward and the
backward walk, so the compiler sorts nothing. A batch is five eighths of the
tokens, cut to whole trips: it holds the pairs a rank expects (half the tokens
in the deployments here: ``k * held / experts``) with a quarter to spare, since
a batch that falls one row short pays for the tokens twice, and a row too many
costs a tenth of what a token does. Where a trip is that large already a trip
is a batch and nothing is held. The worst case is ``tokens * k / batch``
combines of the same body. ``plan_trips`` counts ``combines`` beside ``trips``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["sort_pairs", "sort_pairs_weighted", "plan_trips", "grouped_experts", "FORMS"]


def sort_pairs(top_idx: jnp.ndarray, expert_offset: int, experts_held: int):
    """``top_idx`` [T, K] (expert of each of a token's K choices) -> ``order``
    [T*K], the pair ids (``token * K + slot``) sorted by held expert with the
    pairs of experts held elsewhere last, and ``group_sizes`` [experts_held]."""
    key, group_sizes = _held_key(top_idx, expert_offset, experts_held)
    return _sorted_stably(key, experts_held + 1)[1], group_sizes


def _held_key(top_idx, expert_offset: int, experts_held: int):
    """Each pair's sort key, its held expert or ``experts_held`` for one held
    elsewhere, and the held experts' ``group_sizes``."""
    local = top_idx.reshape(-1).astype(jnp.int32) - expert_offset
    key = jnp.where((local >= 0) & (local < experts_held), local, experts_held)
    held = jnp.arange(experts_held, dtype=jnp.int32)
    return key, jnp.sum(key[:, None] == held[None, :], axis=0, dtype=jnp.int32)


def _sorted_stably(key, bound: int, *carried):
    """``key`` [..., n] int32 with values in [0, ``bound``) sorted stably along
    its last axis: the sorted keys, where each came from, and every ``carried``
    array in that order. Where a key and its place fit one word together they
    are sorted as one: every word differs, no tie is left to keep, and the TPU
    compiler has one operand less to carry through a sort that need not be
    stable (a third of the compile time of the stable sort of two)."""
    n = key.shape[-1]
    at = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    if bound * n < 2 ** 31:
        word, *carried = jax.lax.sort((key * n + at, *carried), dimension=-1, num_keys=1, is_stable=False)
        return word // n, word % n, *carried
    return jax.lax.sort((key, at, *carried), dimension=-1, num_keys=1, is_stable=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sort_with(key, weight, bound):
    """``key`` [N] int32 under ``bound``, ``weight`` [N] -> the stable order of
    ``key`` and the weights in that order, by one sort that carries both."""
    _, order, sorted_weight = _sorted_stably(key, bound, weight)
    return order, sorted_weight


def _sort_with_fwd(key, weight, bound):
    order, sorted_weight = _sort_with(key, weight, bound)
    return (order, sorted_weight), order


def _sort_with_bwd(bound, order, cots):
    # ``order`` is a permutation: sorting by it undoes it, where JAX's own rule
    # for a sorted operand scatters the cotangent back scalar by scalar.
    _, d_weight = jax.lax.sort((order, cots[1]), num_keys=1, is_stable=False)
    return None, d_weight


_sort_with.defvjp(_sort_with_fwd, _sort_with_bwd)


def sort_pairs_weighted(top_idx: jnp.ndarray, weights: jnp.ndarray, expert_offset: int, experts_held: int):
    """:func:`sort_pairs` with the pairs' weights carried through its sort:
    ``top_idx`` and ``weights`` [T, K] -> ``pair_token`` [T*K] (``order // K``),
    ``pair_weight`` [T*K] (``weights.reshape(-1)[order]``, differentiable in
    ``weights``) and ``group_sizes``: the same values with no gather of T*K
    scalars, forward or backward (10 ns a scalar on a v5e against the sort's
    45-100 µs for all of them: ``PERF.md`` §6, PR 52)."""
    key, group_sizes = _held_key(top_idx, expert_offset, experts_held)
    order, pair_weight = _sort_with(key, weights.reshape(-1), experts_held + 1)
    return order // top_idx.shape[-1], pair_weight, group_sizes


FORMS = ("swiglu", "relu2")  # an expert's form: how many matrices, and which activation


def _chunk_out(xc, wts, ws, sizes, form, taps=None):
    """One chunk of sorted pairs through its experts: [C, D] -> [C, D] f32,
    weighted. ``ws`` are the expert's matrices by ``form``: ``swiglu`` gate, up
    and down (``down(silu(gate x) * up x)``), ``relu2`` up and down
    (``down(relu(up x)^2)``, no gate). Rows past ``sum(sizes)`` belong to no
    group; the caller masks them before, and the combine drops them after. ``taps``, zeros, one for
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


def _combine_rows(tokens: int) -> int:
    """How many of the walk's rows go onto ``tokens`` tokens in one batch, at
    the most: five eighths of the tokens (the module's docstring, "What a
    combine costs"). A function of what the walk sees; no family's, no option."""
    return tokens * 5 // 8


def _batch_rows(tokens: int, chunk: int, pairs: int) -> tuple[int, int]:
    """The walk's two static sizes for ``pairs`` sorted rows onto ``tokens``
    tokens: a trip's rows, and a combine's (a multiple of a trip's)."""
    chunk = min(chunk, pairs)
    padded = -(-pairs // chunk) * chunk
    return chunk, min(max(_combine_rows(tokens) // chunk, 1) * chunk, padded)


def plan_trips(group_sizes, chunk: int, pairs: int, tokens: int):
    """The walk of ``pairs`` sorted rows in chunks of ``chunk`` onto ``tokens``
    tokens, trip by trip (``pairs``, ``chunk`` and ``tokens`` static, as
    :func:`grouped_experts` sees them): ``trips``, how many the walk makes;
    ``combines``, in how many batches their rows go onto the tokens (a trip a
    batch where a trip is as large as a batch may be); for each of the
    ``ceil(pairs / chunk)`` it could make, ``windowed`` (its rows belong to at
    most ``_window`` contiguous experts) and ``start`` (that window's first
    expert, clipped so that the window lies inside the held ones); and
    ``grad_experts``, the experts whose gradient rows the backward walk's
    trips add into: the window's a windowed trip, every held one a trip whose
    rows span more."""
    held = group_sizes.shape[0]
    width = _window(held)
    chunk, batch = _batch_rows(tokens, chunk, pairs)
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
        "combines": (total + batch - 1) // batch,
        "windowed": windowed,
        "start": jnp.minimum(lo, held - width),
        "grad_experts": jnp.sum(jnp.where(base < total, jnp.where(windowed, width, held), 0)),
    }


def _gather(x, rows, valid):
    with jax.named_scope("moe_dispatch"):
        return jnp.where(valid[:, None], x[rows], 0)


def _by_token(pair_token, group_sizes, batch: int, tokens: int):
    """The order in which each batch of ``batch`` sorted pairs goes onto the
    tokens, for every batch at once: the token rows of each batch ascending
    (``tokens``, out of range, for the rows past ``sum(group_sizes)``: they sort
    last and the combine drops them) and where in its batch each row lies."""
    with jax.named_scope("moe_dispatch"):
        at = jnp.arange(pair_token.shape[0], dtype=jnp.int32)
        rows = jnp.where(at < jnp.sum(group_sizes), pair_token, tokens).reshape(-1, batch)
        rows, source = _sorted_stably(rows, tokens + 1)  # a batch a row
        return rows.reshape(-1), source.reshape(-1)


def _combine(acc, held, by_token, b):
    """Batch ``b``'s rows, ``held`` [batch, D] as the trips wrote them, onto the
    tokens in one scatter, its indices ascending and the masked rows dropped."""
    with jax.named_scope("moe_combine"):
        batch = held.shape[0]
        rows, source = (jax.lax.dynamic_slice(a, (b * batch,), (batch,)) for a in by_token)
        return acc.at[rows].add(held[source].astype(acc.dtype), indices_are_sorted=True, mode="drop")


def _place(acc, i, out, per: int, by_token):
    """Where trip ``i``'s rows go: onto their tokens where a trip is a batch
    (``acc`` the tokens' sum), else into the trip's place among its batch's
    held rows (``acc`` those: contiguous, and combined ``per`` trips at once)."""
    if per == 1:
        return _combine(acc, out, by_token, i)
    return jax.lax.dynamic_update_slice(acc, out.astype(acc.dtype), ((i % per) * out.shape[0], 0))


def _in_batches(trips, per: int, chunk: int, by_token, walk, acc, *sums):
    """The walk's trips, ``per`` of them a batch: ``walk(first, end, (rows,
    *sums))`` runs the trips from ``first`` on, before ``end``, each placing its
    rows (:func:`_place`) and adding to ``sums``; each batch's held rows are
    then combined into ``acc``. A loop level, not a branch in a trip's body."""
    if per == 1:  # a trip is a batch: its rows go onto the tokens as they are
        return walk(0, trips, (acc, *sums))

    def one_batch(b, carry):
        acc, held, *sums = carry
        held, *sums = walk(b * per, jnp.minimum((b + 1) * per, trips), (held, *sums))
        return _combine(acc, held, by_token, b), held, *sums

    held = jnp.zeros((per * chunk, acc.shape[1]), acc.dtype)
    acc, _, *sums = jax.lax.fori_loop(0, (trips + per - 1) // per, one_batch, (acc, held, *sums))
    return acc, *sums


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _grouped(x, ws, pair_token, pair_weight, group_sizes, by_token, chunk, form, per):
    trips, meta = _walk(pair_token, pair_weight, group_sizes, chunk)

    def trip(i, carry):
        _, rows, wts, valid, sizes = meta(i)
        return (_place(carry[0], i, _chunk_out(_gather(x, rows, valid), wts, ws, sizes, form), per, by_token),)

    walk = lambda first, end, carry: jax.lax.fori_loop(first, end, trip, carry)
    return _in_batches(trips, per, chunk, by_token, walk, jnp.zeros(x.shape, jnp.float32))[0]


def _grouped_fwd(x, ws, pair_token, pair_weight, group_sizes, by_token, chunk, form, per):
    y = _grouped(x, ws, pair_token, pair_weight, group_sizes, by_token, chunk, form, per)
    return y, (x, ws, pair_token, pair_weight, group_sizes, by_token)


def _grouped_bwd(chunk, form, per, res, dy):
    x, ws, pair_token, pair_weight, group_sizes, by_token = res
    trips, meta = _walk(pair_token, pair_weight, group_sizes, chunk)
    held = group_sizes.shape[0]
    width = _window(held)
    plan = plan_trips(group_sizes, chunk, pair_token.shape[0], x.shape[0])
    taps = tuple(jnp.zeros((chunk, w.shape[2]), jnp.float32) for w in ws)

    def trip(i, carry, windowed):
        acc, dwt, dws = carry
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
        return _place(acc, i, dxc, per, by_token), dwt, dws

    def run(windowed, end):
        """The trips from ``i`` on, before ``end``, for as long as they are of this kind."""
        more = lambda state: (state[0] < end) & (plan["windowed"][state[0]] == windowed)
        return lambda state: jax.lax.while_loop(
            more, lambda state: (state[0] + 1, trip(*state, windowed)), state)

    def walk(first, end, carry):
        """The trips from ``first`` on, before ``end``."""
        if width == held:  # every trip adds into every held expert
            return jax.lax.fori_loop(first, end, lambda i, carry: trip(i, carry, False), carry)
        # Trips of one kind come in runs (even loads: all windowed; most
        # experts nearly empty: none), so each kind has a loop of its own and
        # the two take turns: no conditional in a body (the module's docstring).
        return jax.lax.while_loop(
            lambda state: state[0] < end, lambda state: run(False, end)(run(True, end)(state)),
            (jnp.asarray(first, jnp.int32), carry),
        )[1]

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dwt, dws = _in_batches(
        trips, per, chunk, by_token, walk, zeros(x), zeros(pair_weight), tuple(zeros(w) for w in ws))
    return (
        dx.astype(x.dtype), tuple(d.astype(w.dtype) for d, w in zip(dws, ws)),
        None, dwt.astype(pair_weight.dtype), None, (None, None),
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
    chunk, batch = _batch_rows(x.shape[0], chunk, n)
    pad = (-n) % batch
    if pad:  # a chunk and a batch are sliced whole; the tail is masked like any other row
        pair_token = jnp.pad(pair_token, (0, pad))
        pair_weight = jnp.pad(pair_weight, (0, pad))
    pair_token, group_sizes = pair_token.astype(jnp.int32), group_sizes.astype(jnp.int32)
    by_token = _by_token(pair_token, group_sizes, batch, x.shape[0])
    return _grouped(
        x, tuple(ws), pair_token, pair_weight.astype(jnp.float32), group_sizes, by_token,
        chunk, form, batch // chunk,
    )

