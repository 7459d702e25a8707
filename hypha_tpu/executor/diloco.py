"""DiLoCo delta algebra and the outer Nesterov optimizer, as jitted tree ops.

Reference semantics being reproduced:
  * pseudo-gradient: Δθ = θ_t − θ_0 against the round's anchor snapshot
    (executors/accelerate/.../utils.py:119-124);
  * worker merge: θ_new = θ_old + update — the update already contains
    lr·(μ·m + ḡ), sign convention per utils.py:112-116;
  * outer step (parameter server): m ← μ·m + ḡ;  update = lr·(μ·m + ḡ)
    with ḡ = mean of worker pseudo-gradients
    (crates/worker/src/executor/parameter_server.rs:386-446, verified there
    against torch SGD(nesterov=True) — our golden test does the same);
  * averaging is a single (optionally sample-weighted) mean, fixing the
    reference's pairwise-average mis-weighting TODO (parameter_server.rs:192).

On TPU all of these are jit-compiled pytree ops; across co-located replicas
the mean lowers to an ICI collective (parallel.collectives).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

__all__ = [
    "extract_delta",
    "relaid",
    "merge_update",
    "apply_updates",
    "average_deltas",
    "nesterov_init",
    "nesterov_outer_step",
]


def _subtract(params, anchor):
    return jax.tree.map(lambda p, a: (p - a).astype(jnp.float32), params, anchor)


@functools.lru_cache(maxsize=None)
def _subtract_into(treedef, formats):
    """:func:`_subtract` jitted to lay its results out as ``formats`` say: one
    program per tree structure, so a round after the first finds its own
    compiled."""
    return jax.jit(_subtract, out_shardings=jax.tree.unflatten(treedef, formats))


def _rows(leaf) -> tuple[int, ...]:
    return tuple(range(leaf.ndim))


def relaid(params) -> tuple[int, int]:
    """How many leaves of ``params`` do not lie row-major on their device,
    and the bytes of their part of the f32 delta: what :func:`extract_delta`
    has the device transpose."""
    sizes = [
        int(p.size) * 4
        for p in jax.tree.leaves(params)
        if tuple(p.format.layout.major_to_minor) != _rows(p)
    ]
    return len(sizes), sum(sizes)


def _subtraction_for(params):
    """The jitted :func:`_subtract` for trees shaped and placed as ``params``."""
    leaves, treedef = jax.tree.flatten(params)
    formats = tuple(Format(Layout(major_to_minor=_rows(p)), p.sharding) for p in leaves)
    return _subtract_into(treedef, formats)


def extract_delta(params, anchor):
    """Pseudo-gradient Δθ = θ_t − θ_0 (both trees same structure), every
    leaf row-major: the order the wire file has.

    A backend lays an array out as it likes (a v5e keeps an f32
    ``[2688, 10304]`` column-major: a last dimension that is no multiple of
    128 beside one that is), a jitted result takes the same layout by
    default, and ``device_get`` keeps the device's order on the host, where
    ``compress.write_delta`` would transpose such a leaf on one thread before
    it can write the leaf's own memory. So the subtraction is compiled to
    return every leaf row-major under its own sharding: where a leaf lies
    otherwise the device transposes it inside the program that already runs
    there, and where it lies so already, as on the CPU, the request is what
    the compiler would have chosen. The values are the same bit for bit.
    """
    return _subtraction_for(params)(params, anchor)


@jax.jit
def merge_update(params, update):
    """θ_new = θ + update, preserving each leaf's dtype."""
    return jax.tree.map(lambda p, u: (p + u.astype(p.dtype)).astype(p.dtype), params, update)


def merge_update_f32(params, update):
    """θ_new = θ + update with the add in f32 before the per-leaf cast.

    :func:`apply_updates`' precision discipline for flat name→leaf maps
    covering any SUBSET of the tree — the sharded rejoin catch-up applies
    per-shard cumulative Σs to disjoint leaf sets, and casting a long Σ
    to bf16 before the add (plain :func:`merge_update`) would diverge
    from the unsharded catch-up's f32 accumulation."""
    return jax.tree.map(
        lambda p, u: (
            jnp.asarray(p, jnp.float32) + jnp.asarray(u, jnp.float32)
        ).astype(p.dtype),
        params, update,
    )


@jax.jit
def _apply_updates(p, us):
    def leaf(x, *ys):
        total = sum(jnp.asarray(y, jnp.float32) for y in ys)
        return (x.astype(jnp.float32) + total).astype(x.dtype)

    return jax.tree.map(leaf, p, *us)


def apply_updates(params, updates: list):
    """Fold several outer updates into θ in one pass: θ ← θ + Σ updates.

    The rejoin catch-up path (hypha_tpu.ft.rejoin): a worker that missed
    rounds k..r−1 applies their updates — or the parameter server's single
    cumulative Σ — in f32 before the per-leaf cast, so a long catch-up does
    not compound per-round rounding in low-precision params.  The jitted
    body lives at module level so repeated same-shape catch-ups hit the
    compilation cache instead of re-tracing a parameter-sized tree op.
    """
    if not updates:
        return params
    return _apply_updates(params, list(updates))


def average_deltas(deltas: list, weights=None):
    """Mean of worker pseudo-gradients; ``weights`` = per-worker sample counts
    for the sample-weighted fix."""
    if not deltas:
        raise ValueError("no deltas")
    if weights is None:
        w = jnp.full((len(deltas),), 1.0 / len(deltas), jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32)
        w = w / jnp.maximum(w.sum(), 1e-20)

    def leaf_mean(*leaves):
        stacked = jnp.stack([l.astype(jnp.float32) for l in leaves])
        return jnp.tensordot(w, stacked, axes=1)

    return jax.tree.map(leaf_mean, *deltas)


def nesterov_init(params):
    """Zero momentum buffers shaped like the param tree (f32)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


@jax.jit
def _nesterov(momentum, mean_delta, lr, mu):
    m_new = jax.tree.map(lambda m, g: mu * m + g.astype(jnp.float32), momentum, mean_delta)
    update = jax.tree.map(lambda m, g: lr * (mu * m + g.astype(jnp.float32)), m_new, mean_delta)
    return m_new, update


def nesterov_outer_step(momentum, mean_delta, lr: float, mu: float):
    """One outer step: returns (new_momentum, update_to_broadcast).

    Matches torch SGD(nesterov=True) on the ascent-direction pseudo-gradient:
    buf ← μ·buf + ḡ; update = lr·(ḡ + μ·buf); θ ← θ + update.
    """
    return _nesterov(momentum, mean_delta, jnp.float32(lr), jnp.float32(mu))
