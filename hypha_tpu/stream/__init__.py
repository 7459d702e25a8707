"""Streaming outer sync: fragment-wise, compute-overlapped DiLoCo rounds.

Streaming DiLoCo (Douillard et al., 2025, PAPERS.md) removes the outer
round's hard barrier two ways, both reproduced here:

  * **fragments** — the parameter tree is partitioned into F size-balanced
    fragments and only ONE fragment synchronizes per outer round, on a
    staggered schedule (fragment ``r mod F`` is due at round ``r``), so
    peak bytes-in-flight shrinks ~F× while every parameter still syncs
    every F rounds;
  * **overlap** — the due fragment's delta is encoded and uploaded in the
    background while the worker keeps taking inner steps on the
    not-yet-synced params; when the broadcast lands, the outer update is
    merged with a *delayed-update correction* that re-anchors at the
    send-time snapshot, so the drift accrued in flight is shipped with the
    NEXT delta instead of being silently folded into (or clobbered by) the
    outer update.

Pieces:

  * :mod:`partition` — deterministic, size-balanced partition of a flat
    parameter tree into F fragments. Pure function of ``{name: size}``, so
    the parameter server and every worker compute the same fragments
    without exchanging a manifest.
  * :mod:`sync`      — the fragment schedule and the delayed-update
    correction algebra (pure tree ops over flat dicts), shared by the
    training executor and the tests.

Selection is per job via ``sync_mode: blocking | overlap | stream`` on
:class:`~hypha_tpu.scheduler.job_config.DiLoCoJob` (default ``blocking`` —
bit-identical to the pre-streaming behavior).
"""

from __future__ import annotations

from .partition import fragment_of, partition_names, shard_names, shard_of
from .tree import (
    ancestors_of,
    build_reduce_groups,
    children_of,
    parent_of,
    subtree_of,
    top_targets,
    tree_levels,
    with_serve_leaves,
)
from .sync import (
    SYNC_MODES,
    effective_fragments,
    fragment_due,
    merge_corrected,
    next_owned_round,
    placement_parts,
    shard_owns_round,
    shards_due_at,
)

__all__ = [
    "SYNC_MODES",
    "partition_names",
    "fragment_of",
    "fragment_due",
    "effective_fragments",
    "merge_corrected",
    "shard_of",
    "shard_names",
    "placement_parts",
    "shard_owns_round",
    "shards_due_at",
    "next_owned_round",
    "build_reduce_groups",
    "children_of",
    "parent_of",
    "ancestors_of",
    "subtree_of",
    "top_targets",
    "tree_levels",
    "with_serve_leaves",
]
