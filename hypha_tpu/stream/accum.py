"""The streaming sample-weighted delta accumulator, shared fold/un-fold.

One class, three users:

  * the parameter-server executor folds each arriving delta into a running
    f32 partial sum as it lands (hypha_tpu.worker.ps_executor);
  * a recovered PS re-applies the journaled fold/un-fold sequence to
    rebuild the interrupted round's accumulator bit-exactly
    (hypha_tpu.ft.durable);
  * a tree-reduce group reducer pre-folds its group members' deltas into
    ONE partial sum per shard before anything reaches the parameter
    service (hypha_tpu.stream.reduce).

The arithmetic is deliberately identical at every level: ``fold`` adds
``np.float32(sign * samples) * Δ`` per tensor in arrival order, so a
reducer's partial sum is bit-equal to what the shard itself would have
accumulated from the same deltas in the same order — the property the
tree-reduce layer's correctness (and its tests) rest on.

``prefolded`` folds accept a partial sum that is ALREADY sample-weighted:
the payload adds verbatim (scaled only by ``sign`` for un-folds) while the
shipped ``samples`` header still advances the weight total, so the final
``mean`` divides by the true Σ samples across every level of the tree.

The sample weighting is also what keeps straggler-adaptive rounds
(hypha_tpu.ft.adaptive) unbiased: a worker assigned k/4 inner steps ships
``num_samples`` = the tokens it actually processed, so its delta enters
the mean at exactly its share of the round's data — unequal step counts
change the estimator's variance, never its expectation.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from .. import compress
from ..telemetry import trace

__all__ = ["RoundAccum"]


class RoundAccum:
    """Streaming sample-weighted fold of one round's delta files.

    Holds ONE param-sized f32 tree (Σ samples·Δθ) instead of every
    worker's decoded delta: ``fold`` runs as each push lands (off the
    event loop via ``asyncio.to_thread``), ``fold(…, sign=-1)`` un-folds a
    replaced duplicate, and when quorum closes the PS :meth:`take`s the
    sum for its one in-place pass (division and Nesterov fused);
    :meth:`mean` and :meth:`partial` read it without taking it, for the
    reducers and the tests.
    """

    def __init__(self) -> None:
        self._acc: dict[str, np.ndarray] = {}
        self._shapes: dict[str, tuple] = {}
        self.total_samples = 0.0
        self.folds = 0

    def fold(
        self,
        path: Path,
        samples: float,
        sign: float = 1.0,
        prefolded: bool = False,
        span: "trace.TraceSpan | None" = None,
    ) -> None:
        """Read one delta file and fold it. ``span`` is the caller's
        round-trace ``fold`` span (None when off): the file read and the
        arithmetic become its two children."""
        with trace.phase("fold.read", parent=span) as ph:
            tree = compress.read_delta(path)
            ph.set("bytes", Path(path).stat().st_size)
            ph.set("leaves", len(tree))
        with trace.phase("fold.accumulate", parent=span, attrs={"leaves": len(tree)}):
            self.fold_tree(tree, samples, sign, prefolded)

    def fold_tree(
        self,
        tree: dict,
        samples: float,
        sign: float = 1.0,
        prefolded: bool = False,
    ) -> None:
        """Fold an already-decoded delta tree (the file-less entry point
        the group reducer uses on its own freshly decoded payloads)."""
        if self._shapes:
            if set(tree) != set(self._shapes):
                raise ValueError("workers sent deltas with mismatched keys")
        # A prefolded payload is already Σ samples·Δ — re-weighting it
        # would square the sample count; only the un-fold sign applies.
        scale = np.float32(sign) if prefolded else np.float32(sign * samples)
        for key, value in tree.items():
            arr = np.asarray(value, np.float32)
            shape = self._shapes.get(key)
            if shape is None:
                self._shapes[key] = arr.shape
            elif arr.shape != shape:
                raise ValueError(
                    f"delta {key!r}: mismatched shape {arr.shape} vs {shape}"
                )
            contrib = scale * arr
            prev = self._acc.get(key)
            if prev is None:
                self._acc[key] = contrib
            else:
                prev += contrib
        self.total_samples += sign * samples
        self.folds += 1 if sign > 0 else -1

    def _denom(self) -> np.float32:
        return np.float32(max(self.total_samples, 1e-20))

    def mean(self) -> dict[str, np.ndarray]:
        """The sample-weighted mean ḡ = Σ samples·Δθ / Σ samples (f32)."""
        if not self._acc:
            raise ValueError("no deltas folded")
        denom = self._denom()
        return {k: v / denom for k, v in self._acc.items()}

    def take(self) -> tuple[dict[str, np.ndarray], np.float32]:
        """Hand over the partial sum Σ samples·Δθ and the divisor
        :meth:`mean` would use, and leave the accumulator empty: the PS's
        outer step writes the update over the sum where it lies, so nobody
        may read this sum again."""
        if not self._acc:
            raise ValueError("no deltas folded")
        taken = self._acc, self._denom()
        self._acc, self._shapes = {}, {}
        self.total_samples, self.folds = 0.0, 0
        return taken

    def partial(self) -> dict[str, np.ndarray]:
        """The raw weighted partial sum Σ samples·Δθ (f32) — what a group
        reducer ships to its shard (header ``prefold`` + the weight), so
        the shard's own fold of it is bit-equal to having folded the
        members directly in the same order."""
        if not self._acc:
            raise ValueError("no deltas folded")
        return dict(self._acc)
