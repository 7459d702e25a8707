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

import contextlib
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

import numpy as np

from .. import compress, native
from ..compress.frame import ReadStats, f32_layout, read_exact
from ..telemetry import trace

__all__ = ["FoldStats", "RoundAccum", "SumBuffers"]


class SumBuffers:
    """The f32 leaf buffers a round's partial sum is kept in, owned by
    whoever outlives the round (the PS job; a bare ``RoundAccum`` has its
    own, allocated on first use).

    A :class:`RoundAccum` is made anew each round; its sum's pages are not.
    A round's first fold :meth:`lease`s a buffer a leaf, the outer step
    writes the update over the sum and gives it back once the file is on
    disk, and the next round's first fold finds pages that exist: a fresh
    parameter-sized tree costs about a second of page faults a gigabyte, a
    resident one the arithmetic. A buffer belongs to one open sum at a
    time: a second sum open over the same keys allocates its own, which
    are then kept too. A sum that is never given back costs an allocation
    next round and nothing else. ``threads`` is what the fold's kernel may
    use.
    """

    def __init__(self, threads: int = 1) -> None:
        self.threads = threads
        self._free: dict[str, list[np.ndarray]] = {}
        self._staging: np.ndarray | None = None

    def lease(self, key: str, shape: tuple) -> tuple[np.ndarray, bool]:
        """A writable f32 buffer for leaf ``key``, and whether it was kept
        from an earlier round (its contents are stale: overwrite them)."""
        free = self._free.get(key, [])
        while free:
            buf = free.pop()
            if buf.shape == shape:
                return buf, True
        return np.empty(shape, np.float32), False

    def give_back(self, tree: dict[str, np.ndarray]) -> None:
        for key, buf in tree.items():
            self._free.setdefault(key, []).append(buf)

    def take_staging(self, size: int) -> np.ndarray:
        """A flat f32 buffer of at least ``size`` elements: where a round's
        second and later deltas land, a leaf at a time, on their way into
        the sum. Taken, so two folds never share it; put it back."""
        buf, self._staging = self._staging, None
        if buf is None or buf.size < size:
            buf = np.empty(size, np.float32)
        return buf

    def put_staging(self, buf: np.ndarray) -> None:
        if self._staging is None or self._staging.size < buf.size:
            self._staging = buf


@dataclass
class FoldStats(ReadStats):
    """What one fold did: a read's counts and the seconds of its two
    phases. ``resident`` here also counts a leaf that is part of this
    round's sum already."""

    read_s: float = 0.0
    accumulate_s: float = 0.0
    threads: int = 1


class RoundAccum:
    """Streaming sample-weighted fold of one round's delta files.

    Holds ONE param-sized f32 tree (Σ samples·Δθ) instead of every
    worker's decoded delta, in buffers leased from ``buffers`` (the PS
    job's :class:`SumBuffers`; its own when none is given): ``fold`` runs
    as each push lands (off the event loop via ``asyncio.to_thread``),
    ``fold(…, sign=-1)`` un-folds a replaced duplicate, and when quorum
    closes the PS :meth:`take`s the sum for its one in-place pass
    (division and Nesterov fused) and :meth:`release`s the buffers once
    the update is on disk; :meth:`mean` and :meth:`partial` read the sum
    without taking it, for the reducers and the tests.

    Once the buffers exist a fold allocates nothing parameter-sized. A
    plain SafeTensors delta whose tensors are all F32 (what ``delta_codec``
    none sends; known from the file's own header) goes from its file into
    the sum in one pass: a round's first delta is read into the sum's
    buffers and scaled where it landed, a later one is read a leaf at a
    time into one staging leaf and added from there. Anything else is
    decoded as ever (:func:`compress.read_delta`) and accumulated into the
    same buffers. The arithmetic is one kernel (:func:`native.fold_scaled`)
    on every path. A delta that does not match the round (keys, shapes, a
    file shorter than its header says) raises before the sum is touched.
    """

    def __init__(self, buffers: SumBuffers | None = None) -> None:
        self._buffers = SumBuffers() if buffers is None else buffers
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
    ) -> FoldStats:
        """Read one delta file and fold it. ``span`` is the caller's
        round-trace ``fold`` span (None when off). Its children: the file's
        bytes into memory (``fold.read``) and the arithmetic on the sum
        (``fold.accumulate``), one pair a leaf on the one-pass path."""
        path = Path(path)
        stats = FoldStats(bytes=path.stat().st_size)
        times = {"read_s": 0.0, "accumulate_s": 0.0}

        def phase(name: str, attrs: dict | None = None) -> trace.phase:
            return trace.phase(
                f"fold.{name}", parent=span, attrs=attrs, into=times, key=f"{name}_s"
            )

        layout = f32_layout(path)
        if layout is None:
            with phase("read", {"bytes": stats.bytes}) as ph:
                tree = compress.read_delta(path)
                ph.set("leaves", len(tree))
            with phase("accumulate", {"leaves": len(tree)}):
                self._fold_tree(tree, samples, sign, prefolded, stats)
        else:
            with open(path, "rb", buffering=0) as fp:

                def read(key: str, landing: np.ndarray) -> np.ndarray:
                    _, offset, nbytes = layout[key]
                    if nbytes:
                        with phase("read", {"bytes": nbytes}):
                            read_exact(fp.fileno(), offset, landing)
                    return landing

                stats.direct = len(layout)
                self._fold_leaves(
                    {key: shape for key, (shape, _, _) in layout.items()},
                    samples, sign, prefolded, stats, read,
                    staged=True, timed=lambda: phase("accumulate"),
                )
        stats.read_s, stats.accumulate_s = times["read_s"], times["accumulate_s"]
        return stats

    def fold_tree(
        self,
        tree: dict,
        samples: float,
        sign: float = 1.0,
        prefolded: bool = False,
    ) -> None:
        """Fold an already-decoded delta tree (the file-less entry point
        the group reducer uses on its own freshly decoded payloads)."""
        self._fold_tree(tree, samples, sign, prefolded, FoldStats())

    def _fold_tree(self, tree, samples, sign, prefolded, stats: FoldStats) -> None:
        self._fold_leaves(
            {key: np.shape(value) for key, value in tree.items()},
            samples, sign, prefolded, stats,
            lambda key, _: np.ascontiguousarray(tree[key], np.float32),
        )

    def _fold_leaves(
        self,
        shapes: dict[str, tuple],
        samples: float,
        sign: float,
        prefolded: bool,
        stats: FoldStats,
        load: Callable[[str, "np.ndarray | None"], np.ndarray],
        staged: bool = False,
        timed: Callable[[], contextlib.AbstractContextManager] = contextlib.nullcontext,
    ) -> None:
        """The one fold: ``acc <- scale * x`` for a round's first delta (an
        overwrite, so a kept buffer's stale contents never count and
        ``-0.0`` stays ``-0.0``), ``acc <- acc + scale * x`` after.
        ``load(key, landing)`` gives leaf ``key`` as f32; a ``staged``
        loader fills ``landing`` (the sum's own buffer on a first fold, a
        staging leaf after) and returns it."""
        if self._shapes and set(shapes) != set(self._shapes):
            raise ValueError("workers sent deltas with mismatched keys")
        for key, shape in shapes.items():
            if self._shapes.get(key, shape) != shape:
                raise ValueError(
                    f"delta {key!r}: mismatched shape {shape} vs {self._shapes[key]}"
                )
        # A prefolded payload is already Σ samples·Δ — re-weighting it
        # would square the sample count; only the un-fold sign applies.
        scale = np.float32(sign) if prefolded else np.float32(sign * samples)
        pool = self._buffers
        first = not self._acc
        if first:
            leased = {key: pool.lease(key, shape) for key, shape in shapes.items()}
            acc = {key: buf for key, (buf, _) in leased.items()}
            stats.resident = sum(kept for _, kept in leased.values())
        else:
            acc = self._acc
            stats.resident = len(shapes)
        staging = None
        if staged and not first:
            staging = pool.take_staging(max(a.size for a in acc.values()))
        try:
            for key, shape in shapes.items():
                landing = acc[key]
                if staging is not None:
                    landing = staging[:landing.size].reshape(shape)
                x = load(key, landing)
                with timed():
                    ran = native.fold_scaled(acc[key], x, scale, first, pool.threads)
                stats.threads = max(stats.threads, ran)
        except BaseException:
            if first:
                pool.give_back(acc)
            raise
        finally:
            if staging is not None:
                pool.put_staging(staging)
        if first:
            self._acc, self._shapes = acc, dict(shapes)
        stats.leaves = len(shapes)
        self.total_samples += sign * samples
        self.folds += 1 if sign > 0 else -1

    def _denom(self) -> np.float32:
        return np.float32(max(self.total_samples, 1e-20))

    def mean(self) -> dict[str, np.ndarray]:
        """The sample-weighted mean ḡ = Σ samples·Δθ / Σ samples (f32).

        The tests' reference for ``native.fused_mean_nesterov``: the
        in-place pass is held to this followed by
        ``native.nesterov_update``, on the same backend, every bit. No
        caller in the program.
        """
        if not self._acc:
            raise ValueError("no deltas folded")
        denom = self._denom()
        return {k: v / denom for k, v in self._acc.items()}

    def take(self) -> tuple[dict[str, np.ndarray], np.float32]:
        """Hand over the partial sum Σ samples·Δθ and the divisor
        :meth:`mean` would use, and leave the accumulator empty: the PS's
        outer step writes the update over the sum where it lies, so nobody
        may read this sum again. Its buffers stay out of ``buffers`` until
        :meth:`release`."""
        if not self._acc:
            raise ValueError("no deltas folded")
        taken = self._acc, self._denom()
        self._acc, self._shapes = {}, {}
        self.total_samples, self.folds = 0.0, 0
        return taken

    def release(self, taken: dict[str, np.ndarray]) -> None:
        """Give a :meth:`take`n sum's buffers back for the next round's
        sum, once nothing reads them any more."""
        self._buffers.give_back(taken)

    def partial(self) -> dict[str, np.ndarray]:
        """The raw weighted partial sum Σ samples·Δθ (f32) — what a group
        reducer ships to its shard (header ``prefold`` + the weight), so
        the shard's own fold of it is bit-equal to having folded the
        members directly in the same order."""
        if not self._acc:
            raise ValueError("no deltas folded")
        return dict(self._acc)
