"""Streaming dataset: scheduler-assigned SafeTensors slices → batches.

Parity with the reference's ``IterableStreamDataSet`` + ``fetch_data``
(executors/accelerate/.../dataset.py:10-41, utils.py:68-74): an infinite
generator asks the bridge for the next slice path (the scheduler picks the
slice index via its SliceTracker), loads the SafeTensors file, optionally
applies a preprocessor to configured keys, and yields per-sample dicts;
batching stacks ``batch_size`` consecutive samples.

TPU-native difference: batches come out as device-ready stacked numpy
arrays with static shapes (XLA recompiles on shape change, so ragged
tails are dropped — the stream is infinite anyway).

Async input pipeline (``pipeline=True``, ISSUE 15): the same infinite
stream, restructured so the hot path never waits on input —

  * **slice prefetch** — a bounded background :class:`SlicePrefetcher`
    thread runs ``fetch_slice()`` (bridge DataRequest + data-node pull +
    disk write) up to ``prefetch`` slices ahead while the current slice
    trains, so a slice exhaustion costs a queue pop instead of a full
    scheduler round-trip plus a network transfer;
  * **zero-copy batch assembly** — slice tensors are ALREADY stacked
    arrays, so :func:`slice_batches` hands out contiguous
    ``v[i*B:(i+1)*B]`` views instead of re-stacking ``B`` per-sample
    dicts per batch, with a carry-over buffer joining the ragged tail of
    one slice to the head of the next (the only batch that pays a copy —
    exactly the batch the legacy path also materialized). Only the
    configured ``input_names`` keys are read from the SafeTensors file
    (when no preprocessor needs the rest).

Both assemblies yield bit-identical batch values in the identical order;
``pipeline=False`` (the default) runs the original per-sample code path
unchanged.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import numpy as np
from safetensors.numpy import load_file

from ..telemetry import trace
from ..telemetry.ft_metrics import DATA_METRICS

__all__ = [
    "slice_samples",
    "batches",
    "stream_batches",
    "load_slice",
    "slice_batches",
    "SlicePrefetcher",
    "DEFAULT_PREFETCH_SLICES",
]

log = logging.getLogger("hypha.executor.dataset")

# Slices the pipeline fetches ahead when the job doesn't pin a depth: one
# training, one landing, one in flight is the classic double-buffer + 1;
# two held covers a fetch slower than a whole slice's worth of steps
# without ballooning disk footprint.
DEFAULT_PREFETCH_SLICES = 2


def _sample_count(tensors: dict, path: Path | str) -> int:
    """Leading-axis sample count shared by both assemblies: warn + clamp
    on ragged counts, and surface an all-empty slice as an ERROR — the
    legacy path yielded nothing silently, so the infinite stream spun
    re-fetching the same empty slice forever."""
    if not tensors:
        raise ValueError(
            f"slice {path}: no tensors to train on (empty file, or "
            "input_names filtered everything out)"
        )
    counts = {k: int(v.shape[0]) if v.ndim else 0 for k, v in tensors.items()}
    n = min(counts.values())
    if len(set(counts.values())) > 1:
        log.warning("slice %s: ragged sample counts %s; using %d", path, counts, n)
    if n == 0:
        raise ValueError(f"slice {path}: zero samples (counts {counts})")
    return n


def slice_samples(
    path: Path | str,
    input_names: list[str] | None = None,
    preprocessor: Callable[[dict[str, np.ndarray]], dict[str, np.ndarray]] | None = None,
) -> Iterator[dict[str, np.ndarray]]:
    """Yield per-sample dicts from one SafeTensors slice file
    (dataset.py:10-41: each tensor's leading axis indexes samples)."""
    tensors = load_file(str(path))
    if preprocessor is not None:
        tensors = preprocessor(tensors)
    if input_names:
        tensors = {k: tensors[k] for k in input_names}
    n = _sample_count(tensors, path)
    for i in range(n):
        yield {k: v[i] for k, v in tensors.items()}


def load_slice(
    path: Path | str,
    input_names: list[str] | None = None,
    preprocessor: Callable[[dict[str, np.ndarray]], dict[str, np.ndarray]] | None = None,
) -> dict[str, np.ndarray]:
    """One slice as stacked arrays trimmed to the common sample count.

    The zero-copy twin of :func:`slice_samples`: same key filter, same
    preprocessor hook, same ragged-count clamp and same empty-slice
    error — but the arrays stay whole for contiguous-view batching, and
    when only ``input_names`` matter (no preprocessor, which may read
    other keys) only those tensors are deserialized from the file.
    """
    if input_names and preprocessor is None:
        from safetensors import safe_open

        with safe_open(str(path), framework="np") as f:
            missing = [k for k in input_names if k not in f.keys()]
            if missing:
                raise KeyError(
                    f"slice {path}: missing input tensors {missing}"
                )
            tensors = {k: f.get_tensor(k) for k in input_names}
    else:
        tensors = load_file(str(path))
        if preprocessor is not None:
            tensors = preprocessor(tensors)
        if input_names:
            tensors = {k: tensors[k] for k in input_names}
    n = _sample_count(tensors, path)
    return {k: v[:n] for k, v in tensors.items()}


def batches(
    samples: Iterator[dict[str, np.ndarray]], batch_size: int
) -> Iterator[dict[str, np.ndarray]]:
    """Stack consecutive samples into static-shape batches."""
    buf: list[dict[str, np.ndarray]] = []
    for sample in samples:
        buf.append(sample)
        if len(buf) == batch_size:
            yield {k: np.stack([s[k] for s in buf]) for k in buf[0]}
            buf.clear()


def slice_batches(
    slices: Iterator[dict[str, np.ndarray]], batch_size: int
) -> Iterator[dict[str, np.ndarray]]:
    """Zero-copy batches from whole-slice arrays.

    Full batches inside a slice are contiguous ``v[i*B:(i+1)*B]`` views —
    no per-sample re-stacking, no copy. A slice's ragged tail is carried
    over and concatenated with the next slice's head, so batches span
    slice boundaries with the exact values (and order) the per-sample
    path produces; only those boundary batches materialize new arrays,
    which the stacking path did for EVERY batch.
    """
    B = int(batch_size)
    if B <= 0:
        raise ValueError("batch_size must be positive")
    carry: dict[str, np.ndarray] | None = None
    keys: list[str] | None = None
    for tensors in slices:
        if keys is None:
            keys = sorted(tensors)
        elif sorted(tensors) != keys:
            raise ValueError(
                f"slice key mismatch mid-stream: {sorted(tensors)} vs {keys}"
            )
        n = min(int(v.shape[0]) for v in tensors.values())
        start = 0
        if carry is not None:
            have = int(next(iter(carry.values())).shape[0])
            need = B - have
            if n < need:
                carry = {
                    k: np.concatenate([carry[k], tensors[k][:n]])
                    for k in tensors
                }
                continue
            yield {
                k: np.concatenate([carry[k], tensors[k][:need]])
                for k in tensors
            }
            carry = None
            start = need
        full = (n - start) // B
        for i in range(full):
            lo = start + i * B
            yield {k: v[lo : lo + B] for k, v in tensors.items()}
        rem = start + full * B
        if rem < n:
            # Views into the slice arrays: kept alive by this dict until
            # the boundary batch materializes them above.
            carry = {k: v[rem:n] for k, v in tensors.items()}


class SlicePrefetcher:
    """Bounded background slice fetcher: at most ``depth`` fetched-ahead
    slices exist at once (the queue bound throttles the producer), so a
    slice exhaustion on the training thread costs a queue pop while the
    NEXT slice's scheduler round-trip + network pull is already underway.

    Transient fetch failures (a data node mid-restart, a scheduler blip)
    retry with exponential backoff for up to ``retry_deadline_s`` seconds
    before the error surfaces on the consumer — a killed-and-restarted
    data node costs backed-off re-attempts, not a failed job.
    """

    _ERROR = "error"

    def __init__(
        self,
        fetch_slice: Callable[[], str],
        depth: int = DEFAULT_PREFETCH_SLICES,
        retry_deadline_s: float = 60.0,
        retry_base_s: float = 0.25,
    ) -> None:
        self.depth = max(int(depth), 1)
        self._fetch = fetch_slice
        self._retry_deadline_s = float(retry_deadline_s)
        self._retry_base_s = float(retry_base_s)
        self._q: "queue.Queue[tuple[str, Any]]" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._main, daemon=True, name="slice-prefetch"
        )
        self._thread.start()

    # ----------------------------------------------------- producer thread

    def _main(self) -> None:
        while not self._stop.is_set():
            failed_since: float | None = None
            attempt = 0
            while True:
                try:
                    path = self._fetch()
                    break
                except Exception as e:  # noqa: BLE001 — surfaced below
                    DATA_METRICS.prefetch_errors.add(1)
                    now = time.monotonic()
                    failed_since = failed_since if failed_since is not None else now
                    if (
                        self._stop.is_set()
                        or now - failed_since >= self._retry_deadline_s
                    ):
                        self._q.put((self._ERROR, e))
                        return
                    delay = min(self._retry_base_s * (2.0 ** attempt), 5.0)
                    attempt += 1
                    log.warning(
                        "slice prefetch failed (%s); retrying in %.2fs", e, delay
                    )
                    if self._stop.wait(delay):
                        return
            self._q.put(("path", path))
            DATA_METRICS.note_queue_depth(self._q.qsize())

    # ------------------------------------------------------------ consumer

    def take(self) -> str:
        """Next ready slice path, blocking until the prefetcher lands one
        (the blocked time IS the residual slice-boundary stall)."""
        kind, value = self._q.get()
        DATA_METRICS.note_queue_depth(self._q.qsize())
        if kind == self._ERROR:
            raise RuntimeError(f"slice prefetch failed: {value}") from (
                value if isinstance(value, BaseException) else None
            )
        return value

    def close(self) -> None:
        """Stop fetching; unblock a producer parked on the full queue."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except Exception:  # Empty — also robust to interpreter teardown,
                break  # where the generator's GC can outlive module globals
        self._thread.join(timeout=5.0)


def _boundary_wait(acquire: Callable[[], str], span_ctx) -> str:
    """Time (and trace) the training thread's slice acquisition — the
    slice-boundary stall the prefetcher exists to hide. ``span_ctx`` is a
    zero-arg callable returning ``(traceparent, node, round)`` so the span
    joins the current round's trace (no-op when tracing is off)."""
    parent, node, round_num = (
        span_ctx() if span_ctx is not None else (None, None, None)
    )
    span = trace.begin(
        "input_wait", parent=parent, node=node,
        attrs=None if round_num is None else {"round": round_num},
    )
    t0 = time.monotonic()
    try:
        return acquire()
    finally:
        trace.finish(span)
        DATA_METRICS.note_boundary_wait(time.monotonic() - t0)


def stream_batches(
    fetch_slice: Callable[[], str],
    batch_size: int,
    input_names: list[str] | None = None,
    preprocessor: Callable | None = None,
    *,
    pipeline: bool = False,
    prefetch: int | None = None,
    span_ctx: "Callable[[], tuple[Any, Any, Any]] | None" = None,
    unlink_consumed: bool = False,
) -> Iterator[dict[str, np.ndarray]]:
    """Infinite batch stream: ``fetch_slice()`` blocks until the scheduler
    assigns the next slice and returns its local path (utils.py:68-74
    fetch_data + dataset_wrapper's infinite epoch loop).

    ``pipeline=False`` (default) is the original synchronous per-sample
    path, bit-identical batches included; ``pipeline=True`` switches to
    background slice prefetch (``prefetch`` deep) + zero-copy assembly —
    same values, same order.
    """

    if not pipeline:

        def samples() -> Iterator[dict[str, np.ndarray]]:
            while True:
                path = _boundary_wait(fetch_slice, span_ctx)
                yield from slice_samples(path, input_names, preprocessor)

        return batches(samples(), batch_size)

    prefetcher = SlicePrefetcher(
        fetch_slice, depth=prefetch or DEFAULT_PREFETCH_SLICES
    )

    def slices() -> Iterator[dict[str, np.ndarray]]:
        try:
            while True:
                path = _boundary_wait(prefetcher.take, span_ctx)
                arrays = load_slice(path, input_names, preprocessor)
                if unlink_consumed:
                    # Pipelined fetches land under epoch-unique names (a
                    # later epoch must not overwrite a slice still being
                    # read) — drop each one once its arrays are in memory,
                    # or a long job accumulates num_slices files per epoch.
                    Path(path).unlink(missing_ok=True)
                yield arrays
        finally:
            prefetcher.close()

    return slice_batches(slices(), batch_size)
