"""Live weight streaming: zero-downtime train→serve hot swaps.

A serving worker attaches to a training job's parameter-server broadcast
as one more leaf — directly in flat jobs, or as a relay child under a
broadcast tree (``stream.tree.with_serve_leaves``) — and follows the
model BEING TRAINED round by round, without restarts, without draining
the request queue, and without a separate checkpoint-publish pipeline.

The broadcast carries per-round outer UPDATES ``u_r`` (deltas), not
absolute weights: the served model is ``θ_r = θ_0 + Σ_{i<=r} u_i``.
Two invariants follow, and this module exists to hold them:

* **Contiguity.** Updates fold in strict round order starting at
  ``WeightFollow.round + 1`` (the round the dispatched params embody).
  Skipping a round would serve a model that never existed on any
  trainer. :class:`WeightStager` stages out-of-order arrivals and only
  releases complete rounds contiguous with what is already applied.
* **Atomicity.** A round's update spans many fragment wires; flipping
  leaves as fragments land would let an in-flight decode step read
  MIXED-round weights. The stager assembles the full round on the host
  first; the pool then applies it in one assignment at a chunk boundary
  (``DecodePool.request_swap`` → ``_apply_swap``), between dispatched
  programs, where nothing reads ``_vars`` concurrently.

:class:`WeightSubscriber` is the networked half: a
:class:`~hypha_tpu.worker.connectors.Connector` receive loop filtered to
the broadcast's resource tag, honouring the same results-stream protocol
markers train workers do — PS generation bumps (``ps_generation``),
resync announcements (no payload), and rejoin catch-ups (a CUMULATIVE
Σ of rounds; folding one as if it were a single round's delta would
double-apply history, so catch-ups are dropped and counted).

Failure posture: a permanently lost broadcast round wedges the follower
at its last applied round — by design, it keeps SERVING that round
(stale-but-consistent beats fresh-but-fictional). ``stats()`` exposes
the held-round count so operators can alert and re-dispatch.
"""

from __future__ import annotations

import asyncio
import logging
import tempfile
from pathlib import Path
from typing import Any

import numpy as np

from ..compress import read_delta
from ..ft.durable import RESYNC_KEY, restart_signal
from ..ft.rejoin import CATCHUP_KEY
from ..messages import FragmentTag, Receive, Reference, WeightFollow
from ..worker.connectors import Connector

__all__ = ["WeightStager", "WeightSubscriber", "follow_for"]

log = logging.getLogger("hypha.serving.weight_stream")


def follow_for(
    results_tag: str,
    ps_peers: list,
    *,
    groups: list | None = None,
    start_round: int = 0,
    ps_generation: int = 0,
    fragments: int = 0,
    pin_round: int | None = None,
    keep_previous: bool = False,
) -> WeightFollow:
    """Build a follower's :class:`WeightFollow` with the broadcast's
    Receive allowlist derived the way train workers derive theirs: the PS
    shard peers plus every relay head of the reduce ``groups`` — under a
    broadcast tree the follower's wire arrives from its assigned relay,
    and dead-relay failover can re-route it through ANY head, so all of
    them are admitted (an unlisted sender's push is silently dropped by
    the Connector, which would wedge the follower at its last round)."""
    heads = {g[0] for g in (groups or []) if len(g) >= 2}
    allowed = sorted({str(p) for p in ps_peers} | {str(h) for h in heads})
    return WeightFollow(
        results=Receive(Reference.from_peers(allowed, results_tag)),
        round=int(start_round),
        ps_generation=int(ps_generation),
        fragments=int(fragments),
        pin_round=pin_round,
        keep_previous=keep_previous,
    )


class WeightStager:
    """Round assembly for a weight-stream follower. Pure host state.

    Feed every decoded broadcast wire through :meth:`offer`; it returns
    the (possibly empty) list of ``(round, update)`` pairs that became
    ready — complete AND contiguous with ``applied_round`` — in apply
    order. Fragments of one round carry disjoint leaf subsets and merge
    by addition (sharded senders can overlap only on re-sends, which
    overwrite in staging first, so nothing folds twice).

    ``fragments`` pins the wire count a round needs before it can ship
    (stream-staggered jobs broadcast ONE due fragment per round, so the
    scheduler pins 1 there); 0 derives it from each wire's FragmentTag,
    with untagged wires counting as single-file rounds.
    """

    def __init__(
        self,
        *,
        start_round: int = 0,
        ps_generation: int = 0,
        fragments: int = 0,
    ) -> None:
        self.applied_round = int(start_round)
        self.generation = int(ps_generation)
        self.fragments = int(fragments)
        # round -> fragment_id -> leaf arrays (re-sends overwrite).
        self._staging: dict[int, dict[int, dict[str, np.ndarray]]] = {}
        self._expect: dict[int, int] = {}  # round -> wires needed
        self.dropped_stale = 0  # wires for rounds <= applied
        self.rounds_ready = 0
        self.generation_changes = 0

    # ----------------------------------------------------------- queries

    def held_rounds(self) -> list[int]:
        """Rounds staged (complete or not) but not yet releasable —
        non-empty long after traffic means a gap wedged the follower."""
        return sorted(self._staging)

    def _complete(self, round_num: int) -> bool:
        have = self._staging.get(round_num)
        if not have:
            return False
        need = self.fragments or self._expect.get(round_num, 1)
        return len(have) >= need

    # ---------------------------------------------------------- ingest

    def note_generation(self, ps_generation: Any) -> None:
        """Adopt a PS generation observed on a payload-less marker wire
        (resync announce / catch-up header). Round numbering continues
        across PS restarts, so staging is kept — a recovered PS re-sends
        its last committed round and re-sends simply overwrite."""
        if ps_generation is None:
            return
        gen = int(ps_generation)
        if gen != self.generation:
            self.generation_changes += 1
            self.generation = gen

    def offer(
        self,
        round_num: int,
        arrays: dict,
        *,
        fragment_id: int = 0,
        fragments: int = 1,
        ps_generation: Any = None,
    ) -> list[tuple[int, dict]]:
        """Stage one decoded wire; return newly releasable rounds.

        Stale wires (round already applied — a recovered PS re-sending
        its last committed round, or relay duplicates) drop with a
        counter. Future rounds stage until the gap closes.
        """
        self.note_generation(ps_generation)
        r = int(round_num)
        if r <= self.applied_round:
            self.dropped_stale += 1
            return []
        self._staging.setdefault(r, {})[int(fragment_id)] = arrays
        prev = self._expect.get(r, 1)
        self._expect[r] = max(prev, int(fragments), 1)
        ready: list[tuple[int, dict]] = []
        while self._complete(self.applied_round + 1):
            nxt = self.applied_round + 1
            parts = self._staging.pop(nxt)
            self._expect.pop(nxt, None)
            merged: dict[str, np.ndarray] = {}
            for fid in sorted(parts):
                for name, arr in parts[fid].items():
                    if name in merged:
                        merged[name] = merged[name] + np.asarray(arr)
                    else:
                        merged[name] = np.asarray(arr)
            self.applied_round = nxt
            self.rounds_ready += 1
            ready.append((nxt, merged))
        return ready


def _read_and_unlink(path: Path) -> dict:
    """Decode a wire and drop its name, in that order and in one thread. The
    node that saved the file writes the stream's next push over it once
    nobody else names it (``worker/connectors.py`` ``claim_spare``), so the
    name has to outlive the read, also where the caller is cancelled while
    this thread still reads."""
    try:
        return read_delta(path)
    finally:
        path.unlink(missing_ok=True)


class WeightSubscriber:
    """The receive loop: broadcast wire → stager → pool swap request.

    ``pool`` needs ``request_swap(updates, *, round_num, generation,
    keep_previous)`` and ``pin_round`` — :class:`~hypha_tpu.executor.
    pool.DecodePool`'s swap surface (both thread-safe, so calling them
    from the event loop while the serve thread decodes is fine).
    Ownership of the Connector's node stays with the caller; ``stop``
    only cancels the receive task and removes the staging directory's
    leftover wires.
    """

    def __init__(
        self,
        node: Any,
        follow: WeightFollow,
        pool: Any,
        *,
        work_dir: Path | str | None = None,
    ) -> None:
        self.follow = follow
        self.pool = pool
        self._conn = Connector(node)
        self._dir = Path(work_dir) if work_dir is not None else None
        self._task: asyncio.Task | None = None
        self.stager = WeightStager(
            start_round=follow.round,
            ps_generation=follow.ps_generation,
            fragments=follow.fragments,
        )
        self.fragments_received = 0
        self.bytes_received = 0
        self.dropped_markers = 0  # resync announces + catch-up wires
        self.decode_errors = 0
        self.swaps_requested = 0

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Spawn the receive loop on the running event loop."""
        if self._task is None or self._task.done():
            self._task = asyncio.ensure_future(self.run())

    async def stop(self) -> None:
        if self._task is not None:
            from .. import aio

            await aio.reap(self._task)
            self._task = None

    def stats(self) -> dict:
        return {
            "applied_round": self.stager.applied_round,
            "ps_generation": self.stager.generation,
            "fragments_received": self.fragments_received,
            "bytes_received": self.bytes_received,
            "rounds_ready": self.stager.rounds_ready,
            "held_rounds": self.stager.held_rounds(),
            "dropped_stale": self.stager.dropped_stale,
            "dropped_markers": self.dropped_markers,
            "decode_errors": self.decode_errors,
            "swaps_requested": self.swaps_requested,
        }

    # ------------------------------------------------------------- loop

    async def run(self) -> None:
        """Receive broadcast wires until cancelled. The rollback pin (if
        any) applies before the first wire so no early swap races it."""
        if self.follow.results is None:
            raise ValueError("WeightFollow.results is required to subscribe")
        if self.follow.pin_round is not None:
            self.pool.pin_round(self.follow.pin_round)
        dest = self._dir or Path(tempfile.mkdtemp(prefix="weight-stream-"))
        async for rf in self._conn.receive(self.follow.results, dest):
            try:
                await self._handle(rf)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — one bad wire, not the loop
                self.decode_errors += 1
                log.exception("weight-stream wire from %s failed", rf.from_peer)
            # Not on the way out of a cancellation: a read that is still in
            # its thread drops the name itself (``_read_and_unlink``).
            Path(rf.path).unlink(missing_ok=True)

    async def _handle(self, rf: Any) -> None:
        meta = rf.meta or {}
        gen, _resend = restart_signal(meta, self.stager.generation)
        if meta.get(RESYNC_KEY) or meta.get(CATCHUP_KEY):
            # Resync announcements carry no tensor payload. Catch-ups are
            # a rejoiner-targeted CUMULATIVE Σ of rounds — folding one as
            # a single round's delta would double-apply history.
            self.dropped_markers += 1
            self.stager.note_generation(gen)
            return
        tag = FragmentTag.from_header(meta)
        if tag is not None:
            round_num, fid, total = tag.round, tag.fragment_id, tag.fragments
        else:
            try:
                round_num = int(meta.get("round", 0) or 0)
            except (TypeError, ValueError):
                round_num = 0
            fid, total = 0, 1
        # Decode off the event loop: dequantize of a large fragment is
        # milliseconds of pure NumPy that must not stall other receives.
        arrays = await asyncio.to_thread(_read_and_unlink, Path(rf.path))
        self.fragments_received += 1
        self.bytes_received += int(rf.size or 0)
        for r, update in self.stager.offer(
            round_num,
            arrays,
            fragment_id=fid,
            fragments=total,
            ps_generation=gen,
        ):
            self.pool.request_swap(
                update,
                round_num=r,
                generation=self.stager.generation,
                keep_previous=self.follow.keep_previous,
            )
            self.swaps_requested += 1
