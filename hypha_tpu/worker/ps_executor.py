"""The in-runtime parameter-server executor: the DiLoCo outer optimizer.

Reference: crates/worker/src/executor/parameter_server.rs — the one
executor that is *not* an external process (config runtime=parameter-server,
crates/worker/src/config.rs:135-141). It:

  * receives pseudo-gradient files from workers over push-streams (plain
    or bf16 SafeTensors, or quantized HQD1 frames — hypha_tpu.compress
    sniffs the format per file), names hashed against path injection
    (:133-135);
  * aggregates **incrementally**: each arriving delta is decoded and
    folded into a running sample-weighted f32 partial sum off the event
    loop, so by the time the round closes only the Nesterov step remains
    — the PS no longer sits idle while deltas trickle in and then
    re-reads them all (single weighted mean fixes the reference's
    order-dependent pairwise averaging TODO :192-194; the per-round
    double-send guard fixes TODO :215-218 by un-folding the replaced
    delta);
  * applies the Nesterov outer step ``m ← μ·m + ḡ; update = lr·(μ·m + ḡ)``,
    golden-tested against torch SGD(nesterov=True) like the reference
    (:386-446, test :448-524);
  * broadcasts the **update tensor** (not full weights) to all workers
    with bounded-concurrency fan-out (the reference pushes one peer at a
    time, :232-269) — quantized per the job's ``delta_codec`` with the
    PS's own error-feedback residual — and notifies the scheduler
    ``Progress::Updated`` (:274-283);
  * is **durable** when the job checkpoints (hypha_tpu.ft.durable,
    net-new vs the reference): every accepted delta is journaled, every
    committed round's broadcast retained, and the outer state (momentum,
    catch-up Σ, EF residuals, round counter, epoch) checkpointed — a PS
    restart replays the journal, re-announces itself under a bumped
    generation id, and resumes the interrupted round instead of killing
    the job;
  * can run as **one shard of N** (``AggregateExecutorConfig.shard_index``
    / ``num_ps_shards``, hypha_tpu.stream placement): the executor then
    owns a disjoint part of the parameter tree — in stream mode the
    fragments ``f`` with ``shard_of(f, N) == shard_index`` (it aggregates
    only the rounds whose due fragment it owns and skips the rest), in
    blocking mode the fixed part ``shard_index`` of every round — with its
    own journal, checkpoint, generation id and catch-up buffer, so
    aggregate outer-sync bandwidth scales with the shard count instead of
    one peer's NIC. Tree-reduce partials (``PREFOLD_KEY`` pushes from
    hypha_tpu.stream.reduce) fold verbatim and count the workers they
    ``covers`` toward the round's close.

Tensor math runs on the C++ kernels (hypha_tpu.native) with numpy fallback;
on TPU deployments the same step can run as the jitted tree-op in
hypha_tpu.executor.diloco.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import os
import shutil
import time
import uuid
from dataclasses import dataclass, field
from pathlib import Path
from typing import AsyncIterator

import numpy as np
from safetensors.numpy import load_file, save_file

from .. import aio
from .. import compress
from .. import native
from ..compress.frame import frame_f32
from ..codec import native_codec_active
from ..ft.adaptive import LinkTable
from ..ft.durable import (
    GENERATION_KEY,
    RESYNC_KEY,
    DurablePS,
    FoldRecord,
    stale_scheduler_response,
)
from ..ft.membership import PROTOCOL_FT, MembershipUpdate, RoundMembership, quorum_size
from ..ft.rejoin import CATCHUP_KEY, CatchupBuffer
from ..messages import (
    CODEC_KEY,
    PREFOLD_KEY,
    PROTOCOL_PROGRESS,
    SHARD_KEY,
    TRACEPARENT_KEY,
    Ack,
    FragmentTag,
    JobSpec,
    Progress,
    ProgressKind,
    ProgressResponse,
    ProgressResponseKind,
    TransferStrategy,
)
from ..network.node import Node, RequestError
from .connectors import payload_size, push_timeout
from ..stream import (
    effective_fragments,
    fragment_due,
    next_owned_round,
    placement_parts,
    shard_owns_round,
    top_targets,
    with_serve_leaves,
)
from ..stream.accum import RoundAccum, SumBuffers
from ..stream.reduce import tree_broadcast
from ..telemetry import trace
from ..telemetry.flight import FLIGHT
from ..telemetry.ft_metrics import (
    FT_METRICS,
    HET_METRICS,
    SHARD_METRICS,
    STREAM_METRICS,
)
from .job_manager import Execution, JobExecutor

__all__ = ["ParameterServerExecutor"]

log = logging.getLogger("hypha.worker.ps")

# Elastic collect poll tick: upper bound on how long a membership change or
# pending rejoin waits before the collect loop notices it.
_ELASTIC_TICK_S = 0.5

# Broadcast fan-out width: enough concurrent streams to fill the uplink
# without opening one per peer on a wide job.
_BROADCAST_CONCURRENCY = 8

# A push from memory goes out in views of this many bytes: the event loop
# turns between two of them, so a lease renewal or a second peer's push is
# never kept waiting for a whole parameter-sized write.
_PUSH_SLICE = 4 * 1024 * 1024

# Elastic drain slack: a delta whose payload is still streaming when the
# round deadline passes gets this much extra wall-clock to finish before
# the collector abandons it. Pushes are queued at HEADER arrival, so
# without a drain bound one bandwidth-starved link could hold every round
# open for its whole multi-second transfer — the deadline must bound the
# bytes, not just the header.
_DRAIN_SLACK_S = 0.25


def _file_sha(path: Path) -> str:
    """sha256 of a saved wire file (blocking; run off-loop) — the identity
    the round journal dedups client re-sends on."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            h.update(chunk)
    return h.hexdigest()


# The streaming fold/un-fold accumulator moved to hypha_tpu.stream.accum so
# the tree-reduce group reducer shares the exact arithmetic (its partial sum
# must be bit-equal to what the shard would have folded itself). The private
# alias keeps existing imports/tests working.
_RoundAccum = RoundAccum

# A tree-reduce partial's entry in the round's received table is keyed
# separately from the reducer's OWN direct delta (same sending peer, two
# distinct contributions — peer-keying alone would make one replace the
# other).
_PREFOLD_PREFIX = "prefold:"


class _PsTrace:
    """Round-trace context on the parameter server (no-op when off).

    The scheduler hands the NEXT round's root context back on every
    Updated reply — the only message the PS exchanges with the scheduler
    per round — so quorum_wait / outer_step / broadcast spans parent
    under the round root from round 1 on (round 0 opens before any reply
    exists and stays unparented; per-delta upload/fold spans always
    parent on the context stamped in their own push header).
    """

    def __init__(self, node: str) -> None:
        self.node = node
        # Bounded per-round contexts: round r's broadcast still needs its
        # context AFTER the Updated reply handed over round r+1's, and the
        # pipelined stream loop keeps several rounds in flight at once.
        self._by_round: dict[int, str] = {}

    def ctx(self, round_num: int) -> str | None:
        return self._by_round.get(round_num)

    def phase(self, name: str, round_num: int, min_s: float = 0.0) -> trace.phase:
        """A span of the round's close sequence, filed under the round."""
        return trace.phase(
            name, parent=self.ctx(round_num), attrs={"round": round_num},
            node=self.node, min_s=min_s,
        )

    def adopt(self, response, round_num: int) -> None:
        tp = getattr(response, "traceparent", None)
        # Skip a context already filed under an earlier round: on a
        # sharded job the scheduler only advances once EVERY due shard
        # reported, so a non-final shard's Updated reply hands back the
        # CURRENT round's root — filing it under round_num would parent
        # the next round's spans into the previous round's trace.
        if tp and tp not in self._by_round.values():
            self._by_round[round_num] = tp
            while len(self._by_round) > 16:
                self._by_round.pop(min(self._by_round))

    @staticmethod
    def push_ctx(push) -> str | None:
        """The context a delta push's header carries (None untraced)."""
        r = push.resource
        return r.get(TRACEPARENT_KEY) if isinstance(r, dict) else None

    def adopt_push(self, push, round_num: int) -> None:
        """First delta of a round also carries the round's context — the
        PS's only source for round 0 (no Updated reply exists yet)."""
        tp = self.push_ctx(push)
        if tp and round_num not in self._by_round:
            self._by_round[round_num] = tp


class _ElasticState:
    """Per-job elastic-membership state on the parameter server.

    The scheduler owns membership truth; this is the PS's last adopted
    snapshot plus the rejoin catch-up machinery (hypha_tpu.ft.rejoin).
    """

    def __init__(self, cfg, scheduler_peer: str) -> None:
        self.quorum_fraction = cfg.quorum_fraction
        self.round_deadline_s = cfg.round_deadline_s
        self.scheduler_peer = scheduler_peer
        # Pre-adoption placeholder: epoch 0 is overwritten by the first
        # MembershipUpdate before any round traffic consults it.
        self.membership = RoundMembership(  # hypha-lint: disable=round-tag-not-live
            epoch=0, active=sorted(cfg.updates.ref.peers or [])
        )
        self.catchup = CatchupBuffer()
        # peers awaiting a catch-up push -> remaining send attempts
        self.pending_joins: dict[str, int] = {}
        # early deltas: round -> peer -> (path, samples)
        self.early: dict[int, dict[str, tuple[Path, float]]] = {}
        # tree-reduce cover info for early entries: round -> entry key ->
        # (prefolded, covered worker peers)
        self.early_covers: dict[int, dict[str, tuple[bool, frozenset]]] = {}
        # Durable-state root when the job checkpoints (ft.durable); the
        # catch-up push stamps its generation so rejoiners share the
        # restart-detection protocol.
        self.dur: "DurablePS | None" = None
        # Sharded parameter service: stamped into catch-up headers so a
        # rejoiner can tell the N per-shard catch-ups apart.
        self.shard = 0
        self.num_shards = 1

    def quorum(self) -> int:
        return quorum_size(self.quorum_fraction, len(self.membership.active))

    def adopt(self, update: MembershipUpdate) -> None:
        # Epoch-gated: the orchestrator's notifications are concurrent
        # fire-and-forget requests, so an older snapshot can land after a
        # newer one — adopting it would regress the view (e.g. drop a
        # freshly joined peer, whose deltas would then be rejected as
        # non-member). joined is merged regardless: pending_joins is
        # idempotent and a catch-up owed is owed.
        if update.membership.epoch >= self.membership.epoch:
            self.membership = update.membership
        for peer in update.joined:
            self.pending_joins.setdefault(peer, 3)


@dataclass
class _OuterMomentum:
    """The job's resident outer state: the Nesterov momentum, for the job's
    life, and the buffers each round's partial sum is kept in (``sums``).

    ``file`` is read once, by the first outer step, where something put a
    file there before it (the warm start from ``checkpoint_dir``,
    ``DurablePS.restore_momentum``); otherwise a leaf starts as zeros in
    the first round that carries it. ``save`` is whether the job has a
    ``checkpoint_dir``: the durable commit and ``_checkpoint_momentum``
    are the file's only readers, so only then does an outer step write
    it. ``threads`` is what the fused pass and the fold may use. ``sums``
    is where every :class:`RoundAccum` of the job leases its leaves and
    where the round's update puts them back once its last reader has
    ended (:meth:`_Update.retire`), so a round's sum lies at the last
    round's addresses.
    """

    file: Path
    save: bool
    threads: int = 1
    tree: dict[str, np.ndarray] | None = None
    sums: SumBuffers = field(init=False)

    def __post_init__(self) -> None:
        self.sums = SumBuffers(self.threads)


class _Update:
    """One round's outer update where the fused pass left it: the sum's
    resident f32 buffers, framed as the SafeTensors file they used to be
    copied into.

    ``name`` is the wire's name as ever (``update-<round>.safetensors``: a
    worker's node saves a push under its header's name) and ``nbytes`` what
    a push of it sends. :meth:`views` gives that file's bytes as they lie,
    never joined and never copied. The file itself is written for a reader
    that needs one (:meth:`ensure_file`: a durable job's hard link, a codec
    that encodes from it, a broadcast tree that hands its relays a path)
    and is then what gets pushed, byte for byte as before there was a
    choice. Whoever closes the round calls :meth:`retire` once every reader
    has ended (a push done, failed, or cancelled and reaped): the buffers
    go back to the job's sums and the file, if there is one, goes. A fold
    that opens before then leases buffers of its own (:class:`SumBuffers`),
    so no reader ever sees the next round's sum.
    """

    def __init__(
        self, tree: dict[str, np.ndarray], path: Path, accum: "_RoundAccum"
    ) -> None:
        self.tree = tree
        self.path = path
        self.name = path.name
        self.file: Path | None = None
        self._accum: "_RoundAccum | None" = accum
        self._head, self._views = frame_f32(tree)
        self.nbytes = len(self._head) + sum(len(v) for v in self._views)

    def ensure_file(self) -> Path:
        """The update as a file, ``save_file``'s bytes, written once."""
        if self.file is None:
            if self._accum is None:
                raise RuntimeError(f"{self.name}: retired, its buffers are gone")
            save_file(self.tree, str(self.path))
            self.file = self.path
        return self.file

    async def views(self) -> AsyncIterator[memoryview]:
        """The framed update from its first byte, for one attempt of a push."""
        yield memoryview(self._head)
        for view in self._views:
            for off in range(0, len(view), _PUSH_SLICE):
                yield view[off : off + _PUSH_SLICE]
                await asyncio.sleep(0)

    def retire(self) -> None:
        if self._accum is not None:
            self._accum.release(self.tree)
            self._accum = None
        if self.file is not None:
            self.file.unlink(missing_ok=True)
            self.file = None


def _wire_file(wire: "Path | _Update") -> Path:
    """The wire as a file, for a reader that takes a path."""
    return wire.ensure_file() if isinstance(wire, _Update) else wire


def _fire_once(fn):
    """Wrap an async thunk so only the FIRST call runs it.

    The round's broadcast must fire exactly once — either from the
    resilient notify's outage path (first failed attempt, so a quorate
    round closes without the scheduler) or from the normal post-notify
    call — never both, never zero. One helper instead of three hand-rolled
    flag dicts, so the semantics cannot drift between the blocking,
    adaptive and stream loops.
    """
    done = {"v": False}

    async def run() -> None:
        if done["v"]:
            return
        done["v"] = True
        await fn()

    return run


class ParameterServerExecutor(JobExecutor):
    def __init__(
        self, node: Node, work_root: Path | str = "/tmp", cpus: float = 0
    ) -> None:
        self.node = node
        self.work_root = Path(work_root)
        # The outer step's threads: the cores this process may run on, and
        # no more than the node was given (``resources.cpu``, 0 = not said).
        threads = len(os.sched_getaffinity(0))
        if cpus >= 1:
            threads = min(threads, int(cpus))
        self.threads = max(threads, 1)

    def _trace_node(self) -> str:
        """Span/event node label; tests construct executors without a
        node, and tracing must never be the thing that crashes them."""
        return getattr(self.node, "peer_id", None) or "ps"

    async def execute(
        self, job_id: str, spec: JobSpec, scheduler_peer: str
    ) -> Execution:
        cfg = spec.executor.aggregate
        assert cfg is not None
        work_dir = self.work_root / f"hypha-ps-{uuid.uuid4().hex[:12]}"
        work_dir.mkdir(parents=True)
        execution = Execution(job_id)
        # Durable control plane: a scheduler-recoverable job's aggregation
        # outlives a dead scheduler by the adoption grace (arbiter prune
        # defers the lease; _notify_updated_resilient parks the notify).
        execution.adopt_grace_s = (
            float(getattr(cfg, "adopt_grace_s", 0) or 0) or None
        )
        task = asyncio.create_task(
            self._run(execution, job_id, cfg, scheduler_peer, work_dir)
        )

        async def cancel() -> None:
            await aio.reap(task)
            execution.finish("cancelled")

        execution.cancel = cancel  # type: ignore[method-assign]
        return execution

    async def _run(self, execution, job_id, cfg, scheduler_peer, work_dir: Path):
        allowed = set(cfg.updates.ref.peers or [])
        num_workers = cfg.num_workers or len(allowed)
        if num_workers <= 0:
            execution.finish("failed", "aggregate config names no workers")
            return
        elastic = _ElasticState(cfg, scheduler_peer) if cfg.quorum_fraction > 0 else None
        lr, mu = cfg.optimizer.lr, cfg.optimizer.momentum
        sync_mode = getattr(cfg, "sync_mode", "blocking") or "blocking"
        # Sharded parameter service (hypha_tpu.stream placement): this
        # executor may be one shard of N, owning a disjoint set of
        # placement parts. ``parts`` is the total part count every peer
        # derives (stream fragments, or N blocking sub-deltas); N == 1
        # keeps the exact pre-shard value of effective_fragments.
        num_shards = max(int(getattr(cfg, "num_ps_shards", 1) or 1), 1)
        shard = int(getattr(cfg, "shard_index", 0) or 0)
        sharded = num_shards > 1
        parts = placement_parts(
            sync_mode, getattr(cfg, "fragments", 0), num_shards
        )
        # A stream shard aggregates only the rounds whose due fragment it
        # owns; its journal legitimately skips the others (the durable
        # resume contiguity check consults this).
        owned = None
        if sharded and sync_mode == "stream":
            def owned(r, _p=parts, _n=num_shards, _s=shard):
                return shard_owns_round("stream", r, _p, _n, _s)
        # Momentum is resident state of this job (_OuterMomentum). It is a
        # SafeTensors FILE as well (like the reference,
        # parameter_server.rs:392-397) exactly when the job has a
        # checkpoint_dir: the durable commit and the checkpoint copy, which
        # keeps it across PS restarts, are all that ever read the file.
        momentum_file = work_dir / "momentum.safetensors"
        ckpt_dir = Path(cfg.checkpoint_dir) if cfg.checkpoint_dir else None
        momentum = _OuterMomentum(
            momentum_file, save=ckpt_dir is not None, threads=self.threads
        )
        # Durable PS state (ft.durable): a checkpointing job gets a round
        # journal + outer-state checkpoints under the checkpoint dir, so a
        # PS crash resumes the interrupted round instead of killing the job.
        dur: DurablePS | None = None
        try:
            if ckpt_dir is not None:
                dur = await asyncio.to_thread(
                    lambda: DurablePS.open(
                        ckpt_dir,
                        job_id,
                        max(
                            int(
                                getattr(
                                    cfg, "ps_checkpoint_every_rounds", 1
                                ) or 1
                            ),
                            1,
                        ),
                        owned=owned,
                    )
                )
            if ckpt_dir is not None and (dur is None or dur.resume is None):
                # Cross-attempt warm start (a full job restart runs under a
                # NEW job id, so durable recovery does not apply): momentum
                # is the only outer state that transfers.
                saved = ckpt_dir / "momentum.safetensors"
                if saved.is_file():
                    shutil.copyfile(saved, momentum_file)
                    log.info("ps %s: momentum restored from %s", job_id, saved)
        except Exception as e:
            # A corrupt durable root (gapped journal) or an unwritable /
            # full checkpoint disk must FAIL the job visibly — an exception
            # escaping before the main try would leave the Execution
            # unresolved and the scheduler watching a healthy lease on a
            # job that never completes.
            log.exception(
                "parameter server job %s failed opening durable state", job_id
            )
            execution.finish("failed", str(e))
            if dur is not None:
                await asyncio.to_thread(dur.close)
            await asyncio.to_thread(shutil.rmtree, work_dir, ignore_errors=True)
            return
        round_num = 0
        # The delta files the last round left, kept for the next round's
        # pushes to be saved over (_spool_deltas). Only where the plain
        # collector fills ``received`` and no journal names the files: a
        # durable job's accepted files are never written over, and the
        # elastic collector reads a partial file's size as bytes drained.
        spares: "list[Path] | None" = (
            [] if dur is None and elastic is None else None
        )
        # Routed consumer: only this job's pseudo-gradients (matched on the
        # Receive reference's resource tag) reach this loop, so a colocated
        # train job's bridge — or another PS job — never eats our deltas.
        tag = cfg.updates.ref.resource

        def wants(push) -> bool:
            r = push.resource
            return (
                isinstance(r, dict)
                and (tag is None or r.get("resource") == tag)
            )

        consumer = self.node.consume_pushes(wants)
        # End-to-end round tracing (telemetry.trace): every method below
        # no-ops while tracing is off, and no header gains a key.
        ptrace = _PsTrace(self._trace_node())
        membership_reg = None
        if elastic is not None:
            # The scheduler's membership snapshots arrive over /hypha-ft;
            # adopting one is the only mutation, so the collect loop simply
            # re-reads `elastic.membership` on its next poll tick.
            async def on_membership(peer: str, msg: MembershipUpdate) -> Ack:
                if peer != scheduler_peer:
                    return Ack(ok=False, message="membership updates come from the scheduler")
                log.info(
                    "ps %s: membership epoch %d (active=%d suspected=%d joined=%s)",
                    job_id, msg.membership.epoch, len(msg.membership.active),
                    len(msg.membership.suspected), msg.joined,
                )
                elastic.adopt(msg)
                if msg.membership.inner_steps:
                    # Straggler-adaptive assignment published with the
                    # membership (ft.adaptive): record the per-peer
                    # inner-step gauges on the aggregation side too.
                    for p, steps in msg.membership.inner_steps.items():
                        try:
                            HET_METRICS.note_assigned(str(p), int(steps))
                        except (TypeError, ValueError):
                            continue
                return Ack(ok=True)

            membership_reg = (
                self.node.on(PROTOCOL_FT, MembershipUpdate)
                .match(lambda m: m.job_id == job_id)
                .respond_with(on_membership)
            )
        # Broadcast compression state: the job's delta_codec picks the wire
        # format for the update push; quantized codecs feed their error back
        # into the next outer update so the broadcast stream tracks the
        # uncompressed trajectory exactly like the upload stream does.
        bcast_codec = compress.effective_codec(getattr(cfg, "delta_codec", "none"))
        bcast_ef = (
            compress.ErrorFeedback()
            if bcast_codec in compress.QUANT_CODECS
            else None
        )
        # WAN-adaptive outer rounds (ft.adaptive): report per-peer arrival
        # lags with every Updated (straggler-adaptive inner steps), and/or
        # run the per-LINK codec table — fast links keep the job codec,
        # slow links degrade to int8/int4 with per-peer error-feedback
        # residuals. Both default off; the durable/sharded paths keep the
        # static wire (job_config validates the combinations).
        adaptive_steps = bool(getattr(cfg, "adaptive_steps", False))
        link: LinkTable | None = None
        peer_efs: dict[str, "compress.ErrorFeedback | None"] = {}
        if getattr(cfg, "adaptive_codec", False) and dur is None and not sharded:
            cfg_hi = getattr(cfg, "codec_bw_hi_mbps", None)
            cfg_lo = getattr(cfg, "codec_bw_lo_mbps", None)
            link = LinkTable(
                base_codec=bcast_codec,
                # `is not None`, not `or`: an explicit 0.0 threshold means
                # "never degrade past this tier" and must not silently
                # become the default.
                hi_mbps=float(cfg_hi) if cfg_hi is not None else 100.0,
                lo_mbps=float(cfg_lo) if cfg_lo is not None else 10.0,
            )
        if elastic is not None:
            elastic.dur = dur
            elastic.shard = shard
            elastic.num_shards = num_shards
        stream_fragments = parts
        # Durable control plane (ft.durable): the job's adoption grace —
        # how long the Updated notify may park across a scheduler outage
        # (0 = today's single-attempt behavior).
        park_s = float(getattr(cfg, "adopt_grace_s", 0) or 0)
        # Live metrics plane (telemetry.metrics_plane): registry deltas to
        # the scheduler's collector, plus round-tagged quality (the
        # pseudo-gradient/update norms computed in _outer_step) attached
        # to Updated notifies. None (default) = no reporter, no new wire.
        reporter = None
        report_s = getattr(cfg, "report_metrics_s", None)
        if report_s and self.node is not None:
            from ..telemetry.metrics_plane import MetricsReporter

            def _gen() -> "int | None":
                g = getattr(execution, "scheduler_generation", None)
                return int(g) if g is not None else None

            reporter = MetricsReporter(
                self.node,
                getattr(cfg, "metrics_peer", None) or scheduler_peer,
                job_id,
                interval_s=float(report_s),
                round_fn=lambda: execution.round,
                generation_fn=_gen,
            ).start()
        try:
            # Crash recovery (ft.durable): restore the outer-state
            # checkpoint, replay committed rounds from the journal, re-send
            # the last broadcasts, and seed the interrupted round's inputs.
            preload: dict[int, dict[str, tuple[Path, float]]] = {}
            recovered_accums: dict[int, _RoundAccum] = {}
            recovery_done = False
            if dur is not None and dur.resume is not None:
                (
                    round_num, rec_efs, preload, recovered_accums,
                    recovery_done,
                ) = await self._recover(
                    dur, job_id, cfg, scheduler_peer, work_dir,
                    momentum, elastic, lr, mu, bcast_codec,
                    stream=(sync_mode != "blocking") or sharded,
                    fragments=stream_fragments,
                    shard=shard, num_shards=num_shards,
                    execution=execution,
                )
                if bcast_ef is not None and 0 in rec_efs:
                    bcast_ef = rec_efs[0]
            else:
                rec_efs = {}
            if recovery_done:
                execution.finish("completed")
                return
            if sync_mode != "blocking" or sharded:
                # Streaming outer sync (hypha_tpu.stream): per-fragment
                # round accumulators, pipelined broadcast fan-out. A
                # sharded blocking job ALSO runs this loop (its parts are
                # tagged sub-deltas, the due part is fixed at shard_index);
                # the blocking loop below stays byte-identical for the
                # unsharded default.
                await self._stream_rounds(
                    execution, job_id, cfg, scheduler_peer, work_dir,
                    consumer, elastic, allowed, num_workers,
                    momentum, ckpt_dir, lr, mu, bcast_codec,
                    stream_fragments,
                    dur=dur, round_start=round_num,
                    init_accums=recovered_accums, init_pending=preload,
                    init_efs=rec_efs,
                    shard=shard, num_shards=num_shards,
                    sync_mode=sync_mode,
                )
                return
            while True:
                # Live progress for the AdoptAck handshake: the round this
                # collect will close, and the last adopted membership epoch.
                execution.round = round_num
                if elastic is not None:
                    execution.epoch = elastic.membership.epoch
                # A recovered round resumes its replayed accumulator (its
                # preloaded entries are already folded in, bit-exactly).
                accum = recovered_accums.pop(round_num, None)
                preloaded_folded = accum is not None
                if accum is None:
                    accum = _RoundAccum(momentum.sums)
                if dur is not None:
                    await asyncio.to_thread(dur.note_open, round_num)
                # Per-peer arrival lags (collect start -> delta accepted):
                # the straggler controller's round-trip signal, reported
                # with the Updated notify below. Only adaptive jobs fill it
                # — the Updated wire stays byte-identical otherwise.
                arrivals: dict[str, float] | None = (
                    {} if adaptive_steps else None
                )
                qw_span = trace.begin(
                    "quorum_wait", parent=ptrace.ctx(round_num),
                    attrs={"round": round_num}, node=ptrace.node,
                )
                if elastic is not None:
                    received = await self._collect_round_elastic(
                        consumer, job_id, elastic, cfg, work_dir, round_num,
                        accum=accum, dur=dur, link=link, arrivals=arrivals,
                        ptrace=ptrace,
                    )
                else:
                    received = await self._collect_round(
                        consumer, job_id, allowed, num_workers, work_dir,
                        round_num, accum=accum, dur=dur,
                        preloaded=preload.pop(round_num, None),
                        preloaded_folded=preloaded_folded,
                        link=link, arrivals=arrivals, ptrace=ptrace,
                        spares=spares,
                    )
                # Round 0's root context only arrives inside the first
                # delta's header — late-bind the wait span to it.
                trace.reparent(qw_span, ptrace.ctx(round_num))
                trace.finish(qw_span)
                if dur is not None:
                    await asyncio.to_thread(
                        dur.note_close, round_num, list(received)
                    )
                outer_span = trace.begin(
                    "outer_step", parent=ptrace.ctx(round_num),
                    attrs={"round": round_num}, node=ptrace.node,
                )
                quality = {} if report_s else None
                # The update stays where the outer step computed it, and
                # is a file only for a reader that needs one: the durable
                # commit's hard link, a codec that encodes from it (the
                # job's, or the per-link ones below). A broadcast tree
                # asks for it when it comes to that (_broadcast).
                update = await asyncio.to_thread(
                    self._outer_step,
                    received, momentum, lr, mu, work_dir, round_num,
                    accum, quality, outer_span,
                    file=dur is not None or bcast_codec != "none" or link is not None,
                )
                trace.finish(outer_span)
                if link is not None:
                    # Per-link codec selection: peers grouped by their
                    # LINK's codec, each with its own error-feedback
                    # residual. The rejoin catch-up accumulates the RAW
                    # f32 update — each link tracks it within its own
                    # (bounded, re-shipped) quantization error.
                    # NOTE: this is a TWIN of the static close sequence
                    # below (catch-up -> notify -> broadcast -> cleanup ->
                    # DONE check); a change to either copy's ordering —
                    # especially notify-BEFORE-broadcast, see the race
                    # note below — must be mirrored here.
                    update_path = update.ensure_file()
                    if elastic is not None:
                        await asyncio.to_thread(
                            elastic.catchup.accumulate, update_path
                        )
                    bcast_adaptive = _fire_once(
                        lambda _u=update_path, _r=round_num: (
                            self._broadcast_adaptive(
                                cfg, _u, _r, elastic, link, peer_efs,
                                work_dir, traceparent=ptrace.ctx(_r),
                            )
                        )
                    )
                    with ptrace.phase("notify", round_num):
                        response = await self._notify_updated_resilient(
                            scheduler_peer, job_id, round_num,
                            arrivals=arrivals,
                            traceparent=ptrace.ctx(round_num),
                            execution=execution, park_s=park_s,
                            on_first_failure=bcast_adaptive,
                            quality=quality,
                        )
                    ptrace.adopt(response, round_num + 1)
                    await bcast_adaptive()
                    with ptrace.phase("cleanup", round_num, min_s=trace.SLOW_CLEANUP_S):
                        self._spool_deltas(received, work_dir, spares)
                        update.retire()
                    round_num += 1
                    if elastic is not None:
                        await self._serve_joins(elastic, cfg, round_num, work_dir)
                    if response.kind == ProgressResponseKind.DONE:
                        execution.finish("completed")
                        return
                    continue
                wire, sent_update = await asyncio.to_thread(
                    self._encode_broadcast,
                    update, bcast_codec, bcast_ef, work_dir, round_num,
                )
                if elastic is not None:
                    # The running Σ of updates is the rejoin catch-up payload
                    # (θ_r = θ₀ + Σ); fold this round in BEFORE the durable
                    # commit — the checkpoint must already contain it. The
                    # DECODED update is accumulated, not the f32 one:
                    # θ_r must equal what workers actually merged. The
                    # encode already produced the decoded tree — never
                    # re-read and re-dequantize a parameter-sized frame:
                    # where the wire is the f32 update itself, that tree is
                    # the one in memory.
                    await asyncio.to_thread(
                        elastic.catchup.accumulate_tree,
                        update.tree if sent_update is None else sent_update,
                    )
                if dur is not None:
                    # Durable commit: wire file retained for restart
                    # re-broadcast, outer-state checkpoint when due, then
                    # the fsync'd commit record.
                    wire_name = await asyncio.to_thread(
                        dur.store_wire, round_num, _wire_file(wire)
                    )
                    await asyncio.to_thread(
                        dur.commit_round, round_num, 0, wire_name,
                        epoch=(
                            elastic.membership.epoch
                            if elastic is not None else 0
                        ),
                        momentum_file=momentum_file,
                        catchup=elastic.catchup if elastic is not None else None,
                        efs={0: bcast_ef},
                        active=(
                            list(elastic.membership.active)
                            if elastic is not None else []
                        ),
                    )
                if ckpt_dir is not None:
                    self._checkpoint_momentum(momentum_file, ckpt_dir)
                # Notify BEFORE broadcasting: a worker can merge the update
                # and send UpdateReceived the moment the broadcast lands, and
                # the scheduler must already have advanced the round by then —
                # otherwise the worker is told Continue instead of Done and
                # starts a phantom extra round (the reference broadcasts
                # first, parameter_server.rs:232-283, and carries this race).
                # EXCEPTION — scheduler outage (park_s > 0, first attempt
                # failed): the broadcast fires immediately so the quorate
                # round closes without the scheduler; the workers' own
                # UpdateReceived parks on their side, so the ordering race
                # this comment guards cannot bite while it is down.
                bcast_static = _fire_once(
                    lambda _w=wire, _r=round_num: self._broadcast(
                        cfg, _w, _r, elastic,
                        extra_header=(
                            {GENERATION_KEY: dur.generation}
                            if dur is not None else None
                        ),
                        traceparent=ptrace.ctx(_r),
                        span_round=_r,
                    )
                )
                with ptrace.phase("notify", round_num):
                    response = await self._notify_updated_resilient(
                        scheduler_peer, job_id, round_num, arrivals=arrivals,
                        traceparent=ptrace.ctx(round_num),
                        execution=execution, park_s=park_s,
                        on_first_failure=bcast_static,
                        quality=quality,
                    )
                ptrace.adopt(response, round_num + 1)
                if dur is not None:
                    await asyncio.to_thread(
                        dur.note_notified, round_num,
                        response.kind == ProgressResponseKind.DONE,
                    )
                await bcast_static()
                with ptrace.phase("cleanup", round_num, min_s=trace.SLOW_CLEANUP_S):
                    if dur is None:
                        # Durable runs keep the delta files — the journal
                        # references them until a checkpoint covers the round.
                        self._spool_deltas(received, work_dir, spares)
                    # Broadcast done (and catch-up folded): every push has
                    # ended, so the update's buffers go back to the sums
                    # for the next round's fold, and a long job must not
                    # accumulate two parameter-sized files per round.
                    update.retire()
                    if wire is not update:
                        wire.unlink(missing_ok=True)
                round_num += 1
                if elastic is not None:
                    await self._serve_joins(elastic, cfg, round_num, work_dir)
                if response.kind == ProgressResponseKind.DONE:
                    execution.finish("completed")
                    return
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.exception("parameter server job %s failed", job_id)
            execution.finish("failed", str(e))
        finally:
            if reporter is not None:
                await reporter.stop()
            if membership_reg is not None:
                membership_reg.close()
            consumer.close()
            if dur is not None:
                await asyncio.to_thread(dur.close)
            await asyncio.to_thread(shutil.rmtree, work_dir, ignore_errors=True)

    # ------------------------------------------------------ crash recovery

    async def _recover(
        self,
        dur: DurablePS,
        job_id: str,
        cfg,
        scheduler_peer: str,
        work_dir: Path,
        momentum: _OuterMomentum,
        elastic: "_ElasticState | None",
        lr: float,
        mu: float,
        bcast_codec: str,
        *,
        stream: bool,
        fragments: int,
        shard: int = 0,
        num_shards: int = 1,
        execution=None,
    ) -> tuple:
        """Resume this job from its durable state after a PS restart.

        Returns ``(round_num, bcast_efs, preload, accums, done)``:

          * the outer-state checkpoint restores momentum, the rejoin
            catch-up Σ, per-fragment broadcast EF residuals, the round
            counter and membership epoch;
          * rounds the journal committed AFTER the checkpoint re-run their
            outer step from the journaled folds — bit-exact, because the
            folds re-apply in arrival order against checkpointed state;
          * the scheduler is re-notified for the last committed round iff
            the journal lacks its ``notified`` record (the scheduler
            de-duplicates by round either way);
          * each fragment's newest committed broadcast is re-sent, stamped
            with the NEW generation id — workers that already merged it
            drop it by round; workers still waiting are un-wedged; every
            worker sees the generation bump and re-sends its
            un-acknowledged delta (journal dedup absorbs the copies);
          * the interrupted round's (and any parked future rounds') folds
            come back as ``preload``/``accums`` so the collect loops
            resume instead of restarting the round.
        """
        resume = dur.resume
        assert resume is not None
        # The first replayed (or live) outer step reads the restored file.
        await asyncio.to_thread(dur.restore_momentum, momentum.file)
        quant = bcast_codec in compress.QUANT_CODECS
        bcast_efs: dict[int, "compress.ErrorFeedback | None"] = {}
        if quant:
            for frag, residual in (
                await asyncio.to_thread(dur.restore_efs)
            ).items():
                ef = compress.ErrorFeedback()
                ef.restore(residual)
                bcast_efs[frag] = ef
        if elastic is not None:
            await asyncio.to_thread(dur.restore_catchup, elastic.catchup)
            if resume.epoch >= elastic.membership.epoch and resume.active:
                # The checkpointed view holds until the scheduler's next
                # (epoch-gated) membership push supersedes it.
                elastic.membership = RoundMembership(
                    epoch=resume.epoch, active=sorted(resume.active)
                )
        round_num = resume.next_round
        for rec in resume.committed:
            rnd = int(rec["round"])
            frag = int(rec.get("fragment", 0))
            accum = _RoundAccum(momentum.sums)
            for fold, sign in dur.replay_ops(rnd):
                await asyncio.to_thread(
                    accum.fold, dur.deltas_dir / fold.file, fold.samples,
                    sign, fold.prefold,
                )
            update = await asyncio.to_thread(
                self._outer_step,
                {}, momentum, lr, mu, work_dir, rnd, accum, file=True,
            )
            if quant and frag not in bcast_efs:
                bcast_efs[frag] = compress.ErrorFeedback()
            tag = (
                FragmentTag(
                    round=rnd, fragment_id=frag, fragments=fragments
                ).header()
                if stream
                else None
            )
            wire, sent = await asyncio.to_thread(
                self._encode_broadcast,
                update, bcast_codec, bcast_efs.get(frag), work_dir,
                rnd, tag,
            )
            if rnd == dur.newest_commit(frag):
                await asyncio.to_thread(dur.store_wire, rnd, _wire_file(wire))
            if elastic is not None:
                await asyncio.to_thread(
                    elastic.catchup.accumulate_tree,
                    update.tree if sent is None else sent,
                    frag if stream else None,
                )
            update.retire()
            if wire is not update:
                wire.unlink(missing_ok=True)
            round_num = rnd + 1
        FT_METRICS.ps_recoveries.add(1)
        FLIGHT.record(
            "ps.recovered", node=self._trace_node(), job=job_id,
            generation=dur.generation, round=round_num,
            replayed=len(resume.committed),
        )
        log.warning(
            "ps %s: recovered durable state (generation %d): resuming round "
            "%d (%d committed rounds replayed)",
            job_id, dur.generation, round_num, len(resume.committed),
        )
        done = False
        last_round = round_num - 1
        if last_round >= 0:
            notified = resume.notified.get(last_round)
            if notified is None:
                # A PS and scheduler that died together recover in any
                # order: the re-notify parks across the scheduler's own
                # restart window (idempotent by round on its side).
                response = await self._notify_updated_resilient(
                    scheduler_peer, job_id, last_round, shard=shard,
                    execution=execution,
                    park_s=float(getattr(cfg, "adopt_grace_s", 0) or 0),
                )
                done = response.kind == ProgressResponseKind.DONE
                await asyncio.to_thread(dur.note_notified, last_round, done)
            else:
                done = bool(notified)
        # Restart announcement: an empty "resync" push whose header carries
        # the new generation — every worker re-sends its un-acknowledged
        # delta (journal dedup absorbs the copies that did land). The
        # re-broadcasts below carry the generation too, but a crash before
        # the first commit has no broadcast to carry it on.
        resync_extra: dict = {GENERATION_KEY: dur.generation, RESYNC_KEY: True}
        if num_shards > 1:
            # Per-shard generation handshake: workers track one generation
            # PER shard, so the announcement must say which shard restarted
            # (re-sending every part on one shard's bump would spam the
            # healthy shards with re-sends their journals then dedup).
            resync_extra[SHARD_KEY] = shard
        resync = work_dir / "resync.bin"
        await asyncio.to_thread(resync.write_bytes, b"")
        await self._broadcast(
            cfg, resync, round_num, elastic, extra_header=resync_extra
        )
        for rnd, frag, path in dur.last_wires():
            extra: dict = {GENERATION_KEY: dur.generation}
            if num_shards > 1:
                extra[SHARD_KEY] = shard
            if stream:
                extra.update(
                    FragmentTag(
                        round=rnd, fragment_id=frag, fragments=fragments
                    ).header()
                )
            await self._broadcast(cfg, path, rnd, elastic, extra_header=extra)
        preload: dict[int, dict[str, tuple[Path, float]]] = {}
        accums: dict[int, _RoundAccum] = {}
        for rnd in dur.pending_rounds(round_num):
            bucket = preload.setdefault(rnd, {})
            for fold in dur.folds_for(rnd):
                bucket[fold.peer] = (dur.deltas_dir / fold.file, fold.samples)
            if elastic is None or stream:
                # Rebuild the in-flight accumulator by replaying the EXACT
                # fold/un-fold sequence (replay_ops): bit-identical to the
                # crashed process's partial sum, duplicates included.
                accum = accums.setdefault(rnd, _RoundAccum(momentum.sums))
                for fold, sign in dur.replay_ops(rnd):
                    await asyncio.to_thread(
                        accum.fold, dur.deltas_dir / fold.file, fold.samples,
                        sign, fold.prefold,
                    )
        if elastic is not None and not stream:
            # The elastic collector folds early-parked entries itself when
            # their round opens (last-wins per peer — value-correct; exact
            # bitwise resume is only claimed for the deterministic modes).
            for rnd, bucket in preload.items():
                elastic.early.setdefault(rnd, {}).update(bucket)
            preload = {}
        return round_num, bcast_efs, preload, accums, done

    @staticmethod
    async def _ingest(
        dur: "DurablePS | None",
        round_num: int,
        fragment: int,
        peer: str,
        entry: tuple[Path, float],
        sha: "str | None" = None,
        prefold: bool = False,
        covers=(),
    ) -> bool:
        """Journal one accepted delta; False = exact re-send, skip the fold.

        The dedup key is (round, fragment, peer, sha-of-bytes): after a PS
        restart every worker re-sends its un-acknowledged delta, and the
        copies whose original survived in the journal must fold zero more
        times — folding them would double-count the worker in the mean.
        ``sha`` comes from the save-time hasher when available; the
        re-read fallback only covers callers without one.
        """
        if dur is None:
            return True
        path, samples = entry
        if sha is None:
            sha = await asyncio.to_thread(_file_sha, path)
        if dur.already_folded(round_num, fragment, peer, sha):
            path.unlink(missing_ok=True)
            return False
        await asyncio.to_thread(
            dur.note_fold,
            FoldRecord(
                round=round_num, fragment=fragment, peer=peer,
                samples=samples, sha=sha, file=path.name,
                prefold=prefold, covers=list(covers),
            ),
        )
        return True

    @staticmethod
    def _push_cover(meta, peer: str) -> tuple[bool, frozenset]:
        """(prefolded, covered workers) of one push.

        A tree-reduce partial (``PREFOLD_KEY``) covers the group members
        listed in its ``covers`` header — the round's close condition
        counts covered WORKERS, not accepted files. A direct delta covers
        its sender. An unlabeled prefold defensively covers nothing extra
        beyond crediting the file (empty set keeps liveness: the members
        it silently contains will re-send and dedup/replace)."""
        if isinstance(meta, dict) and meta.get(PREFOLD_KEY):
            return True, frozenset(
                str(p) for p in (meta.get("covers") or [])
            )
        return False, frozenset((peer,))

    @staticmethod
    def _covered(
        received, covers: dict[str, tuple[bool, frozenset]]
    ) -> set:
        """Union of worker peers the round's accepted entries represent."""
        out: set = set()
        for key in received:
            _, cov = covers.get(key, (False, frozenset((key,))))
            out |= cov
        return out

    @staticmethod
    def _entry_key(prefolded: bool, peer: str) -> str:
        """Received-table key: a reducer's forwarded partial must not
        collide with the reducer's OWN direct delta."""
        return f"{_PREFOLD_PREFIX}{peer}" if prefolded else peer

    @staticmethod
    def _prefold_superseded(covers, cov, key: str) -> bool:
        """Must a NEW partial be dropped against the accepted ones?

        Multi-level trees make partial-vs-partial overlap possible: a
        mid-tree reducer's flush can fail over to the shard (ANY) while
        the copy its parent "missed" was in fact delivered — the parent's
        later partial then covers a SUPERSET of the failed-over one's
        workers, and a PROPER overlap (neither contains the other) can
        arise when the parent's bucket holds only the descendant's FIRST
        flush while its cumulative re-flush failed over here. Overlaps
        cannot be decomposed (a cumulative sum is one file), so the rule
        is SIZE-ORDERED, bigger cover wins: a new partial folds only when
        every accepted partial it intersects is STRICTLY SMALLER — those
        are un-folded and retired in :meth:`_retire_covered` (losing at
        worst the few members only they covered — a quorum-absorbed
        undercount, the price of liveness). Otherwise the new partial is
        dropped outright, never journaled. Ties keep the accepted entry,
        so reconciliation is deterministic and the round's cover only
        ever grows toward quorum — arrival-ordered retirement would let
        a small failed-over partial evict a reducer's full-subtree flush
        and park the round below quorum forever. Same-sender re-flushes
        (``key`` match) stay on the duplicate-replacement path — a
        cumulative re-flush always covers at least its predecessor, and
        dropping it would freeze the group at its first flush."""
        return any(
            p and (cov & c) and len(c) >= len(cov)
            for k, (p, c) in covers.items()
            if k != key
        )

    @staticmethod
    def _direct_covered(covers, peer: str) -> bool:
        """Is a direct delta from ``peer`` already represented by an
        accepted tree-reduce partial?

        The ANY-failover wire is at-least-once: a member's push can time
        out against its reducer (yet be delivered), fail over to the
        shard, AND arrive inside the reducer's partial. The journal's
        (round, fragment, peer, sha) dedup cannot see this overlap — the
        partial is journaled under the REDUCER's key with different bytes
        — so the cover sets are the reconciliation: a covered direct
        arrival is dropped, never folded or journaled (replay stays
        consistent for free)."""
        return any(p and peer in c for p, c in covers.values())

    async def _retire_covered(
        self, job_id: str, accum, bucket, covers, cov, durable: bool
    ) -> None:
        """The mirror overlap: a partial arriving AFTER its members'
        failed-over direct deltas supersedes them — its cumulative sum
        already contains their contributions, so the direct entries are
        un-folded and retired (sorted member order; recovery's
        ``replay_ops`` re-derives exactly these un-folds from the
        journaled partial's ``covers``, keeping the replay bit-exact).
        Durable files stay on disk for that replay (checkpoint GC)."""
        # Multi-level trees first: an accepted partial from ANOTHER sender
        # whose covers intersect this one's is un-folded whole, sorted-key
        # order (replay_ops mirrors both loops). Every entry reaching here
        # is STRICTLY SMALLER than the new partial (_prefold_superseded
        # dropped the new one otherwise): usually a descendant's
        # failed-over flush this cumulative sum already contains; under a
        # proper overlap the bigger cover wins and the smaller entry's
        # exclusive members are a quorum-absorbed undercount.
        for okey in sorted(k for k in list(bucket) if k in covers):
            info = covers.get(okey)
            if info is None or not info[0] or not (info[1] & cov):
                continue
            log.warning(
                "ps %s: partial %s overlapped by a newer ancestor partial; "
                "un-folding", job_id, okey,
            )
            old = bucket.pop(okey)
            covers.pop(okey, None)
            await self._fold(accum, old, sign=-1.0, prefolded=True)
            if not durable:
                old[0].unlink(missing_ok=True)
        for member in sorted(cov):
            info = covers.get(member)
            if member not in bucket or (info is not None and info[0]):
                continue  # absent, or a partial (groups are disjoint)
            log.warning(
                "ps %s: delta from %s superseded by a tree-reduce partial "
                "covering it; un-folding", job_id, member,
            )
            old = bucket.pop(member)
            covers.pop(member, None)
            await self._fold(accum, old, sign=-1.0, prefolded=False)
            if not durable:
                old[0].unlink(missing_ok=True)

    @staticmethod
    async def _classify_push(push, job_id: str, members, round_num: int):
        """Shared triage for the elastic and streaming collectors.

        Returns the round the delta claims, or None when the push was
        dropped (non-member sender, or stale — its round already
        aggregated); dropped pushes are drained so the sender's accept
        slot is released. One copy of these checks, so a fix (like PR 1's
        epoch gating) cannot silently reach only one sync mode.

        ``members=None`` means "no allowlist" (a plain job whose config
        names no peers). An EMPTY set stays strict — elastic membership
        with every worker evicted must drop everything, not open up.
        """
        peer = push.peer
        if members is not None and peer not in members:
            log.warning(
                "ps %s: push from non-member peer %s dropped", job_id, peer
            )
            await push.read_all()
            return None
        delta_round = round_num
        if isinstance(push.resource, dict) and "round" in push.resource:
            try:
                delta_round = int(push.resource["round"])
            except (TypeError, ValueError):
                delta_round = round_num
        if delta_round < round_num:
            log.warning(
                "ps %s: stale delta for round %d from %s dropped (now %d)",
                job_id, delta_round, peer, round_num,
            )
            FT_METRICS.stale_deltas_dropped.add(1)
            await push.read_all()
            return None
        return delta_round

    @staticmethod
    async def _fold(
        accum: "_RoundAccum | None",
        entry: tuple[Path, float],
        sign: float = 1.0,
        prefolded: bool = False,
        span_attrs: dict | None = None,
        parent: str | None = None,
        trace_node: str | None = None,
    ) -> None:
        """Fold one saved delta into the round's partial sum, off-loop.

        Decode + fold overlap the next push's arrival — the streaming
        aggregation that leaves only the Nesterov step at quorum close.
        ``accum`` is None when a caller (tests) only wants collection.
        ``prefolded`` marks a tree-reduce partial: already Σ samples·Δθ,
        added verbatim (scaled only by ``sign``). ``span_attrs`` opens a
        round-trace ``fold`` span around the work (accept-path folds
        only; un-folds and replays stay spanless). What the fold did
        (``direct``: leaves that went from the file into resident memory
        in one pass; ``resident``: leaves whose buffer was there and not
        allocated) goes on that span and on the ``ps fold:`` line.
        """
        if accum is None:
            return
        fold_span = (
            trace.begin(
                "fold", parent=parent, attrs=span_attrs, node=trace_node,
                usage=True,
            )
            if span_attrs is not None and sign > 0
            else None
        )
        did = await asyncio.to_thread(
            accum.fold, entry[0], entry[1], sign, prefolded, fold_span
        )
        if fold_span is not None:
            fold_span.attributes.update(
                leaves=did.leaves, direct=did.direct, resident=did.resident
            )
        trace.finish(fold_span)
        attrs = span_attrs or {}
        log.info(
            "ps fold: round=%s peer=%s sign=%d bytes=%d leaves=%d direct=%d "
            "resident=%d read_s=%.3f accumulate_s=%.3f threads=%d",
            attrs.get("round", "-"), attrs.get("peer", "-"), sign, did.bytes,
            did.leaves, did.direct, did.resident, did.read_s,
            did.accumulate_s, did.threads,
        )

    async def _collect_round(
        self,
        consumer,
        job_id: str,
        allowed: set[str],
        num_workers: int,
        work_dir: Path,
        round_num: int,
        accum: "_RoundAccum | None" = None,
        dur: "DurablePS | None" = None,
        preloaded: dict[str, tuple[Path, float]] | None = None,
        preloaded_folded: bool = False,
        link: "LinkTable | None" = None,
        arrivals: "dict[str, float] | None" = None,
        ptrace: "_PsTrace | None" = None,
        spares: "list[Path] | None" = None,
    ) -> dict[str, tuple[Path, float]]:
        """Gather one pseudo-gradient per worker: peer -> (path, samples).

        ``preloaded`` seeds the round with journaled folds a recovered PS
        rebuilt; ``preloaded_folded`` says the caller's replayed
        accumulator already contains them (the bit-exact resume path) so
        only the missing workers are waited for. ``link`` feeds the
        measured-bandwidth table as each delta streams in; ``arrivals``
        (when given) records each peer's collect-start -> accepted lag.
        ``spares`` are the delta files the last round left
        (``_spool_deltas``; never a durable job's): each accepted push
        takes one to be saved over, while there are any.
        """
        t_open = asyncio.get_running_loop().time()
        received: dict[str, tuple[Path, float]] = dict(preloaded or {})
        # Tree-reduce cover info: entry key -> (prefolded, covered worker
        # peers). Journaled entries rebuild theirs from the fold records;
        # everything else covers its sender.
        covers: dict[str, tuple[bool, frozenset]] = {}
        if dur is not None:
            for f in dur.folds_for(round_num):
                covers[f.peer] = (f.prefold, frozenset(f.covers or (f.peer,)))
        if not preloaded_folded:
            for key, entry in received.items():
                await self._fold(
                    accum, entry,
                    prefolded=covers.get(key, (False, frozenset()))[0],
                )
        if arrivals is not None:
            # Journal-seeded folds landed before this collect: zero lag.
            for covered_peer in self._covered(received, covers):
                arrivals.setdefault(str(covered_peer), 0.0)
        dest_dir = dur.deltas_dir if dur is not None else work_dir
        while len(self._covered(received, covers)) < num_workers:
            push = await consumer.next()
            peer = push.peer
            if allowed and peer not in allowed:
                log.warning("ps %s: push from disallowed peer %s", job_id, peer)
                await push.read_all()
                continue
            if dur is not None:
                # Durable runs must be round-aware even in plain mode: a
                # recovered PS's resync makes EVERY worker re-send its last
                # delta, and ones for an already-committed round would
                # otherwise fold into — and instantly close — the resumed
                # round (their dedup key carries the OLD round, so the sha
                # guard alone cannot catch them). A worker can never run
                # AHEAD of the PS (broadcasts only follow commits), so
                # stale is the only tag to drop.
                delta_round = await self._classify_push(
                    push, job_id, None, round_num
                )
                if delta_round is None:
                    continue
            meta = push.resource if isinstance(push.resource, dict) else {}
            prefolded, cov = self._push_cover(meta, peer)
            key = self._entry_key(prefolded, peer)
            if prefolded:
                SHARD_METRICS.prefold_partials.add(1)
                if self._prefold_superseded(covers, cov, key):
                    log.info(
                        "ps %s: partial from %s contained in an accepted "
                        "ancestor partial; dropped", job_id, peer,
                    )
                    await push.read_all()
                    continue
            elif self._direct_covered(covers, peer):
                log.info(
                    "ps %s: delta from %s already covered by a tree-reduce "
                    "partial; dropped", job_id, peer,
                )
                await push.read_all()
                continue
            if dur is None and key in received:
                # Double-send guard (fixes reference TODO :215-218): a
                # re-send replaces the previous delta instead of
                # mis-counting the round. Non-durable saves land on the
                # SAME deterministic path, so the superseded entry must be
                # un-folded (reading its original bytes) BEFORE the save.
                log.warning("ps %s: duplicate delta from %s; replacing", job_id, peer)
                old = received.pop(key)
                await self._fold(accum, old, sign=-1.0, prefolded=prefolded)
                old[0].unlink(missing_ok=True)
            # Unique names on durable runs: the journal references each
            # accepted file by name, so a re-send must never overwrite the
            # bytes a journaled fold points at.
            hasher = hashlib.sha256() if dur is not None else None
            if ptrace is not None:
                ptrace.adopt_push(push, round_num)
            entry = await self._save_delta(
                push, dest_dir, round_num,
                name_suffix=(
                    f"-{uuid.uuid4().hex[:8]}" if dur is not None else ""
                ),
                hasher=hasher, name_key=key, link=link,
                trace_node=ptrace.node if ptrace is not None else None,
                over=spares.pop() if spares else None,
            )
            if arrivals is not None:
                lag = asyncio.get_running_loop().time() - t_open
                if prefolded:
                    for member in cov:
                        arrivals.setdefault(str(member), lag)
                else:
                    arrivals[peer] = lag
            if not await self._ingest(
                dur, round_num, 0, key, entry,
                sha=hasher.hexdigest() if hasher is not None else None,
                prefold=prefolded, covers=cov,
            ):
                log.info(
                    "ps %s: duplicate re-send from %s (journaled); dropped",
                    job_id, peer,
                )
                continue
            if key in received:
                # Durable path only (unique names): retire the superseded
                # entry after the save — its file still holds the original
                # bytes, so the un-fold is exact. The file itself STAYS on
                # disk: recovery's replay_ops re-reads it to reproduce this
                # very un-fold (checkpoint GC retires it later).
                log.warning("ps %s: duplicate delta from %s; replacing", job_id, peer)
                old = received.pop(key)
                await self._fold(accum, old, sign=-1.0, prefolded=prefolded)
            if prefolded and cov:
                await self._retire_covered(
                    job_id, accum, received, covers, cov,
                    durable=dur is not None,
                )
            received[key] = entry
            covers[key] = (prefolded, cov)
            await self._fold(
                accum, entry, prefolded=prefolded,
                span_attrs={"round": round_num, "peer": peer},
                parent=_PsTrace.push_ctx(push),
                trace_node=ptrace.node if ptrace is not None else None,
            )
            log.info(
                "ps %s: round %d delta %d/%d (from %s)",
                job_id, round_num, len(received), num_workers, peer,
            )
        return received

    async def _collect_round_elastic(
        self,
        consumer,
        job_id: str,
        st: _ElasticState,
        cfg,
        work_dir: Path,
        round_num: int,
        accum: "_RoundAccum | None" = None,
        dur: "DurablePS | None" = None,
        link: "LinkTable | None" = None,
        arrivals: "dict[str, float] | None" = None,
        ptrace: "_PsTrace | None" = None,
    ) -> dict[str, tuple[Path, float]]:
        """Quorum + deadline gather: peer -> (path, samples).

        Close conditions (both require ``len(received) >= quorum``):
          * every live active worker (active − suspected) has reported, or
          * ``round_deadline_s`` expired since the round's collect began.
        Deltas tagged with an old round number are dropped as stale; ones
        tagged with a future round are parked and pre-credited to it.
        A recovered PS seeds ``st.early`` with the journaled folds, so the
        interrupted round's deltas re-fold here instead of being re-waited.

        Adaptive extensions (ft.adaptive, both None on static jobs):
        ``link`` measures each accepted delta's bandwidth AND extends the
        deadline by its ``first_round_grace`` while any expected peer is
        still unmeasured — a peer must never be quorum-dropped before the
        table has seen one upload from it (nothing adaptive could have
        reacted yet). ``arrivals`` records per-peer collect->accept lags
        for the straggler controller; expected peers missing at close are
        counted as quorum drops (HET_METRICS).
        """
        received: dict[str, tuple[Path, float]] = dict(st.early.pop(round_num, {}))
        # Tree-reduce cover info: entry key -> (prefolded, covered workers).
        covers: dict[str, tuple[bool, frozenset]] = dict(
            st.early_covers.pop(round_num, {})
        )
        if dur is not None:
            for f in dur.folds_for(round_num):
                covers.setdefault(
                    f.peer, (f.prefold, frozenset(f.covers or (f.peer,)))
                )
        for key, (p, c) in list(covers.items()):
            # A recovery-seeded bucket is a last-wins table: it can hold
            # both a partial and a direct entry the live collector had
            # retired as covered — drop the directs before folding.
            if p and c:
                for member in sorted(c):
                    info = covers.get(member)
                    if member in received and not (info and info[0]):
                        received.pop(member)
                        covers.pop(member, None)
        for key, entry in received.items():
            # Parked early arrivals were never folded (their round hadn't
            # opened); fold them now that it has.
            await self._fold(
                accum, entry,
                prefolded=covers.get(key, (False, frozenset()))[0],
            )
        dest_dir = dur.deltas_dir if dur is not None else work_dir
        loop = asyncio.get_running_loop()
        t_open = loop.time()
        if arrivals is not None:
            # Early-parked deltas (and journal-seeded folds) landed before
            # this collect even opened: zero lag, emphatically not a drop.
            for covered_peer in self._covered(received, covers):
                arrivals.setdefault(str(covered_peer), 0.0)

        def deadline_at() -> float | None:
            if st.round_deadline_s <= 0:
                return None
            if link is not None and any(
                not link.measured(p) for p in st.membership.expected()
            ):
                # First-round grace: an expected peer the bandwidth table
                # has never seen must get one chance to land an upload
                # before the deadline can drop it.
                return t_open + st.round_deadline_s * link.first_round_grace
            return t_open + st.round_deadline_s

        deadline_logged = False
        while True:
            # A rejoiner announced mid-round starts contributing to THIS
            # round: serve its catch-up from inside the wait loop.
            await self._serve_joins(st, cfg, round_num, work_dir)
            deadline = deadline_at()
            covered = self._covered(received, covers)
            expected = st.membership.expected() | covered
            quorate = len(covered) >= st.quorum()
            if received and quorate and covered >= expected:
                break
            now = loop.time()
            if deadline is not None and now >= deadline:
                if quorate:
                    break
                if not deadline_logged:
                    deadline_logged = True
                    log.warning(
                        "ps %s: round %d deadline passed with %d/%d deltas; "
                        "waiting for quorum",
                        job_id, round_num, len(received), st.quorum(),
                    )
            timeout = _ELASTIC_TICK_S
            if deadline is not None and now < deadline:
                timeout = min(timeout, max(deadline - now, 0.05))
            try:
                push = await consumer.next(timeout=timeout)
            except asyncio.TimeoutError:
                continue
            peer = push.peer
            # Stale = the round it belongs to already aggregated (its
            # sender was past the deadline / partitioned); folding it into
            # the current mean would double-apply old progress.
            delta_round = await self._classify_push(
                push, job_id, st.membership.active, round_num
            )
            if delta_round is None:
                continue
            if ptrace is not None:
                ptrace.adopt_push(push, delta_round)
            meta = push.resource if isinstance(push.resource, dict) else {}
            prefolded, cov = self._push_cover(meta, peer)
            key = self._entry_key(prefolded, peer)
            cov_table = (
                covers
                if delta_round == round_num
                else st.early_covers.get(delta_round, {})
            )
            if prefolded:
                SHARD_METRICS.prefold_partials.add(1)
                if self._prefold_superseded(cov_table, cov, key):
                    log.info(
                        "ps %s: partial from %s contained in an accepted "
                        "ancestor partial; dropped", job_id, peer,
                    )
                    await push.read_all()
                    continue
            elif self._direct_covered(cov_table, peer):
                log.info(
                    "ps %s: delta from %s already covered by a tree-reduce "
                    "partial; dropped", job_id, peer,
                )
                await push.read_all()
                continue
            # ALWAYS save under a unique name, then retire any superseded
            # duplicate AFTER the save succeeds. Saving onto the old
            # deterministic path would truncate the already-folded
            # original the moment the drain starts — and a drain the
            # deadline then abandons (bounded_save) would have destroyed
            # a contribution the round actually had. Durable runs need
            # the unique names anyway (the journal references files by
            # name).
            suffix = f"-{uuid.uuid4().hex[:8]}"
            hasher = hashlib.sha256() if dur is not None else None
            # The drain bound applies only once the round is already
            # QUORATE: abandoning a surplus straggler's slow transfer
            # merely trims it, but a quorum-REQUIRED delta must drain to
            # completion however slow its link — abandoning it would
            # starve the round of the very delta its close is waiting
            # for (every retry would get an ever-smaller budget).
            drain_deadline = (
                deadline_at()
                if len(self._covered(received, covers)) >= st.quorum()
                else None
            )
            if delta_round > round_num:
                # Early: a fast worker already merged this round's broadcast
                # and shipped the next pseudo-gradient; credit it forward.
                bucket = st.early.setdefault(delta_round, {})
                entry = await self._save_delta_bounded(
                    push, dest_dir, delta_round, suffix=suffix,
                    hasher=hasher, key=key, link=link,
                    deadline=drain_deadline, job_id=job_id,
                )
                if entry is None:
                    continue
                if not await self._ingest(
                    dur, delta_round, 0, key, entry,
                    sha=hasher.hexdigest() if hasher is not None else None,
                    prefold=prefolded, covers=cov,
                ):
                    continue
                # Superseded durable files stay for replay_ops (GC'd at
                # checkpoint); a non-durable original is retired now that
                # its replacement fully landed.
                old = bucket.pop(key, None)
                if old is not None and dur is None:
                    old[0].unlink(missing_ok=True)
                early_cov = st.early_covers.setdefault(delta_round, {})
                if prefolded and cov:
                    # Nothing in a parked bucket has folded yet, so the
                    # covered directs just leave the table (accum=None).
                    await self._retire_covered(
                        job_id, None, bucket, early_cov, cov,
                        durable=dur is not None,
                    )
                bucket[key] = entry
                early_cov[key] = (prefolded, cov)
                continue
            entry = await self._save_delta_bounded(
                push, dest_dir, delta_round, suffix=suffix,
                hasher=hasher, key=key, link=link,
                deadline=drain_deadline, job_id=job_id,
            )
            if entry is None:
                continue
            if arrivals is not None:
                lag = loop.time() - t_open
                if prefolded:
                    # A tree-reduce partial carries its whole group: every
                    # covered member arrived (inside the partial) at this
                    # lag — without this, the straggler controller would
                    # perpetually drop-penalize healthy reduced workers.
                    for member in cov:
                        arrivals.setdefault(str(member), lag)
                else:
                    arrivals[peer] = lag
            if not await self._ingest(
                dur, delta_round, 0, key, entry,
                sha=hasher.hexdigest() if hasher is not None else None,
                prefold=prefolded, covers=cov,
            ):
                log.info(
                    "ps %s: duplicate re-send from %s (journaled); dropped",
                    job_id, peer,
                )
                continue
            old = received.pop(key, None)
            if old is not None:
                # Retire the superseded entry only AFTER its replacement
                # fully landed (unique names — the un-fold reads the
                # original bytes either way). Durable files stay on disk
                # for recovery's replay_ops (checkpoint GC).
                log.warning(
                    "ps %s: duplicate delta from %s; replacing", job_id, peer
                )
                await self._fold(accum, old, sign=-1.0, prefolded=prefolded)
                if dur is None:
                    old[0].unlink(missing_ok=True)
            if prefolded and cov:
                await self._retire_covered(
                    job_id, accum, received, covers, cov,
                    durable=dur is not None,
                )
            received[key] = entry
            covers[key] = (prefolded, cov)
            await self._fold(
                accum, entry, prefolded=prefolded,
                span_attrs={"round": round_num, "peer": peer},
                parent=_PsTrace.push_ctx(push),
                trace_node=ptrace.node if ptrace is not None else None,
            )
            log.info(
                "ps %s: round %d delta %d (quorum %d, active %d) from %s",
                job_id, round_num, len(received), st.quorum(),
                len(st.membership.active), peer,
            )
        # Degraded = fewer covered WORKERS than the job bought replicas (a
        # departed worker that was never replaced keeps every round
        # degraded, even though the shrunken active set reported "in full").
        covered = self._covered(received, covers)
        full = max(cfg.num_workers, len(st.membership.active))
        if len(covered) < full:
            FT_METRICS.degraded_rounds.add(1)
            log.warning(
                "ps %s: round %d DEGRADED — aggregating %d of %d",
                job_id, round_num, len(received), full,
            )
        # Quorum drops: expected (live active) workers whose delta missed
        # the close — wasted straggler compute, the count the adaptive
        # controller exists to drive to zero.
        dropped = st.membership.expected() - covered
        if dropped:
            HET_METRICS.note_quorum_drop(round_num, sorted(dropped))
        return received

    # ------------------------------------------------------- streaming sync

    async def _stream_rounds(
        self,
        execution,
        job_id: str,
        cfg,
        scheduler_peer: str,
        work_dir: Path,
        consumer,
        elastic: "_ElasticState | None",
        allowed: set[str],
        num_workers: int,
        momentum: _OuterMomentum,
        ckpt_dir: Path | None,
        lr: float,
        mu: float,
        bcast_codec: str,
        fragments: int,
        dur: "DurablePS | None" = None,
        round_start: int = 0,
        init_accums: dict[int, "_RoundAccum"] | None = None,
        init_pending: dict[int, dict[str, tuple[Path, float]]] | None = None,
        init_efs: dict[int, "compress.ErrorFeedback | None"] | None = None,
        shard: int = 0,
        num_shards: int = 1,
        sync_mode: str = "stream",
    ) -> None:
        """The pipelined round loop for ``sync_mode: overlap | stream``.

        Differences from the blocking loop above:

          * deltas fold into PER-ROUND accumulators keyed by their
            ``FragmentTag`` the moment they land — a delta for a round
            that has not opened yet (its sender merged the previous
            broadcast before a straggler shipped) folds into that round's
            own accumulator instead of parking un-aggregated;
          * the broadcast fan-out runs as a BACKGROUND task: the loop
            proceeds to collecting the next round's fragment while the
            previous update is still streaming to slow peers, so one slow
            link no longer gates every round. Fan-outs of the SAME
            fragment are chained (round r+F waits for round r) so a
            worker can never receive them out of round order; different
            fragments overlap freely, and total in-flight fan-outs are
            capped at the fragment count as memory backpressure;
          * the rejoin catch-up accumulates at round-close time, in round
            order, so θ₀ + Σ stays exact even when fragment broadcasts
            complete out of order (CatchupBuffer's fragment-wise argument).

        Error feedback is per fragment on the broadcast side: one shared
        residual would be clobbered by the next fragment's absorb.

        Sharded runs (``num_shards > 1``) reuse this loop for EVERY sync
        mode: in stream mode the shard iterates only the rounds whose due
        fragment it owns (the other shards close the rest concurrently);
        in blocking mode its due part is fixed at ``shard_index`` and
        every round is owned. Broadcast and notify headers then carry
        ``SHARD_KEY`` so workers track generations per shard.
        """
        accums: dict[int, _RoundAccum] = dict(init_accums or {})
        pending: dict[int, dict[str, tuple[Path, float]]] = dict(
            init_pending or {}
        )
        pending_covers: dict[int, dict[str, tuple[bool, frozenset]]] = {}
        bcast_efs: dict[int, "compress.ErrorFeedback | None"] = dict(
            init_efs or {}
        )
        adaptive_steps = bool(getattr(cfg, "adaptive_steps", False))
        bcast_tasks: set[asyncio.Task] = set()
        last_bcast: dict[int, asyncio.Task] = {}  # fragment -> newest fan-out
        quant = bcast_codec in compress.QUANT_CODECS
        sharded = num_shards > 1

        def due_fn(r: int) -> int:
            # Stream: the staggered schedule (fragment r mod F). Sharded
            # blocking: this shard's fixed part, every round.
            if sharded and sync_mode != "stream":
                return shard
            return fragment_due(r, fragments)

        def next_owned(r: int) -> int:
            if not sharded or sync_mode != "stream":
                return r
            return next_owned_round(sync_mode, r, fragments, num_shards, shard)

        round_num = next_owned(round_start)
        ptrace = _PsTrace(self._trace_node())
        park_s = float(getattr(cfg, "adopt_grace_s", 0) or 0)
        try:
            while True:
                # Live progress for the AdoptAck handshake.
                execution.round = round_num
                if elastic is not None:
                    execution.epoch = elastic.membership.epoch
                if dur is not None:
                    await asyncio.to_thread(dur.note_open, round_num)
                arrivals: dict[str, float] | None = (
                    {} if adaptive_steps else None
                )
                qw_span = trace.begin(
                    "quorum_wait", parent=ptrace.ctx(round_num),
                    attrs={"round": round_num, "fragment": due_fn(round_num)},
                    node=ptrace.node,
                )
                received = await self._collect_round_stream(
                    consumer, job_id, cfg, elastic, allowed, num_workers,
                    work_dir, round_num, fragments, accums, pending,
                    dur=dur, due_fn=due_fn, pending_covers=pending_covers,
                    sharded=sharded, arrivals=arrivals,
                    owned_fn=(
                        (lambda r: shard_owns_round(
                            sync_mode, r, fragments, num_shards, shard
                        ))
                        if sharded and sync_mode == "stream"
                        else None
                    ),
                    ptrace=ptrace, sums=momentum.sums,
                )
                trace.reparent(qw_span, ptrace.ctx(round_num))
                trace.finish(qw_span)
                if dur is not None:
                    await asyncio.to_thread(
                        dur.note_close, round_num, list(received)
                    )
                frag = due_fn(round_num)
                tag = FragmentTag(
                    round=round_num, fragment_id=frag, fragments=fragments
                )
                accum = accums.pop(round_num, None)
                outer_span = trace.begin(
                    "outer_step", parent=ptrace.ctx(round_num),
                    attrs={"round": round_num, "fragment": frag},
                    node=ptrace.node,
                )
                quality = (
                    {"fragment": float(frag)}
                    if getattr(cfg, "report_metrics_s", None)
                    else None
                )
                update = await asyncio.to_thread(
                    self._outer_step,
                    received, momentum, lr, mu, work_dir, round_num,
                    accum, quality, outer_span,
                    file=dur is not None or bcast_codec != "none",
                )
                trace.finish(outer_span)
                if frag not in bcast_efs:
                    bcast_efs[frag] = (
                        compress.ErrorFeedback() if quant else None
                    )
                wire, sent_update = await asyncio.to_thread(
                    self._encode_broadcast,
                    update, bcast_codec, bcast_efs[frag], work_dir,
                    round_num, tag.header(),
                )
                if elastic is not None:
                    # Catch-up accumulation at CLOSE time, in close order —
                    # never from the background broadcast, whose completion
                    # order is unordered across fragments. Before the
                    # durable commit, whose checkpoint must contain it.
                    await asyncio.to_thread(
                        elastic.catchup.accumulate_tree,
                        update.tree if sent_update is None else sent_update,
                        frag,
                    )
                if dur is not None:
                    wire_name = await asyncio.to_thread(
                        dur.store_wire, round_num, _wire_file(wire)
                    )
                    await asyncio.to_thread(
                        dur.commit_round, round_num, frag, wire_name,
                        epoch=(
                            elastic.membership.epoch
                            if elastic is not None else 0
                        ),
                        momentum_file=momentum.file,
                        catchup=(
                            elastic.catchup if elastic is not None else None
                        ),
                        efs=bcast_efs,
                        active=(
                            list(elastic.membership.active)
                            if elastic is not None else []
                        ),
                    )
                if ckpt_dir is not None:
                    self._checkpoint_momentum(momentum.file, ckpt_dir)
                # Freeze the fan-out's peer set at CLOSE time: the
                # backgrounded push must not pick up a rejoiner who joins
                # while it is pending — that peer's catch-up (served
                # below) already folds this round, and the blocking loop's
                # "a rejoiner never sees an update it must skip" invariant
                # should survive the pipelining. (The worker additionally
                # stale-drops by round tag, as defense in depth.)
                bcast_peers = (
                    list(elastic.membership.active)
                    if elastic is not None
                    else None
                )
                bcast_header = dict(tag.header())
                if dur is not None:
                    bcast_header[GENERATION_KEY] = dur.generation
                if sharded:
                    bcast_header[SHARD_KEY] = shard
                async def _spawn_bcast(
                    _u=update, _w=wire, _rcv=received,
                    _r=round_num, _tag=tag, _frag=frag,
                    _peers=bcast_peers, _hdr=bcast_header,
                ) -> None:
                    last_bcast[_frag] = aio.spawn(
                        self._broadcast_and_cleanup(
                            cfg, _u, _w, _rcv, _r, _tag, elastic,
                            # Per-fragment ordering barrier: round r+F's
                            # fan-out for fragment p waits for round r's
                            # (see _broadcast_and_cleanup).
                            after=last_bcast.get(_frag),
                            peers=_peers,
                            header=_hdr,
                            # Durable runs keep the delta files — the
                            # journal references them until a checkpoint
                            # covers them.
                            keep_received=dur is not None,
                            traceparent=ptrace.ctx(_r),
                        ),
                        tasks=bcast_tasks,
                        what=f"stream broadcast r{_r}",
                        logger=log,
                    )

                launch_bcast = _fire_once(_spawn_bcast)

                # Notify BEFORE broadcasting (same race note as the
                # blocking loop: the scheduler must have advanced the
                # round before any worker's UpdateReceived) — except
                # across a scheduler outage, where the first failed
                # attempt launches the fan-out so the quorate round
                # closes without the scheduler.
                response = await self._notify_updated_resilient(
                    scheduler_peer, job_id, round_num, shard=shard,
                    arrivals=arrivals,
                    traceparent=ptrace.ctx(round_num),
                    execution=execution, park_s=park_s,
                    on_first_failure=launch_bcast,
                    quality=quality,
                )
                ptrace.adopt(response, next_owned(round_num + 1))
                if dur is not None:
                    await asyncio.to_thread(
                        dur.note_notified, round_num,
                        response.kind == ProgressResponseKind.DONE,
                    )
                await launch_bcast()
                STREAM_METRICS.fragment_closed(frag)
                if sharded:
                    SHARD_METRICS.shard_rounds_closed.add(1)
                round_num = next_owned(round_num + 1)
                if elastic is not None:
                    await self._serve_joins(elastic, cfg, round_num, work_dir)
                # Memory backpressure only (ordering is the chain above):
                # bound the round files held by un-finished fan-outs to
                # roughly one cycle of fragments.
                live = [t for t in bcast_tasks if not t.done()]
                if len(live) >= max(fragments, 1) + 1:
                    await asyncio.wait(
                        live, return_when=asyncio.FIRST_COMPLETED
                    )
                if response.kind == ProgressResponseKind.DONE:
                    # The final update must still reach the workers — their
                    # DONE comes with the UpdateReceived it triggers.
                    await aio.wait_quiet(*bcast_tasks, timeout=60.0)
                    execution.finish("completed")
                    return
        finally:
            await aio.reap(*bcast_tasks)

    async def _collect_round_stream(
        self,
        consumer,
        job_id: str,
        cfg,
        st: "_ElasticState | None",
        allowed: set[str],
        num_workers: int,
        work_dir: Path,
        round_num: int,
        fragments: int,
        accums: dict[int, "_RoundAccum"],
        pending: dict[int, dict[str, tuple[Path, float]]],
        dur: "DurablePS | None" = None,
        due_fn=None,
        pending_covers: "dict | None" = None,
        owned_fn=None,
        sharded: bool = False,
        arrivals: "dict[str, float] | None" = None,
        ptrace: "_PsTrace | None" = None,
        sums: "SumBuffers | None" = None,
    ) -> dict[str, tuple[Path, float]]:
        """Gather one round's FRAGMENT deltas: peer -> (path, samples).

        Every arriving delta folds into the accumulator of the round its
        ``FragmentTag`` names — the current round or a future one (whose
        collect hasn't opened yet) — so aggregation work always overlaps
        the wire. Close conditions match the non-stream paths: all of
        ``num_workers`` COVERED (plain — a tree-reduce partial covers its
        group), or quorum+deadline (elastic). ``due_fn`` maps a round to
        its due part (default: the staggered stream schedule; a sharded
        blocking run fixes it at the shard index).
        """
        if due_fn is None:
            def due_fn(r: int) -> int:
                return fragment_due(r, fragments)
        if pending_covers is None:
            pending_covers = {}
        received = pending.pop(round_num, {})
        covers: dict[str, tuple[bool, frozenset]] = pending_covers.pop(
            round_num, {}
        )
        if dur is not None:
            for f in dur.folds_for(round_num):
                covers.setdefault(
                    f.peer, (f.prefold, frozenset(f.covers or (f.peer,)))
                )
        frag = due_fn(round_num)
        dest_dir = dur.deltas_dir if dur is not None else work_dir
        loop = asyncio.get_running_loop()
        t_open = loop.time()
        if arrivals is not None:
            # Deltas parked while earlier rounds collected (fast workers
            # ran ahead) landed before this collect opened: zero lag.
            for covered_peer in self._covered(received, covers):
                arrivals.setdefault(str(covered_peer), 0.0)
        deadline = None
        if st is not None and st.round_deadline_s > 0:
            deadline = loop.time() + st.round_deadline_s
        deadline_logged = False
        while True:
            if st is not None:
                await self._serve_joins(st, cfg, round_num, work_dir)
                covered = self._covered(received, covers)
                expected = st.membership.expected() | covered
                quorate = len(covered) >= st.quorum()
                if received and quorate and covered >= expected:
                    break
                now = loop.time()
                if deadline is not None and now >= deadline:
                    if quorate:
                        break
                    if not deadline_logged:
                        deadline_logged = True
                        log.warning(
                            "ps %s: round %d (fragment %d) deadline passed "
                            "with %d/%d deltas; waiting for quorum",
                            job_id, round_num, frag, len(received),
                            st.quorum(),
                        )
                timeout = _ELASTIC_TICK_S
                if deadline is not None and now < deadline:
                    timeout = min(timeout, max(deadline - now, 0.05))
            else:
                if len(self._covered(received, covers)) >= num_workers:
                    break
                timeout = None
            try:
                push = await consumer.next(timeout=timeout)
            except asyncio.TimeoutError:
                continue
            peer = push.peer
            members = (
                st.membership.active
                if st is not None
                else (allowed or None)  # empty allowlist = unrestricted
            )
            delta_round = await self._classify_push(
                push, job_id, members, round_num
            )
            if delta_round is None:
                continue
            if ptrace is not None:
                ptrace.adopt_push(push, delta_round)
            if owned_fn is not None and not owned_fn(delta_round):
                # Mis-routed: this round's due fragment belongs to another
                # shard — parking it here would leak it forever (this shard
                # never opens that round).
                SHARD_METRICS.misrouted_pushes.add(1)
                log.warning(
                    "ps %s: push for round %d from %s is another shard's; "
                    "dropped", job_id, delta_round, peer,
                )
                await push.read_all()
                continue
            meta = push.resource if isinstance(push.resource, dict) else {}
            prefolded, cov = self._push_cover(meta, peer)
            key = self._entry_key(prefolded, peer)
            if prefolded:
                SHARD_METRICS.prefold_partials.add(1)
            tag = FragmentTag.from_header(push.resource)
            if tag is not None and (
                tag.fragments != fragments
                or tag.fragment_id != due_fn(delta_round)
            ):
                # A mis-partitioned (or mis-ROUTED — another shard's part)
                # sender would fold the wrong tensors into the mean — drop
                # loudly rather than corrupt a round. On a sharded run this
                # IS the stale-placement signal (in blocking mode there is
                # no owned_fn path — every round is owned — so the metric
                # must fire here too).
                if sharded:
                    SHARD_METRICS.misrouted_pushes.add(1)
                log.warning(
                    "ps %s: fragment tag mismatch from %s "
                    "(round %d fragment %d/%d, expected %d/%d); dropped",
                    job_id, peer, delta_round, tag.fragment_id,
                    tag.fragments, due_fn(delta_round),
                    fragments,
                )
                await push.read_all()
                continue
            accum = accums.get(delta_round)
            if accum is None:
                accum = accums[delta_round] = _RoundAccum(sums)
            bucket = (
                received
                if delta_round == round_num
                else pending.setdefault(delta_round, {})
            )
            cov_table = (
                covers
                if delta_round == round_num
                else pending_covers.setdefault(delta_round, {})
            )
            if prefolded and self._prefold_superseded(cov_table, cov, key):
                log.info(
                    "ps %s: partial from %s contained in an accepted "
                    "ancestor partial; dropped", job_id, peer,
                )
                await push.read_all()
                continue
            if not prefolded and self._direct_covered(cov_table, peer):
                log.info(
                    "ps %s: delta from %s already covered by a tree-reduce "
                    "partial; dropped", job_id, peer,
                )
                await push.read_all()
                continue
            # Save under a UNIQUE name, then validate, then retire any
            # duplicate: validating first means a corrupt/relabeled
            # re-send can never destroy the peer's already-folded good
            # delta (retiring before save — the elastic path's rule — is
            # only safe because that path has no post-save validation).
            hasher = hashlib.sha256() if dur is not None else None
            suffix = f"-{uuid.uuid4().hex[:8]}"
            # Drain bound only once quorate (see the elastic collector):
            # a quorum-required delta must drain however slow its link.
            drain_deadline = None
            if st is not None and deadline is not None and (
                len(self._covered(received, covers)) >= st.quorum()
            ):
                drain_deadline = deadline
            entry = await self._save_delta_bounded(
                push, dest_dir, delta_round, suffix=suffix,
                hasher=hasher, key=key, deadline=drain_deadline,
                job_id=job_id,
            )
            if entry is None:
                continue
            if tag is not None and not await asyncio.to_thread(
                self._frame_tag_matches, entry[0], tag
            ):
                # The sender's push header and what it baked into the HQD1
                # frame disagree — a relabeled/replayed file. Trust neither.
                log.warning(
                    "ps %s: frame tag mismatch from %s (header %s); dropped",
                    job_id, peer, tag,
                )
                entry[0].unlink(missing_ok=True)
                continue
            if not await self._ingest(
                dur, delta_round, due_fn(delta_round),
                key, entry,
                sha=hasher.hexdigest() if hasher is not None else None,
                prefold=prefolded, covers=cov,
            ):
                log.info(
                    "ps %s: duplicate re-send from %s (journaled); dropped",
                    job_id, peer,
                )
                continue
            old = bucket.pop(key, None)
            if old is not None:
                log.warning(
                    "ps %s: duplicate delta from %s; replacing", job_id, peer
                )
                await self._fold(accum, old, sign=-1.0, prefolded=prefolded)
                if dur is None:
                    # Durable files stay for replay_ops (checkpoint GC).
                    old[0].unlink(missing_ok=True)
            if prefolded and cov:
                await self._retire_covered(
                    job_id, accum, bucket, cov_table, cov,
                    durable=dur is not None,
                )
            bucket[key] = entry
            cov_table[key] = (prefolded, cov)
            if arrivals is not None and delta_round == round_num:
                lag = loop.time() - t_open
                if prefolded:
                    for member in cov:
                        arrivals.setdefault(str(member), lag)
                else:
                    arrivals[peer] = lag
            await self._fold(
                accum, entry, prefolded=prefolded,
                span_attrs={
                    "round": delta_round, "peer": peer,
                    "fragment": due_fn(delta_round),
                },
                parent=_PsTrace.push_ctx(push),
                trace_node=ptrace.node if ptrace is not None else None,
            )
            log.info(
                "ps %s: round %d fragment %d delta %d (from %s%s)",
                job_id, round_num, frag,
                len(received), peer,
                "" if delta_round == round_num else f", parked r{delta_round}",
            )
        if st is not None:
            covered = self._covered(received, covers)
            full = max(cfg.num_workers, len(st.membership.active))
            if len(covered) < full:
                FT_METRICS.degraded_rounds.add(1)
                log.warning(
                    "ps %s: round %d DEGRADED — aggregating %d of %d",
                    job_id, round_num, len(received), full,
                )
            dropped = st.membership.expected() - covered
            if dropped:
                HET_METRICS.note_quorum_drop(round_num, sorted(dropped))
        return received

    @staticmethod
    def _frame_tag_matches(path: Path, tag: FragmentTag) -> bool:
        """Cross-check an HQD1 frame's baked-in tag against the push
        header's (runs off-loop). Untagged frames (SafeTensors codecs,
        pre-tag senders) pass — the header is then the only identity."""
        baked = compress.frame_tag(path)
        if baked is None:
            return True
        try:
            return (
                int(baked.get("round", tag.round)) == tag.round
                and int(baked.get("fragment_id", tag.fragment_id))
                == tag.fragment_id
            )
        except (TypeError, ValueError):
            return False

    async def _broadcast_and_cleanup(
        self,
        cfg,
        update: _Update,
        wire: "Path | _Update",
        received: dict[str, tuple[Path, float]],
        round_num: int,
        tag: FragmentTag,
        elastic: "_ElasticState | None",
        after: "asyncio.Task | None" = None,
        peers: list[str] | None = None,
        header: dict | None = None,
        keep_received: bool = False,
        traceparent: str | None = None,
    ) -> None:
        """One round's backgrounded fan-out, after which the update's
        buffers go back to the sums and the round's files are retired.

        ``after`` chains this fan-out behind the SAME fragment's previous
        broadcast: without the barrier, a slow peer link could deliver
        round r+F's update for fragment p before round r's, and the
        worker would merge the newer one and drop the older as stale —
        silently losing an outer update. Different fragments still fan
        out concurrently (disjoint tensors, the worker absorbs them in
        any order). ``peers`` is the membership frozen at round close.
        ``keep_received`` leaves the delta files to the durable journal's
        checkpoint GC instead of retiring them here."""
        if after is not None:
            await aio.wait_quiet(after)
        try:
            await self._broadcast(
                cfg, wire, round_num, elastic,
                extra_header=header if header is not None else tag.header(),
                peers_override=peers,
                traceparent=traceparent,
                span_round=round_num,
            )
        finally:
            if not keep_received:
                for path, _ in received.values():
                    path.unlink(missing_ok=True)
            update.retire()
            if wire is not update:
                wire.unlink(missing_ok=True)

    @staticmethod
    def _spool_deltas(
        received: dict[str, tuple[Path, float]],
        work_dir: Path,
        spares: "list[Path] | None",
    ) -> None:
        """A closed round's delta files, done with: kept as spares for the
        next round's pushes to be saved over, or unlinked.

        A parameter-sized write into a fresh file costs page faults at
        about 1 GB/s on the chip's host, and one over pages that exist a
        fraction of that, so where ``spares`` is a list (a plain blocking
        job without a journal) the files move into ``work_dir/spare`` and
        ``_collect_round`` hands them out one a push. The spool never
        holds more files than this round received: what is over, and
        everything where ``spares`` is None, is unlinked as before. It
        goes with ``work_dir`` when the job ends.
        """
        spool = work_dir / "spare"
        for path, _ in received.values():
            if spares is not None and len(spares) < len(received):
                try:
                    spool.mkdir(exist_ok=True)
                    spare = spool / path.name
                    os.replace(path, spare)
                    spares.append(spare)
                    continue
                except OSError:
                    pass
            path.unlink(missing_ok=True)

    async def _save_delta_bounded(
        self, push, dest_dir: Path, delta_round: int, *,
        suffix: str, hasher, key: str,
        link: "LinkTable | None" = None,
        deadline: "float | None" = None,
        job_id: str = "",
    ) -> "tuple[Path, float] | None":
        """Save one delta with the DRAIN bounded by the round deadline.

        A push is queued the moment its header frame lands; the payload
        may still be streaming for many seconds on a bandwidth-starved
        link. Without a bound, one such drain holds the round open past
        the deadline for every peer (the close condition is only
        re-checked between accepts) — the exact straggler pathology the
        deadline exists to cut off. An abandoned drain counts as the
        round's quorum drop at close; the sender's retry path re-ships
        it and the stale guard (or the next round's collect) disposes of
        the copy. Returns None when abandoned.
        """
        trace_node = self._trace_node()
        if deadline is None:
            return await self._save_delta(
                push, dest_dir, delta_round, name_suffix=suffix,
                hasher=hasher, name_key=key, link=link,
                trace_node=trace_node,
            )
        loop = asyncio.get_running_loop()
        budget = max(deadline - loop.time(), 0.0) + _DRAIN_SLACK_S
        try:
            return await asyncio.wait_for(
                self._save_delta(
                    push, dest_dir, delta_round, name_suffix=suffix,
                    hasher=hasher, name_key=key, link=link,
                    trace_node=trace_node,
                ),
                timeout=budget,
            )
        except asyncio.TimeoutError:
            log.warning(
                "ps %s: delta drain from %s for round %d abandoned "
                "after %.1fs (deadline passed mid-transfer)",
                job_id, push.peer, delta_round, budget,
            )
            FLIGHT.record(
                "ps.drain_abandoned", node=trace_node, peer=push.peer,
                round=delta_round, budget_s=round(budget, 3), job=job_id,
            )
            push.finish()
            name = hashlib.sha256(
                (key or push.peer).encode()
            ).hexdigest()[:24]
            partial = (
                dest_dir / f"delta-{delta_round}-{name}{suffix}.safetensors"
            )
            if link is not None:
                # The abandoned drain IS a measurement: ``drained`` bytes
                # in ``budget`` seconds bounds the link from above.
                # Without it a link too slow to EVER finish inside the
                # grace window would stay unmeasured forever — the grace
                # would extend every round's deadline and the codec
                # ladder would never engage.
                try:
                    drained = partial.stat().st_size
                except OSError:
                    drained = 0
                link.observe(push.peer, max(drained, 1), budget)
            partial.unlink(missing_ok=True)
            return None

    @staticmethod
    async def _save_delta(
        push, work_dir: Path, round_num: int, name_suffix: str = "",
        hasher=None, name_key: "str | None" = None,
        link: "LinkTable | None" = None,
        trace_node: "str | None" = None,
        over: "Path | None" = None,
    ) -> tuple[Path, float]:
        """Save one pseudo-gradient push; returns (path, sample weight).

        ``name_suffix`` de-collides re-sends for callers that validate
        after saving (the streaming collector) — without it a duplicate
        lands on the SAME deterministic path as the entry it supersedes.
        ``hasher`` is updated with the payload as it streams to disk
        (durable runs journal the sha — hashing inline avoids a second
        parameter-sized read of the file just written). ``name_key``
        overrides the peer id in the deterministic name — a reducer's
        forwarded partial must not land on the same path as the reducer's
        own direct delta. ``link`` (ft.adaptive) times the save — the
        push streams the payload, so the wall-clock of draining it to
        disk IS the link — and feeds the per-peer bandwidth EWMA the
        codec ladder keys on. ``over`` is a spare the job's spool kept
        (``_spool_deltas``): the push is saved over it and not into a
        fresh file (``PushStream.save_to``). What the save did (``pages``:
        ``recycled`` or ``fresh``; ``read_s`` receiving, ``write_s``
        writing) goes on the span and on the ``ps upload:`` line.
        """
        name = hashlib.sha256((name_key or push.peer).encode()).hexdigest()[:24]
        dest = work_dir / f"delta-{round_num}-{name}{name_suffix}.safetensors"
        # The receiver-side ``upload`` span: header arrival → payload
        # drained, i.e. the sender's LINK — the span the timeline's
        # straggler attribution keys on (the sender names itself in
        # ``peer``, its header carries the round's trace context).
        up_span = trace.begin(
            "upload",
            parent=_PsTrace.push_ctx(push),
            attrs={"round": round_num, "peer": push.peer},
            node=trace_node, usage=True,
        )
        t0 = time.monotonic()
        if over is None:
            # As every other collector calls it (and every stand-in for a
            # push that a test hands them).
            nbytes = await push.save_to(dest, hasher=hasher)
        else:
            nbytes = await push.save_to(dest, hasher=hasher, over=over)
        wall_s = time.monotonic() - t0
        try:
            size = int(nbytes) if nbytes else dest.stat().st_size
        except (TypeError, ValueError, OSError):
            size = 0
        pages = "recycled" if getattr(push, "recycled", False) else "fresh"
        read_s = float(getattr(push, "read_s", 0.0))
        write_s = float(getattr(push, "write_s", 0.0))
        if up_span is not None:
            up_span.attributes.update(
                bytes=size, pages=pages,
                read_s=round(read_s, 6), write_s=round(write_s, 6),
            )
        trace.finish(up_span)
        log.info(
            "ps upload: round=%s peer=%s bytes=%d pages=%s wall_s=%.3f "
            "read_s=%.3f write_s=%.3f",
            round_num, push.peer, size, pages, wall_s, read_s, write_s,
        )
        if link is not None and size > 0:
            link.observe(push.peer, size, wall_s)
        samples = 1.0
        if isinstance(push.resource, dict):
            try:
                samples = float(push.resource.get("num_samples", 1.0))
            except (TypeError, ValueError):
                samples = 1.0
            if not np.isfinite(samples) or samples <= 0:
                samples = 1.0
        return dest, samples

    async def _serve_joins(
        self, st: _ElasticState, cfg, round_num: int, work_dir: Path
    ) -> None:
        """Push the cumulative-update catch-up to newly joined peers."""
        pending = [p for p, n in st.pending_joins.items() if n > 0]
        if not pending:
            return
        # One serialization per call: the cumulative sum only changes at
        # accumulate() (once per round), not per rejoiner or retry tick —
        # re-writing the parameter-sized file per peer was pure waste.
        path = st.catchup.write(work_dir / "catchup.safetensors")
        for peer in pending:
            header = {
                "resource": cfg.results.ref.resource or "results",
                "name": f"catchup-{round_num}.safetensors",
                "round": round_num,
                "epoch": st.membership.epoch,
                CATCHUP_KEY: True,
            }
            if st.num_shards > 1:
                # A sharded job's rejoiner needs one catch-up PER shard
                # (each covers only its own fragments' Σ).
                header[SHARD_KEY] = st.shard
            if st.dur is not None:
                header[GENERATION_KEY] = st.dur.generation
            try:
                # A couple of backed-off tries per tick: a rejoiner's node
                # may still be binding its listener when the join lands.
                await aio.retry(
                    lambda p=peer: self.node.push(p, header, path),
                    attempts=2, base_delay=0.2,
                    attempt_timeout=push_timeout(path, base=30.0),
                    retry_on=(RequestError, OSError),
                    what=f"catch-up to {peer}", logger=log,
                )
            except (RequestError, OSError, asyncio.TimeoutError) as e:
                st.pending_joins[peer] -= 1
                if st.pending_joins[peer] <= 0:
                    log.error("ps: catch-up to %s failed for good: %s", peer, e)
                    del st.pending_joins[peer]
                continue
            del st.pending_joins[peer]
            FLIGHT.record(
                "ft.catchup_served", node=self._trace_node(), peer=peer,
                round=round_num, rounds=st.catchup.rounds,
            )
            log.info(
                "ps: served catch-up (%d rounds, next %d) to rejoiner %s",
                st.catchup.rounds, round_num, peer,
            )

    def _outer_step(
        self,
        received: dict[str, tuple[Path, float]],
        momentum: _OuterMomentum,
        lr: float,
        mu: float,
        work_dir: Path,
        round_num: int,
        accum: "_RoundAccum | None" = None,
        stats: dict | None = None,
        parent: "trace.TraceSpan | None" = None,
        file: bool = False,
    ) -> _Update:
        """Nesterov over the round's sample-weighted mean pseudo-gradient.

        The streaming path hands in an accumulator that already folded
        every delta as it arrived. Its sum is taken (the accumulator is
        left empty) and one fused pass a leaf divides it by Σw and runs the
        Nesterov recurrence IN PLACE: the momentum is updated where it
        lies in ``momentum`` (resident for the job, only this round's keys
        touched, so a fragment round costs its fragment) and the update is
        written over the sum — nothing parameter-sized is allocated here,
        and nothing parameter-sized is written: the update is returned
        where it lies (:class:`_Update`), framed for the broadcast to push
        from, and is a file only where the caller knows of a reader that
        needs one (``file``: a durable job, a wire codec, per-link codecs)
        or a later one asks (``ensure_file``). The caller owns the buffers
        until it ``retire``s the update.
        C++ kernel over the leaf's elements in threads
        (native.fused_mean_nesterov), numpy fallback. Callers without an
        accumulator (tests, the degenerate path) fold the received files
        now, with the same validation.

        Momentum is durable exactly when the job has a ``checkpoint_dir``
        (``momentum.save``): then its file is written here, before the
        caller's durable commit, notify and broadcast; otherwise nobody
        would read it and it is not written.

        ``stats`` (metrics plane, None = skip the extra flops) is filled
        with the round's training-quality numbers: the L2 norms of the
        mean pseudo-gradient and of the applied outer update, plus the
        accepted-delta count. ``parent`` is the caller's ``outer_step``
        span: the phases below are its children when tracing is on, and
        the log line's ``*_s`` fields either way (a phase that did not
        run reads 0.000 and has no span).
        """
        t0 = time.monotonic()
        times = dict.fromkeys(
            ("mean_s", "load_s", "nesterov_s", "save_update_s", "save_momentum_s"),
            0.0,
        )

        def phase(
            name: str, key: str, attrs: dict | None = None, usage: bool = False
        ) -> trace.phase:
            return trace.phase(
                f"outer_step.{name}", parent=parent, attrs=attrs, into=times,
                key=key, usage=usage,
            )

        if accum is None or accum.folds == 0:
            accum = _RoundAccum(momentum.sums) if accum is None else accum
            for path, samples in received.values():
                accum.fold(path, samples)
        update, denom = accum.take()
        nbytes = sum(int(a.nbytes) for a in update.values())
        resident = momentum.tree is not None and all(
            key in momentum.tree for key in update
        )
        if momentum.tree is None:
            momentum.tree = {}
            if momentum.file.is_file():
                with phase("load_momentum", "load_s") as ph:
                    momentum.tree = {
                        k: np.require(v, np.float32, ["C", "W"])
                        for k, v in load_file(str(momentum.file)).items()
                    }
                    ph.set("bytes", sum(int(m.nbytes) for m in momentum.tree.values()))
                    ph.set("leaves", len(momentum.tree))
        for key, acc in update.items():
            m = momentum.tree.get(key)
            if m is not None and m.size != acc.size:
                # The flat kernel trusts n = momentum.size; a short tensor
                # from a buggy/malicious worker must fail here, before any
                # leaf's momentum is written, not read out of bounds.
                raise ValueError(
                    f"delta {key!r}: size {acc.size} != momentum {m.size}"
                )
        g_sq = u_sq = 0.0
        threads = 1
        with phase(
            "nesterov", "nesterov_s",
            {"native": native.native_available(), "fused_mean": True,
             "in_place": True, "bytes": nbytes, "leaves": len(update)},
            usage=True,
        ) as ph:
            for key, acc in update.items():
                acc = update[key] = np.require(acc, np.float32, ["C", "W"])
                m = momentum.tree.get(key)
                if m is None:
                    m = momentum.tree[key] = np.zeros(acc.shape, np.float32)
                if stats is not None:
                    g_sq += float(np.vdot(acc, acc))
                threads = max(threads, native.fused_mean_nesterov(
                    acc, denom, m, lr, mu, momentum.threads
                ))
                if stats is not None:
                    u_sq += float(np.vdot(acc, acc))
            ph.set("threads", threads)
        if stats is not None:
            # |Σ/d| = |Σ|/d: the mean's norm without the mean's tree.
            stats["delta_norm"] = float(np.sqrt(g_sq) / denom)
            stats["update_norm"] = float(np.sqrt(u_sq))
            stats["accepted"] = float(len(received))
        with phase(
            "save_update", "save_update_s",
            {"in_memory": not file, "leaves": len(update)},
        ) as ph:
            out = _Update(
                update, work_dir / f"update-{round_num}.safetensors", accum
            )
            if file:
                out.ensure_file()
            ph.set("bytes", out.nbytes)
        if momentum.save:
            momentum_tmp = work_dir / "momentum.next.safetensors"
            with phase("save_momentum", "save_momentum_s") as ph:
                save_file(momentum.tree, str(momentum_tmp))
                ph.set("bytes", momentum_tmp.stat().st_size)
                ph.set("leaves", len(momentum.tree))
                os.replace(momentum_tmp, momentum.file)
        # native_kernels=False means the numpy fallback ran: same numbers,
        # the slow outer step — said aloud so no run mistakes one for the other.
        log.info(
            "ps outer step: round=%d deltas=%d tensors=%d native_kernels=%s "
            "native_cbor=%s wall_s=%.3f mean_s=%.3f load_s=%.3f "
            "nesterov_s=%.3f save_update_s=%.3f save_momentum_s=%.3f bytes=%d "
            "threads=%d momentum_resident=%d momentum_saved=%d "
            "update_in_memory=%d",
            round_num, len(received), len(update), native.native_available(),
            native_codec_active(), time.monotonic() - t0,
            times["mean_s"], times["load_s"], times["nesterov_s"],
            times["save_update_s"], times["save_momentum_s"], nbytes,
            threads, resident, momentum.save, not file,
        )
        return out

    @staticmethod
    def _encode_broadcast(
        update: _Update,
        codec: str,
        ef: "compress.ErrorFeedback | None",
        work_dir: Path,
        round_num: int,
        tag: dict | None = None,
    ) -> tuple["Path | _Update", "dict[str, np.ndarray] | None"]:
        """Re-encode the f32 update for the wire per the job's codec.

        int8/int4 write an HQD1 frame of Q(update + residual) and keep the
        new residual; bf16 casts the SafeTensors payload; both read the
        update's file, which is written here if it is not there. "none"
        broadcasts the f32 update untouched (the seed's format): the wire
        is the update itself, from memory or from its file. ``tag`` stamps
        a streaming round's (round, fragment) identity into HQD1 frames.
        Returns the wire plus the update AS RECEIVERS WILL DECODE IT
        (None for "none") so the catch-up sum never re-reads and
        re-dequantizes the frame.
        """
        if codec == "none":
            return update, None
        wire = work_dir / f"update-{round_num}.wire.safetensors"
        sent = compress.write_delta(
            wire, dict(load_file(str(update.ensure_file()))), codec, ef=ef, tag=tag
        )
        return wire, sent

    @staticmethod
    def _checkpoint_momentum(momentum_file: Path, ckpt_dir: Path) -> None:
        """Atomic copy of the momentum file into the checkpoint dir."""
        if not momentum_file.is_file():
            return
        ckpt_dir.mkdir(parents=True, exist_ok=True)
        tmp = ckpt_dir / ".momentum.tmp"
        shutil.copyfile(momentum_file, tmp)
        os.replace(tmp, ckpt_dir / "momentum.safetensors")

    async def _broadcast_adaptive(
        self,
        cfg,
        update_path: Path,
        round_num: int,
        elastic: "_ElasticState | None",
        link: "LinkTable",
        peer_efs: dict,
        work_dir: Path,
        traceparent: str | None = None,
    ) -> None:
        """Per-LINK broadcast: peers grouped by the codec the measured-
        bandwidth table picked for their link, one wire per GROUP.

        Non-quantized codecs carry no residual, so their groups share one
        encode ("none" ships the f32 update file itself, zero extra
        work); only quantized links pay a per-peer encode, because their
        error-feedback residuals are necessarily per-peer — residual
        streams depend on the exact payload sequence a link saw, and one
        shared residual would absorb another link's error and bias both.
        The residual instance is kept across codec changes (f32 error is
        codec-independent), so a link that degrades int8 -> int4 mid-job
        keeps tracking the true trajectory. The selected codec is
        stamped into the push header (``CODEC_KEY``) so the worker
        switches its next UPLOAD to it — the HQD1 frame is
        self-describing, so no other negotiation exists. Each group fans
        out through the ordinary :meth:`_broadcast` (same retry /
        bounded-concurrency / tolerated-failure semantics).
        """
        peers = (
            list(elastic.membership.active)
            if elastic is not None
            else list(cfg.results.ref.peers or [])
        )
        if not peers:
            return
        by_codec: dict[str, list[str]] = {}
        for peer in peers:
            by_codec.setdefault(link.codec_for(peer), []).append(peer)
        # The f32 tree is only materialized if some link needs a re-encode
        # (a healthy pool at base codec "none" pays nothing).
        tree_box: dict = {}

        def update_tree() -> dict:
            if "tree" not in tree_box:
                tree_box["tree"] = dict(load_file(str(update_path)))
            return tree_box["tree"]

        sends: list[tuple[Path, str, list[str]]] = []
        scratch: list[Path] = []
        for codec, group in sorted(by_codec.items()):
            if codec in compress.QUANT_CODECS:
                for peer in group:
                    ef = peer_efs.get(peer)
                    if ef is None:
                        ef = peer_efs[peer] = compress.ErrorFeedback()
                    tag = hashlib.sha256(peer.encode()).hexdigest()[:12]
                    wire = work_dir / (
                        f"update-{round_num}.{tag}.wire.safetensors"
                    )
                    await asyncio.to_thread(
                        compress.write_delta, wire, update_tree(), codec,
                        ef=ef,
                    )
                    scratch.append(wire)
                    sends.append((wire, codec, [peer]))
            elif codec == "none":
                sends.append((update_path, codec, list(group)))
            else:
                wire = work_dir / (
                    f"update-{round_num}.{codec}.wire.safetensors"
                )
                await asyncio.to_thread(
                    compress.write_delta, wire, update_tree(), codec
                )
                scratch.append(wire)
                sends.append((wire, codec, list(group)))
        tasks = [
            asyncio.create_task(
                self._broadcast(
                    cfg, wire, round_num, elastic,
                    extra_header={CODEC_KEY: codec},
                    peers_override=group,
                    traceparent=traceparent,
                    span_round=round_num,
                ),
                name=f"ps-abcast-{codec}",
            )
            for wire, codec, group in sends
        ]
        try:
            await asyncio.gather(*tasks)
        finally:
            await aio.reap(*(t for t in tasks if not t.done()))
            for wire in scratch:
                wire.unlink(missing_ok=True)

    async def _broadcast(
        self,
        cfg,
        wire: "Path | _Update",
        round_num: int,
        elastic: "_ElasticState | None" = None,
        extra_header: dict | None = None,
        peers_override: list[str] | None = None,
        traceparent: str | None = None,
        span_round: int | None = None,
    ) -> None:
        """Push the update tensor to every worker in parallel (:232-269 —
        the reference pushes one peer at a time and the slowest link gates
        the whole round). Fan-out is bounded at ``_BROADCAST_CONCURRENCY``
        streams; per-peer send failures are tolerated — the worker can
        catch up next round (:265-268). ``TransferStrategy.ANY`` keeps its
        first-success semantics: the first push that lands cancels the
        rest.

        Elastic mode broadcasts to the current membership's active set
        (rejoiners included, departed peers skipped) and stamps the
        membership epoch into the header so every worker knows which view
        of the round produced this update.

        ``traceparent`` (round-update broadcasts on traced jobs only)
        stamps the round's trace context into the push header and, with
        ``span_round``, wraps the fan-out in a ``broadcast`` span —
        resync/catch-up/re-broadcast callers pass neither and keep their
        exact header bytes.

        ``wire`` is a file, or the round's f32 update where the outer step
        left it (:class:`_Update`): that one is pushed from its file if
        somebody has had it written, else straight from memory, each
        attempt from the first byte. A worker cannot tell the two apart.
        The caller retires the update after this returns or raises, by
        when every push has ended."""
        peers = cfg.results.ref.peers or []
        strategy = cfg.results.ref.strategy or TransferStrategy.ALL
        header = {
            "resource": cfg.results.ref.resource or "results",
            "name": wire.name,
            "round": round_num,
        }
        if extra_header:
            header.update(extra_header)
        trace.inject(header, traceparent)
        if elastic is not None:
            peers = list(elastic.membership.active)
            header["epoch"] = elastic.membership.epoch
        if peers_override is not None:
            # Pipelined rounds freeze the peer set at close time — a
            # rejoiner joining mid-fan-out gets its catch-up, not this
            # round's update (its catch-up already contains it).
            peers = peers_override
        # Live weight streaming: serve subscribers join the fan-out HERE —
        # after the elastic-membership and pipelined-round overrides, both
        # of which rewrite ``peers`` to the round's train members. Serve
        # followers are not round members (no quorum, no catch-up
        # accounting) and must survive every override; under a broadcast
        # tree they hang off relays via with_serve_leaves below instead of
        # inflating the PS's own egress.
        serve_peers = [
            str(p) for p in (getattr(cfg, "serve_peers", None) or [])
        ]
        if serve_peers:
            peers = list(peers) + [p for p in serve_peers if p not in peers]
        if not peers:
            return
        # Broadcast tree (hypha_tpu.stream.tree): push each wire to the
        # top-level relays (and ungrouped workers) only; the relays
        # re-push down their subtrees, cutting this node's egress per
        # round from W pushes to ~G. ANY-strategy fan-outs (first success
        # wins) keep the direct path — racing a tree against itself makes
        # no sense — as do single-peer sets.
        tree_map = getattr(cfg, "broadcast_tree", None)
        tree_groups = (
            list(getattr(tree_map, "groups", None) or [])
            if tree_map is not None
            else []
        )
        if (
            tree_groups
            and strategy != TransferStrategy.ANY
            and len(peers) > 1
        ):
            # A relay is handed a path, and hands its own node one.
            wire = await asyncio.to_thread(_wire_file, wire)
            bcast_span = (
                trace.begin(
                    "broadcast", parent=traceparent,
                    attrs={
                        "round": span_round, "peers": len(peers),
                        "tree": True, "source": "file",
                        "bytes": payload_size(wire),
                    },
                    node=self._trace_node(), usage=True,
                )
                if span_round is not None
                else None
            )
            try:
                # Serve leaves attach to relay heads (broadcast-only plan:
                # the relays derive the identical assignment from their
                # ShardMap's serve_leaves) — a leaf whose relay is live
                # drops out of top_targets and rides the relay hop; a
                # leaf with no live relay stays a direct target.
                bcast_groups = with_serve_leaves(
                    tree_groups,
                    serve_peers
                    + list(getattr(tree_map, "serve_leaves", None) or []),
                )
                targets = top_targets(bcast_groups, peers)
                delivered, lost = await tree_broadcast(
                    self.node, header, str(header.get("resource", "results")),
                    bcast_groups, targets, wire,
                    allowed=set(peers),
                    concurrency=_BROADCAST_CONCURRENCY,
                    what="ps tree broadcast", logger=log,
                )
                if lost:
                    log.warning(
                        "ps: tree broadcast left %d peer(s) unreached; "
                        "they catch up next round", lost,
                    )
            finally:
                trace.finish(bcast_span)
            return
        if isinstance(wire, _Update) and wire.file is not None:
            wire = wire.file
        in_memory = isinstance(wire, _Update)
        # A new iterator each attempt: a retry sends the whole frame.
        source = wire.views if in_memory else (lambda: wire)
        size = payload_size(wire.nbytes if in_memory else wire)
        bcast_span = (
            trace.begin(
                "broadcast", parent=traceparent,
                attrs={
                    "round": span_round, "peers": len(peers),
                    "source": "memory" if in_memory else "file",
                    "bytes": size,
                },
                node=self._trace_node(), usage=True,
            )
            if span_round is not None
            else None
        )
        sem = asyncio.Semaphore(_BROADCAST_CONCURRENCY)
        # Traced: what each peer's push did, on the span that is there:
        # ``attempts``, and of the last one ``connect_s`` (dial and stream,
        # to the header frame written), ``send_s`` and ``close_s``.
        pushes: dict[str, dict] = {}
        if bcast_span is not None:
            bcast_span.attributes["pushes"] = pushes

        def push(peer: str):
            if bcast_span is None:
                return self.node.push(peer, header, source())
            did = pushes[peer] = {
                "attempts": pushes.get(peer, {}).get("attempts", 0) + 1
            }
            return self.node.push(peer, header, source(), timing=did)

        async def push_one(peer: str) -> bool:
            async with sem:
                try:
                    # One backed-off re-try rides out a worker's transient
                    # blip; a genuinely dead peer is still tolerated — it
                    # catches up from the next round's broadcast.
                    await aio.retry(
                        lambda: push(peer),
                        attempts=2, base_delay=0.25,
                        attempt_timeout=push_timeout(size),
                        retry_on=(RequestError, OSError),
                        what=f"broadcast to {peer}", logger=log,
                    )
                    return True
                except (RequestError, OSError, asyncio.TimeoutError) as e:
                    log.warning(
                        "ps: broadcast to %s failed (%s); retry next round",
                        peer, e,
                    )
                    return False

        tasks = [
            asyncio.create_task(push_one(p), name=f"ps-bcast-{p}")
            for p in peers
        ]
        try:
            if strategy == TransferStrategy.ANY:
                try:
                    for fut in asyncio.as_completed(tasks):
                        if await fut:
                            break
                finally:
                    # First success (or caller cancellation): the losers of
                    # the race are cancelled and awaited, never abandoned.
                    await aio.reap(*(t for t in tasks if not t.done()))
            else:
                try:
                    await asyncio.gather(*tasks)
                finally:
                    # push_one only absorbs RequestError; a raw transport
                    # error (ConnectionResetError out of a severed stream)
                    # escapes the gather — the siblings must not be left
                    # streaming a file the job teardown is about to rmtree.
                    await aio.reap(*(t for t in tasks if not t.done()))
        finally:
            trace.finish(bcast_span)

    async def _notify_updated(
        self, scheduler_peer: str, job_id: str, round_num: int, shard: int = 0,
        arrivals: "dict[str, float] | None" = None,
        traceparent: str | None = None,
        execution=None,
        quality: "dict | None" = None,
    ) -> ProgressResponse:
        gen = (
            getattr(execution, "scheduler_generation", None)
            if execution is not None
            else None
        )
        progress = Progress(
            kind=ProgressKind.UPDATED, job_id=job_id, round=round_num,
            shard=shard, traceparent=traceparent,
            # Durable control plane: stamped only once a scheduler restart
            # actually happened (generation >= 2) — a never-restarted job's
            # Updated keeps today's exact bytes.
            scheduler_generation=(gen if gen is not None and gen >= 2 else None),
        )
        if arrivals is not None:
            # Straggler-adaptive inner steps (ft.adaptive): per-peer
            # round-trip lags for the scheduler's EWMA controller. Only
            # adaptive jobs attach the key — a static job's Updated stays
            # byte-identical to today's wire.
            progress.metrics = {
                "arrival_s": {p: round(t, 6) for p, t in arrivals.items()}
            }
        if quality:
            # Metrics plane (telemetry.metrics_plane): the round's
            # training-quality numbers (pseudo-gradient/update norms,
            # accepted deltas) ride the round-tagged Updated — only
            # reporting jobs attach the key; the static wire is untouched.
            progress.metrics = {**progress.metrics, "quality": dict(quality)}
        resp = await self.node.request(
            scheduler_peer, PROTOCOL_PROGRESS, progress, timeout=30
        )
        if not isinstance(resp, ProgressResponse):
            raise RequestError(f"unexpected progress response {resp!r}")
        if execution is not None:
            new_gen, stale = stale_scheduler_response(
                resp, getattr(execution, "scheduler_generation", None)
            )
            if stale:
                # A zombie predecessor answered: its OK/DONE decision must
                # not drive this shard's round machinery — drop and
                # re-notify (the live scheduler answers the retry).
                FT_METRICS.stale_generation_dropped.add(1)
                raise RequestError(
                    "stale scheduler generation on Updated reply"
                )
            execution.scheduler_generation = new_gen
        return resp

    async def _notify_updated_resilient(
        self, scheduler_peer: str, job_id: str, round_num: int, *,
        shard: int = 0,
        arrivals: "dict[str, float] | None" = None,
        traceparent: str | None = None,
        execution=None,
        park_s: float = 0.0,
        on_first_failure=None,
        quality: "dict | None" = None,
    ) -> ProgressResponse:
        """Updated notify that survives a scheduler outage.

        With ``park_s`` (the job's adoption grace) set, a SECOND
        consecutive failed attempt triggers ``on_first_failure`` — the
        round's broadcast, so an already-quorate round closes and workers
        merge WITHOUT the scheduler — then the notify parks in aio.retry
        until the restarted scheduler answers (idempotent by round on its
        side) or the grace runs out (execution fails, the existing
        re-auction path takes over). Two failures, not one: a single
        transient RPC blip against a LIVE scheduler must not reorder
        broadcast-before-notify — with the scheduler up, the workers'
        UpdateReceived is NOT parked, so the early broadcast would
        resurrect the exact Continue-vs-Done phantom-round race the
        static ordering exists to prevent. The notify-before-broadcast
        ordering is therefore preserved through any one-off failure, and
        a real outage costs one extra backed-off attempt (~1 s) before
        the round closes scheduler-free.
        """
        if park_s <= 0:
            return await self._notify_updated(
                scheduler_peer, job_id, round_num, shard=shard,
                arrivals=arrivals, traceparent=traceparent,
                execution=execution, quality=quality,
            )
        failures = {"n": 0}

        async def once() -> ProgressResponse:
            try:
                return await self._notify_updated(
                    scheduler_peer, job_id, round_num, shard=shard,
                    arrivals=arrivals, traceparent=traceparent,
                    execution=execution, quality=quality,
                )
            except (RequestError, OSError, asyncio.TimeoutError):
                failures["n"] += 1
                if failures["n"] == 2 and on_first_failure is not None:
                    FLIGHT.record(
                        "ps.notify_parked", node=self._trace_node(),
                        job=job_id, round=round_num, shard=shard,
                    )
                    await on_first_failure()
                raise

        return await aio.retry(
            once,
            base_delay=0.5, max_delay=5.0, deadline=park_s,
            retry_on=(RequestError, OSError),
            what=f"updated r{round_num} -> scheduler", logger=log,
        )
