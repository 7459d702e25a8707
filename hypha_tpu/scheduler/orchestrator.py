"""The scheduler orchestrator: allocate → wire → dispatch → supervise.

Reference call stack being reproduced (SURVEY.md §3.1,
crates/scheduler/src/bin/hypha-scheduler.rs:54-432):

  1. auction ``num_workers`` train workers + 1 parameter server
     (GreedyWorkerAllocator over gossip);
  2. accept offers by first lease renewal (WorkerHandle) and keep the
     renewal loops alive — a renewal failure is the worker-failure signal;
  3. per-worker batch size = floor(offered.gpu / required.gpu) clamped to
     ``max_batch_size`` (hypha-scheduler.rs:320-322);
  4. resolve the dataset's data provider from the discovery records;
  5. spawn DataScheduler (slice assignment), ProgressTracker +
     BatchScheduler (the DiLoCo control plane) and the MetricsBridge;
  6. dispatch the aggregate job to the PS and a train job per worker;
  7. supervise: job completes when the batch scheduler reports every
     worker DONE.

Failure handling comes in two tiers (net-new vs the reference, whose only
answer is aborting the run — rfc/2025-08-04 "Next Steps"):

  * **Elastic membership** (``job.ft`` set, hypha_tpu.ft): a train-worker
    death — lease renewal failure, failed job status, or φ-accrual
    suspicion — *degrades* the round instead of aborting it. The departed
    peer leaves the epoch-numbered membership view, the parameter server is
    told to aggregate at quorum, and a replacement is auctioned and caught
    up (``rejoin=True`` dispatch + the PS's cumulative-update push) without
    restarting anyone else. Only PS death or quorum loss fails the attempt.
  * **Full restart** (``max_attempts > 1``): the last resort — the failed
    attempt's leases lapse and the whole job re-runs, warm-starting from
    checkpoints when configured.

The no-progress watchdog is per-round: when ``status_timeout`` is not
given, the deadline derives from the synchronization simulation's projected
round time once every worker has timing statistics (satellite of the ft
work: a 600 s whole-run constant both masked early stalls on fast jobs and
killed slow-but-healthy large-model rounds).
"""

from __future__ import annotations

import asyncio
import logging
import uuid
from pathlib import Path
from typing import Any

from .. import aio, messages
from ..ft.adaptive import StragglerController
from ..ft.detector import PhiAccrualDetector
from ..ft.durable import (
    DEFAULT_ADOPT_DEADLINE_S,
    DEFAULT_ADOPT_GRACE_S,
    DurableScheduler,
)
from ..ft.membership import (
    PROTOCOL_FT,
    FTConfig,
    MembershipUpdate,
    MembershipView,
    quorum_size,
)
from ..messages import (
    AGGREGATE_EXECUTOR_NAME,
    PROTOCOL_API,
    PROTOCOL_PROGRESS,
    TRAIN_EXECUTOR_NAME,
    AdoptAck,
    AggregateExecutorConfig,
    DataRecord,
    Executor,
    ExecutorDescriptor,
    Fetch,
    JobSpec,
    Progress,
    ProgressKind,
    Receive,
    Reference,
    SchedulerHello,
    Send,
    ShardMap,
    TrainExecutorConfig,
    WorkerSpec,
)
from ..network.node import Node, RequestError
from ..stream import placement_parts, shards_due_at
from ..telemetry import trace
from ..telemetry.flight import FLIGHT
from ..telemetry.ft_metrics import FT_METRICS
from .allocator import GreedyWorkerAllocator
from .batch_scheduler import BatchScheduler
from .data_scheduler import DataScheduler
from .job_config import DiLoCoJob
from .metrics_bridge import MetricsBridge, MetricsConnector
from .simulation import project
from .task import DispatchError, StatusRouter, Task
from .trackers import ProgressTracker, WorkerState
from .worker_handle import WorkerHandle

__all__ = [
    "Orchestrator",
    "JobResult",
    "JobFailed",
    "AllocationError",
    "AdoptionFailed",
]

log = logging.getLogger("hypha.scheduler.orchestrator")

# Watchdog fallback while no per-round projection exists (no statistics
# yet, or a worker without a single timed batch).
DEFAULT_STATUS_TIMEOUT = 600.0
# Adaptive per-round deadline = clamp(factor · projected_round_time + the
# PS round deadline, floor, DEFAULT_STATUS_TIMEOUT).
ROUND_DEADLINE_FACTOR = 5.0
ROUND_DEADLINE_FLOOR_S = 60.0


class AllocationError(RuntimeError):
    pass


class JobFailed(RuntimeError):
    pass


class AdoptionFailed(RuntimeError):
    """Scheduler crash recovery could not adopt the previous attempt's
    executions (no/unreadable journal, or nothing alive to adopt). The
    caller falls back to the existing fresh-run / re-auction path."""


class JobResult:
    def __init__(
        self,
        job_id: str,
        rounds: int,
        metrics: list,
        attempt: int = 0,
        ft: dict | None = None,
    ) -> None:
        self.job_id = job_id
        self.rounds = rounds
        self.metrics = metrics  # [(peer, round, {name: value})]
        self.attempt = attempt  # 0 = first attempt succeeded (no restart)
        # Elastic-membership summary when the job ran with job.ft:
        # {"epoch", "active", "departed", "suspected", "rejoins"}.
        self.ft = ft


class _RunContext:
    """Everything one attempt's supervision + rejoin path needs."""

    def __init__(self) -> None:
        self.job: DiLoCoJob | None = None
        self.ft: FTConfig | None = None
        self.base_id = ""
        self.updates_tag = ""
        self.results_tag = ""
        self.handles: dict[str, WorkerHandle] = {}
        # One handle / job id / updates tag per PS shard (index = shard
        # index; a single-PS job has exactly one of each, with the exact
        # pre-shard job id and tag). A slot is None while that shard is
        # being restarted.
        self.ps_handles: list[WorkerHandle | None] = []
        self.ps_job_ids: list[str] = []
        self.ps_peers: list[str] = []  # planned shard peer ids (index = shard)
        self.shard_tags: list[str] = []
        self.shard_map: ShardMap | None = None
        self.reduce_groups: list[list[str]] = []
        self.router: StatusRouter | None = None
        self.tracker: ProgressTracker | None = None
        # Straggler-adaptive inner steps (hypha_tpu.ft.adaptive): the EWMA
        # round-trip controller, when job.adaptive_steps is on.
        self.adaptive: "StragglerController | None" = None
        self.assign_published = -1  # last round whose assignment was pushed
        self.data_scheduler: DataScheduler | None = None
        self.complete: asyncio.Event | None = None
        self.activity: list[float] = []
        self.status_timeout: float | None = None
        self.auction_timeout = 2.0
        self.detector: PhiAccrualDetector | None = None
        self.membership: MembershipView | None = None
        self.rejoin_count = 0
        self.notify_tasks: set[asyncio.Task] = set()
        # PS crash recovery (ft.durable): each shard's dispatched aggregate
        # spec is re-used verbatim on restart (same job id + stream tags,
        # so the recovered shard resumes its own durable state). A dead
        # shard is re-auctioned INDIVIDUALLY — the other shards keep
        # closing their rounds throughout.
        self.ps_specs: list[JobSpec] = []
        self.ps_restarts = 0
        self.ps_restarting: set[int] = set()
        # Scheduler crash recovery (ft.durable DurableScheduler): the
        # control plane's own journal (None when job.scheduler_recovery is
        # off), the adoption grace stamped into dispatched specs, the
        # BatchScheduler (held for round journaling + adoption), and the
        # last journaled round frontier.
        self.dur: "DurableScheduler | None" = None
        self.adopt_grace: float | None = None
        self.batch_scheduler: "BatchScheduler | None" = None
        self.round_journaled = -1
        # Live metrics plane (telemetry.metrics_plane): the scheduler-side
        # collector (None when job.metrics_plane is off — the default, no
        # new wire at all).
        self.metrics = None


class Orchestrator:
    def __init__(
        self,
        node: Node,
        metrics_connector: MetricsConnector | None = None,
    ) -> None:
        self.node = node
        self.allocator = GreedyWorkerAllocator(node)
        self.metrics_bridge = MetricsBridge(metrics_connector)
        # The last run's live-metrics collector (telemetry.metrics_plane):
        # kept on the orchestrator so benches/embedders can read the
        # store's rollups and loss curves after run() returns.
        self.metrics = None

    # ------------------------------------------------------------ allocation

    @staticmethod
    def _train_worker_spec(job: DiLoCoJob) -> WorkerSpec:
        return WorkerSpec(
            resources=job.resources.worker,
            executor=[
                ExecutorDescriptor(executor_class="train", name=TRAIN_EXECUTOR_NAME)
            ],
        )

    async def _allocate_train(
        self, job: DiLoCoJob, *, auction_timeout: float, attempts: int
    ) -> list:
        res = job.resources
        train_spec = self._train_worker_spec(job)
        for attempt in range(attempts):
            offers = await self.allocator.request(
                train_spec, res.worker_price, auction_timeout, res.num_workers
            )
            if len(offers) >= res.num_workers:
                return offers[: res.num_workers]
            log.warning(
                "auction %d/%d: %d/%d train offers",
                attempt + 1, attempts, len(offers), res.num_workers,
            )
        raise AllocationError(f"could not allocate {res.num_workers} train workers")

    async def _allocate_ps(
        self,
        job: DiLoCoJob,
        taken: set,
        *,
        auction_timeout: float,
        attempts: int,
        count: int = 1,
    ) -> list:
        """Auction ``count`` parameter-server (shard) executions.

        Distinct peers are preferred — the whole point of sharding is that
        each shard's deltas leave a different NIC — first distinct from
        the train workers, then from each other; when the mesh is smaller
        than the shard count, peers are reused (each shard still runs its
        own executor/journal under its own updates tag).
        """
        res = job.resources
        ps_spec = WorkerSpec(
            resources=res.parameter_server,
            executor=[
                ExecutorDescriptor(executor_class="aggregate", name=AGGREGATE_EXECUTOR_NAME)
            ],
        )
        for _attempt in range(attempts):
            offers = await self.allocator.request(
                ps_spec, res.parameter_server_price, auction_timeout,
                count + len(taken),
            )
            if not offers:
                continue
            # A peer already sold as a train worker can also host a PS if
            # its capacity covers both leases; prefer distinct peers.
            distinct = [o for o in offers if o.peer_id not in taken]
            ranked = distinct + [o for o in offers if o.peer_id in taken]
            picked: list = []
            seen: set = set()
            for offer in ranked:  # one offer per distinct peer first
                if offer.peer_id not in seen:
                    picked.append(offer)
                    seen.add(offer.peer_id)
                if len(picked) == count:
                    return picked
            while picked and len(picked) < count:
                # Reuse peers round-robin when the mesh is small.
                picked.append(ranked[len(picked) % len(ranked)])
            if len(picked) == count:
                return picked
        raise AllocationError(
            f"could not allocate {count} parameter server shard(s)"
        )

    @staticmethod
    def batch_size_for(offered, required, max_batch: int | None) -> int:
        """floor(offered/required) on the accelerator axis, clamped
        (hypha-scheduler.rs:320-322 sizes by gpu; tpu chips when the job
        asks for them)."""
        if required.tpu > 0:
            size = int(offered.tpu // required.tpu)
        elif required.gpu > 0:
            size = int(offered.gpu // required.gpu)
        else:
            size = max_batch or 1
        size = max(1, size)
        if max_batch is not None:
            size = min(size, max_batch)
        return size

    # ------------------------------------------------------------------ run

    async def run(
        self,
        job: DiLoCoJob,
        *,
        auction_timeout: float = 2.0,
        allocation_attempts: int = 3,
        status_timeout: float | None = None,
        max_attempts: int = 1,
        retry_backoff: float = 11.0,
    ) -> JobResult:
        """Run the job; with ``max_attempts > 1``, a failed attempt (PS
        death, quorum loss, stall) is re-run from scratch against whatever
        workers the auction finds — and when the job has a
        ``checkpoint_dir`` the replacement attempt warm-starts from the
        last completed round.

        With ``job.ft`` set, single train-worker failures never reach this
        level: they degrade the round at quorum and trigger a rejoin
        (hypha_tpu.ft), demoting the full restart to a last resort.
        ``retry_backoff`` defaults past the 10 s lease TTL so the failed
        attempt's leases lapse and the surviving workers' capacity frees
        before re-auctioning. ``status_timeout=None`` uses the per-round
        adaptive watchdog (simulation-projected round time).
        """
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        last: JobFailed | AllocationError | None = None
        sched_root = self._scheduler_root(job)
        for attempt in range(max_attempts):
            if attempt:
                log.warning(
                    "job attempt %d/%d failed (%s); retrying in %.0fs",
                    attempt, max_attempts, last, retry_backoff,
                )
                await asyncio.sleep(retry_backoff)
            # Scheduler crash recovery (ft.durable): a journal left by a
            # dead predecessor means live executions may still be training
            # — adopt them in place instead of re-auctioning. Any adoption
            # failure (no/corrupt journal, nothing alive) falls back to
            # the fresh-run path below, which wipes the stale journal.
            if (
                attempt == 0
                and sched_root is not None
                and DurableScheduler.has_state(sched_root)
            ):
                try:
                    result = await self._resume_once(
                        job,
                        auction_timeout=auction_timeout,
                        status_timeout=status_timeout,
                    )
                    result.attempt = attempt
                    return result
                except AdoptionFailed as e:
                    log.warning(
                        "scheduler recovery could not adopt the previous "
                        "attempt (%s); falling back to a fresh run", e,
                    )
                except (JobFailed, AllocationError) as e:
                    last = e
                    continue
            try:
                result = await self._run_once(
                    job,
                    auction_timeout=auction_timeout,
                    allocation_attempts=allocation_attempts,
                    status_timeout=status_timeout,
                )
                result.attempt = attempt
                return result
            except (JobFailed, AllocationError) as e:
                last = e
        assert last is not None
        raise last

    @staticmethod
    def _scheduler_root(job: DiLoCoJob) -> Path | None:
        if (
            getattr(job, "scheduler_recovery", False)
            and job.checkpoint_dir
            and job.ft is not None
            and job.ft.enabled
        ):
            return Path(job.checkpoint_dir) / "scheduler"
        return None

    # ------------------------------------------------------------- job specs

    def _train_spec(
        self,
        ctx: _RunContext,
        suffix: str,
        handle: WorkerHandle,
        rejoin: bool = False,
    ) -> JobSpec:
        job = ctx.job
        assert job is not None and ctx.ps_handles
        # Placement peers, NOT live handles: a shard mid-restart comes back
        # on the SAME peer id (_restart_ps), so a worker dispatched during
        # the outage must still wire every shard's results stream —
        # compacting out the restarting slot would make it wait on a
        # catch-up/broadcast source it never registered.
        if ctx.shard_map is not None and ctx.shard_map.shards:
            ps_peers = list(ctx.shard_map.shards)
        else:
            ps_peers = [h.peer_id for h in ctx.ps_handles if h is not None]
        assert ps_peers, "train spec needs at least one parameter server peer"
        # Tree-reduce role for THIS worker: the first member of its group
        # pre-folds the others' deltas (reduce_members); the rest route
        # their pushes [reducer, shard] with ANY failover (reduce_via).
        # Multi-level plans compose here unmodified: a mid-tree reducer
        # heads one group AND is a member of its parent's, so it gets
        # BOTH fields — members to fold, a parent to forward to.
        reduce_via = None
        reduce_members: list[str] = []
        for group in ctx.reduce_groups:
            if handle.peer_id == group[0]:
                reduce_members = [p for p in group[1:]]
            elif handle.peer_id in group:
                reduce_via = group[0]
        # Broadcast tree: reducers also relay result wires down their
        # subtree, and every worker's results allowlist must admit its
        # ancestor chain (any ancestor can be the hop that delivers —
        # including around a dead relay). Off (the default) ships
        # exactly today's Receive reference.
        tree_on = bool(getattr(job, "broadcast_tree", False)) and bool(
            ctx.reduce_groups
        )
        # Async input pipeline: resolve the prefetch window HERE so the
        # executor's prefetcher, the fetch reference and the scheduler's
        # slice-retirement accounting all see one number. None (pipeline
        # off, the default) stamps no new field anywhere — today's bytes.
        prefetch_depth = None
        if getattr(job, "input_pipeline", False):
            from ..executor.dataset import DEFAULT_PREFETCH_SLICES

            prefetch_depth = (
                int(getattr(job, "prefetch_slices", 0) or 0)
                or DEFAULT_PREFETCH_SLICES
            )
        results_peers = list(ps_peers)
        if tree_on:
            from ..stream import ancestors_of

            results_peers += [
                a
                for a in ancestors_of(ctx.reduce_groups, handle.peer_id)
                if a not in results_peers
            ]
        return JobSpec(
            job_id=f"{ctx.base_id}-{suffix}",
            executor=Executor(
                kind="train",
                name=TRAIN_EXECUTOR_NAME,
                train=TrainExecutorConfig(
                    model=job.model,
                    data=Fetch(
                        Reference.from_scheduler(
                            self.node.peer_id, job.dataset,
                            prefetch=prefetch_depth,
                        )
                    ),
                    updates=Send(
                        Reference.from_peers([ps_peers[0]], ctx.updates_tag)
                    ),
                    results=Receive(
                        # Every shard broadcasts on the shared results tag;
                        # tree-reduce jobs also accept the reducer-relayed
                        # streams (same tag, shard peers only; broadcast
                        # trees add the worker's ancestor relays).
                        Reference.from_peers(results_peers, ctx.results_tag)
                    ),
                    ps_shards=ctx.shard_map,
                    reduce_via=reduce_via,
                    reduce_members=reduce_members,
                    relay_results=(
                        True if tree_on and reduce_members else None
                    ),
                    optimizer=job.inner_optimizer,
                    batch_size=handle.batch_size,
                    preprocessor=job.preprocessor,
                    scheduler=job.lr_scheduler,
                    loss=job.loss,
                    sharding=job.sharding,
                    lora=job.lora,
                    delta_dtype=job.delta_dtype,
                    delta_codec=job.delta_codec,
                    sync_mode=job.sync_mode,
                    fragments=job.num_fragments,
                    rejoin=rejoin,
                    # Durable control plane: workers park control sends and
                    # hold leases this long across a scheduler outage
                    # (None — recovery off — ships no new wire field).
                    # getattr: tests drive this with bare namespace ctxs.
                    adopt_grace_s=getattr(ctx, "adopt_grace", None),
                    # Live metrics plane: report cadence + the collector
                    # peer (this scheduler). None — metrics off — ships
                    # no new wire fields.
                    report_metrics_s=(
                        float(getattr(job, "metrics_interval_s", 1.0))
                        if getattr(job, "metrics_plane", False)
                        else None
                    ),
                    metrics_peer=(
                        self.node.peer_id
                        if getattr(job, "metrics_plane", False)
                        else None
                    ),
                    input_pipeline=(
                        True if prefetch_depth is not None else None
                    ),
                    prefetch_slices=prefetch_depth,
                    checkpoint=(
                        {
                            "dir": f"{job.checkpoint_dir}/{handle.peer_id}",
                            "every_rounds": job.checkpoint_every,
                        }
                        if job.checkpoint_dir
                        else None
                    ),
                ),
            ),
        )

    def _plan_streams(
        self,
        ctx: _RunContext,
        job: DiLoCoJob,
        worker_peers: list[str],
        ps_peers: list[str],
        num_shards: int,
        parts: int,
    ) -> None:
        """Derive the attempt's stream identities from its peer lists:
        job-unique tags, per-shard job ids/tags, the deterministic
        tree-reduce grouping and the ShardMap placement, and the per-shard
        aggregate specs. Pure function of (base_id, peers, job) — which is
        exactly why a restarted scheduler can rebuild all of it from the
        journaled plan record instead of persisting every spec."""
        ctx.ps_peers = list(ps_peers)
        ctx.updates_tag = f"updates:{ctx.base_id}"
        ctx.results_tag = f"results:{ctx.base_id}"
        if num_shards == 1:
            ctx.shard_tags = [ctx.updates_tag]
            ctx.ps_job_ids = [f"{ctx.base_id}-ps"]
        else:
            ctx.shard_tags = [
                f"{ctx.updates_tag}.s{k}" for k in range(num_shards)
            ]
            ctx.ps_job_ids = [
                f"{ctx.base_id}-ps{k}" for k in range(num_shards)
            ]
        # Tree-reduce plan: deterministic sorted-peer-id groups-of-groups
        # (stream.tree). ``reduce_tree_depth`` unset builds exactly the
        # single-level chunks PR 6 shipped — the first member of each
        # group is its reducer, singleton groups dropped — so the
        # ShardMap's ``groups`` stay byte-identical. Depth >= 2 collapses
        # the tree into per-reducer groups whose children span levels
        # (mid-tree reducers appear both as a head and as another head's
        # member), which is what _train_spec's reduce_via/reduce_members
        # derivation already composes.
        group_size = int(getattr(job, "reduce_group_size", 0) or 0)
        depth = int(getattr(job, "reduce_tree_depth", 0) or 1)
        ctx.reduce_groups = []
        if group_size >= 2:
            from ..stream import build_reduce_groups

            ctx.reduce_groups = build_reduce_groups(
                worker_peers, group_size, depth
            )
        # The placement announcement workers route by. Built for any
        # sharded OR tree-reduced job; plain single-PS jobs ship None
        # and keep the exact pre-shard wire.
        ctx.shard_map = None
        if num_shards > 1 or ctx.reduce_groups:
            # Placement is a pure function of the job spec: a restarted
            # scheduler rebuilds the identical map, and the golden wire
            # bytes pin round=0 — workers route by shard tag, not round.
            ctx.shard_map = ShardMap(  # hypha-lint: disable=round-tag-not-live
                round=0,
                shards=list(ps_peers),
                tags=list(ctx.shard_tags),
                fragments=parts,
                groups=[list(g) for g in ctx.reduce_groups],
                # None for single-level plans: PR 6's exact wire bytes.
                tree_depth=(depth if depth >= 2 else None),
            )
        # Live weight streaming: serving followers ride the broadcast as
        # extra leaves. Under a broadcast tree they hang off relay heads
        # (stream.tree.with_serve_leaves reads serve_leaves from the
        # announced placement); flat jobs just append them to the PS's
        # push set via AggregateExecutorConfig.serve_peers below. Never
        # added to ``groups`` — reducers must not wait on them.
        serve_peers = [
            str(p) for p in (getattr(job, "serve_peers", None) or [])
        ]
        if (
            ctx.shard_map is not None
            and serve_peers
            and getattr(job, "broadcast_tree", False)
        ):
            ctx.shard_map.serve_leaves = list(serve_peers)
        ft = ctx.ft
        ctx.ps_specs = [
            JobSpec(
                job_id=ctx.ps_job_ids[k],
                executor=Executor(
                    kind="aggregate",
                    name=AGGREGATE_EXECUTOR_NAME,
                    aggregate=AggregateExecutorConfig(
                        updates=Receive(
                            Reference.from_peers(
                                worker_peers, ctx.shard_tags[k]
                            )
                        ),
                        results=Send(
                            Reference.from_peers(
                                worker_peers, ctx.results_tag
                            )
                        ),
                        optimizer=job.outer_optimizer,
                        num_workers=len(worker_peers),
                        checkpoint_dir=(
                            (
                                f"{job.checkpoint_dir}/ps"
                                if num_shards == 1
                                else f"{job.checkpoint_dir}/ps{k}"
                            )
                            if job.checkpoint_dir
                            else None
                        ),
                        ps_checkpoint_every_rounds=job.ps_checkpoint_every_rounds,
                        quorum_fraction=ft.quorum_fraction if ft else 0.0,
                        round_deadline_s=ft.round_deadline_s if ft else 0.0,
                        # The broadcast mirrors the upload codec: the
                        # receive side sniffs frames, so one field is
                        # enough for both directions.
                        delta_codec=job.delta_codec,
                        # Workers and the PS must agree on the fragment
                        # schedule, so both sides get the same pair.
                        sync_mode=job.sync_mode,
                        fragments=job.num_fragments,
                        shard_index=k,
                        num_ps_shards=num_shards,
                        # WAN-adaptive knobs (ft.adaptive): None — not
                        # False — when off, so a static job's dispatched
                        # spec carries no new wire fields at all.
                        adaptive_steps=(
                            True if getattr(job, "adaptive_steps", False)
                            else None
                        ),
                        adaptive_codec=(
                            True if getattr(job, "adaptive_codec", False)
                            else None
                        ),
                        codec_bw_hi_mbps=(
                            job.codec_bw_hi_mbps
                            if getattr(job, "adaptive_codec", False)
                            else None
                        ),
                        codec_bw_lo_mbps=(
                            job.codec_bw_lo_mbps
                            if getattr(job, "adaptive_codec", False)
                            else None
                        ),
                        # Broadcast tree: the PS mirrors the reduce
                        # placement downward (None = today's star fan-out,
                        # no new wire).
                        broadcast_tree=(
                            ctx.shard_map
                            if getattr(job, "broadcast_tree", False)
                            and ctx.reduce_groups
                            else None
                        ),
                        # Live weight streaming followers (None = today's
                        # exact wire; appended AFTER elastic overrides in
                        # the PS's _broadcast, never round members).
                        serve_peers=(serve_peers or None),
                        # Durable control plane: the PS parks its Updated
                        # notify (broadcast-first) across a scheduler
                        # outage (None = recovery off, no new wire).
                        adopt_grace_s=ctx.adopt_grace,
                        # Live metrics plane (None = off, no new wire).
                        report_metrics_s=(
                            float(getattr(job, "metrics_interval_s", 1.0))
                            if getattr(job, "metrics_plane", False)
                            else None
                        ),
                        metrics_peer=(
                            self.node.peer_id
                            if getattr(job, "metrics_plane", False)
                            else None
                        ),
                    ),
                ),
            )
            for k in range(num_shards)
        ]

    def _plan_record(self, ctx: _RunContext, ps_peers: list[str]) -> dict:
        """The journaled plan: what :meth:`_plan_streams` cannot re-derive
        (base id, peer lists) plus the lease/batch bindings adoption needs."""
        return {
            "base_id": ctx.base_id,
            "workers": {
                peer: {
                    "lease_id": handle.lease_id,
                    "batch_size": handle.batch_size,
                }
                for peer, handle in ctx.handles.items()
            },
            "ps_peers": list(ps_peers),
        }

    async def _journal_dispatch(
        self,
        ctx: _RunContext,
        job_id: str,
        handle: WorkerHandle,
        kind: str,
        shard: int | None = None,
    ) -> None:
        if getattr(ctx, "dur", None) is None:
            return
        # Off-loop like every other journal write: note_dispatch fsyncs,
        # and the journal lock may be held across a compaction rewrite —
        # neither may stall progress responses or lease renewals.
        await asyncio.to_thread(
            ctx.dur.note_dispatch,
            job_id,
            handle.peer_id,
            handle.lease_id,
            kind,
            shard,
            handle.batch_size or None,
        )

    def _journal_round_soon(self, ctx: _RunContext) -> None:
        """Journal a round-frontier advance off-loop (fire-and-forget like
        the membership pushes: a torn/lost round record costs re-deriving
        one round from AdoptAcks, never correctness)."""
        if getattr(ctx, "dur", None) is None or ctx.tracker is None:
            return
        if ctx.tracker.round <= ctx.round_journaled:
            return
        ctx.round_journaled = ctx.tracker.round
        ctrl = ctx.adaptive.snapshot() if ctx.adaptive is not None else None
        aio.spawn(
            asyncio.to_thread(ctx.dur.note_round, ctx.tracker.round, ctrl),
            tasks=ctx.notify_tasks,
            what="scheduler journal round",
            logger=log,
        )

    async def _start_data(self, ctx: _RunContext, job: DiLoCoJob) -> None:
        """Dataset discovery + slice scheduler
        (hypha-scheduler.rs:269,435-457). Re-run as-is on scheduler
        recovery: provider records live in the registry, not the journal."""
        raw = await self.node.get_record(job.dataset)
        if raw is None:
            raise JobFailed(f"no data record for dataset {job.dataset!r}")
        record = messages.decode(raw)
        if not isinstance(record, DataRecord):
            raise JobFailed(f"bad data record {record!r}")
        providers = await self.node.find_providers(job.dataset)
        if not providers:
            raise JobFailed(f"no provider for dataset {job.dataset!r}")
        ctx.data_scheduler = DataScheduler(
            self.node, providers[0], job.dataset, record.num_slices
        )
        ctx.data_scheduler.start()

    def _make_adaptive(self, ctx: _RunContext, job: DiLoCoJob) -> None:
        if not getattr(job, "adaptive_steps", False):
            return
        # Base inner-step count: the round's sample budget spread
        # over one aggregate sweep of the fleet's batch sizes —
        # what a uniform pool would run per worker per round.
        total_batch = sum(h.batch_size for h in ctx.handles.values())
        ctx.adaptive = StragglerController(
            base_steps=max(
                1,
                round(
                    job.rounds.avg_samples_between_updates
                    / max(total_batch, 1)
                ),
            )
        )

    def _start_metrics(self, ctx: _RunContext, job: DiLoCoJob) -> None:
        """Stand up the live metrics plane's scheduler half (the
        MetricsCollector): store + SLO watchdog + journal + the
        /hypha-metrics handler. No-op (today's exact behavior and wire)
        unless ``job.metrics_plane`` is on."""
        if not getattr(job, "metrics_plane", False):
            return
        from ..telemetry.metrics_plane import MetricsCollector

        journal_dir = getattr(job, "metrics_dir", None)
        if journal_dir is None:
            tracing = trace.active()
            journal_dir = tracing.trace_dir if tracing is not None else None

        def on_advisory(adv) -> None:
            # Advisory, not actuator: the orchestrator LOGS the breach
            # (the RoundMembership posture); enforcement is future work.
            log.warning(
                "SLO advisory for job %s: %s (peer=%s value=%.6g) — "
                "logged only",
                adv.job_id or ctx.base_id, adv.rule, adv.peer or "fleet",
                adv.value,
            )

        ctx.metrics = MetricsCollector(
            self.node,
            ctx.base_id,
            slo_rules=list(getattr(job, "slo_rules", []) or []),
            journal_dir=journal_dir,
            on_advisory=on_advisory,
            round_fn=lambda: ctx.tracker.round if ctx.tracker else 0,
        ).start()
        self.metrics = ctx.metrics

    def _start_control(
        self,
        ctx: _RunContext,
        job: DiLoCoJob,
        num_shards: int,
        parts: int,
        generation: int | None = None,
    ):
        """Stand up the DiLoCo control plane: BatchScheduler + the
        /hypha-progress handler. ``generation`` is None for a fresh run
        (unstamped responses, today's exact wire) and the bumped scheduler
        generation on recovery. Returns (collected_metrics, registration)."""
        ctx.complete = asyncio.Event()
        collected: list = []
        ctx.activity = [asyncio.get_running_loop().time()]  # watchdog feed

        def on_metrics(peer: str, round_num: int, metrics: dict) -> None:
            collected.append((peer, round_num, metrics))
            self.metrics_bridge.on_metrics(peer, round_num, metrics)
            if ctx.metrics is not None:
                # Round-tagged training-quality points (loss, loss EWMA,
                # delta norm, tokens/s) join the live store.
                ctx.metrics.ingest_quality(peer, round_num, metrics)

        batch_scheduler = BatchScheduler(
            ctx.tracker, on_metrics=on_metrics, on_complete=ctx.complete.set,
            shards_due=(
                (
                    lambda r: shards_due_at(
                        job.sync_mode, r, parts, num_shards
                    )
                )
                if num_shards > 1
                else None
            ),
            adaptive=ctx.adaptive,
            generation=generation,
        )
        ctx.batch_scheduler = batch_scheduler
        # A traced lease renewal is a span under the round that is open.
        for handle in (*ctx.handles.values(), *ctx.ps_handles):
            if handle is not None:
                handle.round_ctx = batch_scheduler.round_ctx

        async def on_progress(peer: str, progress: Progress):
            # Deliberately ahead of the generation fence: any traffic from
            # a peer — even a zombie predecessor's — is a liveness signal,
            # and the timestamp feeds failure detection only.
            ctx.activity[0] = asyncio.get_running_loop().time()  # hypha-lint: disable=handler-mutates-before-guard
            if ctx.detector is not None:
                # Every progress message is a liveness signal — per-batch
                # Status heartbeats mostly, but the PS's Updated and the
                # round metrics count too.
                ctx.detector.heartbeat(peer)
            if (
                ctx.metrics is not None
                and progress.kind == ProgressKind.UPDATED
            ):
                # The PS's round-tagged quality (pseudo-gradient/update
                # norms, accepted deltas) rides its Updated notify — only
                # reporting jobs attach the key, so the static wire is
                # untouched.
                quality = dict(progress.metrics).get("quality")
                if isinstance(quality, dict):
                    ctx.metrics.ingest_quality(peer, progress.round, quality)
            response = batch_scheduler.on_progress(peer, progress)
            self._journal_round_soon(ctx)
            if (
                ctx.adaptive is not None
                and ctx.membership is not None
                and ctx.tracker is not None
                and ctx.tracker.round > ctx.assign_published
            ):
                # A round advanced: publish the fresh per-worker
                # inner-step assignment with the round membership so
                # the PS can account expected contributions (and the
                # HET telemetry gauges follow). Fire-and-forget like
                # every other membership push — a lost snapshot is
                # repaired by the next one.
                ctx.assign_published = ctx.tracker.round
                self._notify_membership_soon(ctx)
            return response

        progress_reg = self.node.on(PROTOCOL_PROGRESS, Progress).respond_with(
            on_progress
        )
        return collected, progress_reg

    async def _run_once(
        self,
        job: DiLoCoJob,
        *,
        auction_timeout: float = 2.0,
        allocation_attempts: int = 3,
        status_timeout: float | None = None,
    ) -> JobResult:
        ft = job.ft if (job.ft is not None and job.ft.enabled) else None
        worker_offers = await self._allocate_train(
            job, auction_timeout=auction_timeout, attempts=allocation_attempts
        )
        ctx = _RunContext()
        ctx.job = job
        ctx.ft = ft
        ctx.status_timeout = status_timeout
        ctx.auction_timeout = auction_timeout
        if self._scheduler_root(job) is not None:
            assert ft is not None
            ctx.adopt_grace = (
                ft.scheduler_adopt_grace_s
                if ft.scheduler_adopt_grace_s is not None
                else DEFAULT_ADOPT_GRACE_S
            )
        progress_reg = None
        tasks: list[Task] = []
        try:
            # Acceptance: first renewal converts each temp lease — must happen
            # within the 500 ms offer window, so BEFORE the PS auction runs
            # (worker.rs:75; rfc/2025-08-04 "Lease Renewal"). Bounded
            # fan-out, not a serial walk: at N=128 a serial sweep of
            # round trips would blow the offer window by itself; insertion
            # stays in offer order so worker indices are deterministic.
            # Handles are recorded as they are created (index slot, then
            # merged in offer order), not from gather's return value: if
            # one offer fails mid-fan-out, the siblings already created
            # must still reach ctx.handles so the outer cleanup releases
            # their leases instead of leaking them until expiry.
            created: "list[WorkerHandle | None]" = [None] * len(worker_offers)

            async def _create(i: int, offer) -> None:
                created[i] = await WorkerHandle.create(self.node, offer)

            try:
                await aio.gather_bounded(
                    [
                        (lambda i=i, o=offer: _create(i, o))
                        for i, offer in enumerate(worker_offers)
                    ],
                    limit=16,
                )
            finally:
                for handle in created:
                    if handle is not None:
                        ctx.handles[handle.peer_id] = handle
            num_shards = max(int(getattr(job, "num_ps_shards", 1) or 1), 1)
            ps_offers = await self._allocate_ps(
                job,
                set(ctx.handles),
                auction_timeout=auction_timeout,
                attempts=allocation_attempts,
                count=num_shards,
            )
            for offer in ps_offers:
                ctx.ps_handles.append(
                    await WorkerHandle.create(self.node, offer)
                )

            for handle in ctx.handles.values():
                handle.batch_size = self.batch_size_for(
                    handle.offer.resources,
                    job.resources.worker,
                    job.rounds.max_batch_size,
                )

            await self._start_data(ctx, job)

            ctx.tracker = ProgressTracker(
                parameter_server=[h.peer_id for h in ctx.ps_handles],
                update_target=job.rounds.avg_samples_between_updates,
                update_epochs=job.rounds.update_rounds,
            )
            for peer, handle in ctx.handles.items():
                ctx.tracker.add_worker(peer, handle.batch_size)

            if ft is not None:
                ctx.detector = PhiAccrualDetector(threshold=ft.phi_threshold)
                ctx.membership = MembershipView(list(ctx.handles))
                for handle in ctx.handles.values():
                    handle.on_renew = ctx.detector.heartbeat

            parts = placement_parts(
                job.sync_mode, job.num_fragments, num_shards
            )
            self._make_adaptive(ctx, job)
            collected, progress_reg = self._start_control(
                ctx, job, num_shards, parts
            )

            ctx.router = StatusRouter(self.node)
            ctx.base_id = str(uuid.uuid4())
            worker_peers = list(ctx.handles)
            ps_peers = [h.peer_id for h in ctx.ps_handles]
            # Job-unique stream tags: push routing keys on these, so several
            # jobs (or a PS colocated with a train job) can share worker
            # nodes without consuming each other's tensor streams. With N
            # shards, each shard gets its OWN updates tag so colocated
            # shard executors never consume each other's parts.
            self._plan_streams(
                ctx, job, worker_peers, ps_peers, num_shards, parts
            )
            # Live metrics plane: collector after the base id exists (the
            # journal is named for the job), before anything dispatches.
            self._start_metrics(ctx, job)
            sched_root = self._scheduler_root(job)
            if sched_root is not None:
                # Durable control plane: open FRESH (a previous attempt's
                # journal must not be adopted against this attempt's
                # executions) and persist the plan before anything runs.
                ctx.dur = await asyncio.to_thread(
                    lambda: DurableScheduler.open(sched_root, fresh=True)
                )
                await asyncio.to_thread(
                    ctx.dur.note_plan, self._plan_record(ctx, ps_peers)
                )
            for k, spec in enumerate(ctx.ps_specs):
                ps_task = await Task.dispatch(
                    self.node, ctx.router, spec, [ctx.ps_handles[k]]
                )
                tasks.append(ps_task)
                await self._journal_dispatch(
                    ctx, spec.job_id, ctx.ps_handles[k], "aggregate", shard=k
                )
            # Train dispatches fan out with bounded concurrency (each is
            # an independent request to a distinct peer); journaling stays
            # in worker order afterwards so the journal is deterministic.
            pairs = [
                (self._train_spec(ctx, f"w{i}", handle), handle)
                for i, (peer, handle) in enumerate(ctx.handles.items())
            ]
            dispatched = await aio.gather_bounded(
                [
                    (
                        lambda s=spec, h=handle: Task.dispatch(
                            self.node, ctx.router, s, [h]
                        )
                    )
                    for spec, handle in pairs
                ],
                limit=8,
            )
            for (spec, handle), task in zip(pairs, dispatched):
                tasks.append(task)
                await self._journal_dispatch(ctx, spec.job_id, handle, "train")

            await self._supervise(ctx, tasks)
            ft_summary = None
            if ctx.membership is not None:
                snap = ctx.membership.snapshot()
                ft_summary = {
                    "epoch": snap.epoch,
                    "active": snap.active,
                    "suspected": snap.suspected,
                    "departed": snap.departed,
                    "rejoins": ctx.rejoin_count,
                }
            if ctx.dur is not None:
                # A finished job's journal must not be adopted by the next
                # run against executions that no longer exist.
                await asyncio.to_thread(ctx.dur.complete)
            return JobResult(ctx.base_id, ctx.tracker.round, collected, ft=ft_summary)
        finally:
            for task in ctx.notify_tasks:
                task.cancel()
            if ctx.notify_tasks:
                await asyncio.gather(
                    *list(ctx.notify_tasks), return_exceptions=True
                )
            if ctx.dur is not None:
                await asyncio.to_thread(ctx.dur.close)
            if ctx.metrics is not None:
                await ctx.metrics.close()
            if progress_reg is not None:
                progress_reg.close()
            if ctx.data_scheduler is not None:
                ctx.data_scheduler.stop()
            if ctx.router is not None:
                ctx.router.close()
            for handle in ctx.handles.values():
                await handle.release()
            for ps_handle in ctx.ps_handles:
                if ps_handle is not None:
                    await ps_handle.release()
            await self.metrics_bridge.close()

    # --------------------------------------------------- scheduler recovery

    async def _adopt_executions(
        self,
        ctx: _RunContext,
        records: dict[str, dict],
        round_hint: int,
        deadline_s: float,
        clock=None,
    ) -> dict[str, AdoptAck]:
        """Run the SchedulerHello/AdoptAck handshake on the existing
        executor channels.

        ``records`` maps job id → its latest journaled dispatch record.
        Peers are re-asked with backoff until they answer or ``deadline_s``
        passes (injectable ``clock`` pins the deadline in tests without
        real waiting); a definitive answer — ``running``, ``gone`` or
        ``stale`` — stops the asking. Whatever is still unanswered at the
        deadline is handed to the caller's fallback: the existing
        depart/rejoin and per-shard ps-restart re-auction paths.
        """
        assert ctx.dur is not None
        loop = asyncio.get_running_loop()
        now = clock or loop.time
        stop_at = now() + max(deadline_s, 0.0)
        acks: dict[str, AdoptAck] = {}
        pending = dict(records)

        async def ask(
            job_id: str, rec: dict, timeout: float
        ) -> "tuple[str, AdoptAck | None]":
            hello = SchedulerHello(
                generation=ctx.dur.generation,
                job_id=job_id,
                round=round_hint,
            )
            span = trace.begin(
                "adopt", attrs={"job": job_id, "round": round_hint},
                node="scheduler",
            )
            try:
                resp = await self.node.request(
                    str(rec.get("peer", "")), PROTOCOL_API, hello,
                    timeout=timeout,
                )
            except (RequestError, OSError, asyncio.TimeoutError) as e:
                trace.finish(span, ok=False)
                log.info("adoption hello for %s failed: %s", job_id, e)
                return job_id, None
            if not isinstance(resp, AdoptAck):
                trace.finish(span, ok=False)
                return job_id, None
            trace.finish(span, ok=resp.state == "running")
            return job_id, resp

        first_pass = True
        while pending and (first_pass or now() < stop_at):
            first_pass = False
            # Fan the sweep out (the hellos are independent) and bound
            # each request by the REMAINING deadline: a serial sweep over
            # N dead peers would overshoot the adoption deadline N-fold
            # and delay the re-auction fallback by the same factor.
            timeout = min(5.0, max(stop_at - now(), 0.5))
            results = await asyncio.gather(
                *(
                    ask(job_id, rec, timeout)
                    for job_id, rec in pending.items()
                )
            )
            for job_id, resp in results:
                if resp is None:
                    continue
                acks[job_id] = resp
                rec = pending.pop(job_id, None) or {}
                if resp.state == "running":
                    FT_METRICS.adopted_executions.add(1)
                FLIGHT.record(
                    "scheduler.adopt_ack", node="scheduler", job=job_id,
                    peer=str(rec.get("peer", "")), state=resp.state,
                    round=resp.round, epoch=resp.epoch,
                )
            if pending and now() < stop_at:
                await asyncio.sleep(0.3)
        return acks

    async def _resume_once(
        self,
        job: DiLoCoJob,
        *,
        auction_timeout: float = 2.0,
        status_timeout: float | None = None,
    ) -> JobResult:
        """Adopt a dead predecessor's executions instead of re-auctioning.

        The journal supplies the plan (base id → every stream identity is
        re-derived), the live dispatch records and the last round
        frontier; the fleet supplies the truth — each AdoptAck reports the
        execution's actual round, so the scheduler FAST-FORWARDS to where
        training already is (a quorate round that closed during the outage
        is never re-run). Executions that fail the lease re-arm or never
        ack within the adoption deadline fall back to the existing
        depart/rejoin (train) and per-shard restart (PS) re-auction paths
        once supervision starts.
        """
        ft = job.ft if (job.ft is not None and job.ft.enabled) else None
        sched_root = self._scheduler_root(job)
        assert ft is not None and sched_root is not None
        try:
            dur = await asyncio.to_thread(
                lambda: DurableScheduler.open(sched_root)
            )
        except Exception as e:
            raise AdoptionFailed(f"scheduler journal unreadable: {e}") from e
        if dur.resume is None:
            await asyncio.to_thread(dur.close)
            raise AdoptionFailed("journal holds no adoptable plan")
        res = dur.resume
        ctx = _RunContext()
        ctx.job = job
        ctx.ft = ft
        ctx.dur = dur
        ctx.status_timeout = status_timeout
        ctx.auction_timeout = auction_timeout
        ctx.adopt_grace = (
            ft.scheduler_adopt_grace_s
            if ft.scheduler_adopt_grace_s is not None
            else DEFAULT_ADOPT_GRACE_S
        )
        ctx.base_id = res.base_id
        ctx.rejoin_count = res.rejoins
        ctx.ps_restarts = res.ps_restarts
        num_shards = max(int(getattr(job, "num_ps_shards", 1) or 1), 1)
        parts = placement_parts(job.sync_mode, job.num_fragments, num_shards)
        plan = res.plan
        plan_workers: dict = dict(plan.get("workers") or {})
        ps_peers = [str(p) for p in (plan.get("ps_peers") or [])]
        if not plan_workers or len(ps_peers) != num_shards:
            await asyncio.to_thread(dur.close)
            raise AdoptionFailed("journaled plan is incomplete")
        log.warning(
            "scheduler recovery: generation %d adopting job %s at round %d "
            "(%d journaled executions)",
            dur.generation, ctx.base_id, res.round, len(res.dispatches),
        )
        recovery_span = trace.begin(
            "scheduler_recovery",
            attrs={"generation": dur.generation, "round": res.round},
            node="scheduler",
        )
        progress_reg = None
        tasks: list[Task] = []
        try:
            # Stream identities re-derive deterministically from the plan:
            # the ORIGINAL worker set keeps tags/groups/specs matching what
            # the live executions were dispatched with.
            self._plan_streams(
                ctx, job, sorted(plan_workers), ps_peers, num_shards, parts
            )
            self._start_metrics(ctx, job)
            # Latest per-execution dispatch records, classified. Train
            # records for departed peers (a rejoin superseded them) are
            # skipped via the journaled membership's active list.
            member = res.member or {}
            active = [
                str(p)
                for p in (member.get("active") or sorted(plan_workers))
            ]
            lease_ids: dict[str, str] = {
                peer: str(rec.get("lease_id", ""))
                for peer, rec in plan_workers.items()
            }
            batch_sizes: dict[str, int] = {
                peer: int(rec.get("batch_size", 1) or 1)
                for peer, rec in plan_workers.items()
            }
            train_jobs: dict[str, str] = {}  # peer -> job id
            for job_id, rec in res.dispatches.items():
                if rec.get("kind") != "train":
                    continue
                peer = str(rec.get("peer", ""))
                train_jobs[peer] = job_id
                lease_ids[peer] = str(rec.get("lease_id", ""))
                if rec.get("batch_size"):
                    batch_sizes[peer] = int(rec["batch_size"])
            # Re-arm the journaled leases: the workers held them through
            # the outage (adoption grace), so the first renewal resumes
            # liveness tracking exactly where the dead loop stopped.
            dead_workers: list[str] = []
            for peer in active:
                if peer not in lease_ids or peer not in train_jobs:
                    dead_workers.append(peer)
                    continue
                try:
                    handle = await WorkerHandle.adopt(
                        self.node, peer, lease_ids[peer]
                    )
                except (RequestError, OSError, asyncio.TimeoutError) as e:
                    log.warning(
                        "adoption: lease re-arm for %s failed: %s", peer, e
                    )
                    dead_workers.append(peer)
                    continue
                handle.batch_size = batch_sizes.get(peer, 1)
                ctx.handles[peer] = handle
            ctx.ps_handles = [None] * num_shards
            dead_shards: list[int] = []
            for k, ps_job_id in enumerate(ctx.ps_job_ids):
                rec = res.dispatches.get(ps_job_id)
                if rec is None:
                    dead_shards.append(k)
                    continue
                try:
                    ctx.ps_handles[k] = await WorkerHandle.adopt(
                        self.node, str(rec.get("peer", "")),
                        str(rec.get("lease_id", "")),
                    )
                except (RequestError, OSError, asyncio.TimeoutError) as e:
                    log.warning(
                        "adoption: lease re-arm for ps shard %d failed: %s",
                        k, e,
                    )
                    dead_shards.append(k)
            if not ctx.handles and all(h is None for h in ctx.ps_handles):
                raise AdoptionFailed("nothing alive to adopt")

            # The re-adoption handshake proper, bounded by the deadline.
            hello_records = {
                train_jobs[peer]: {"peer": peer}
                for peer in ctx.handles
            }
            for k, ps_job_id in enumerate(ctx.ps_job_ids):
                if ctx.ps_handles[k] is not None:
                    rec = res.dispatches.get(ps_job_id) or {}
                    hello_records[ps_job_id] = {"peer": rec.get("peer", "")}
            deadline_s = (
                ft.scheduler_adopt_deadline_s
                if ft.scheduler_adopt_deadline_s is not None
                else DEFAULT_ADOPT_DEADLINE_S
            )
            acks = await self._adopt_executions(
                ctx, hello_records, res.round, deadline_s
            )
            running = {
                job_id: ack
                for job_id, ack in acks.items()
                if ack.ok and ack.state == "running"
            }
            # Fully-finished job adopted post-mortem: every execution is
            # gone and the journal frontier covers the whole plan — report
            # success instead of re-running a completed job from scratch.
            if not running and res.round >= job.rounds.update_rounds:
                await asyncio.to_thread(dur.complete)
                return JobResult(ctx.base_id, res.round, [])
            if not running:
                raise AdoptionFailed("no execution answered the hello")

            await self._start_data(ctx, job)
            ctx.tracker = ProgressTracker(
                parameter_server=ps_peers,
                update_target=job.rounds.avg_samples_between_updates,
                update_epochs=job.rounds.update_rounds,
            )
            ctx.detector = PhiAccrualDetector(threshold=ft.phi_threshold)
            # Tracker + membership include the DEAD peers too: the prelude
            # below routes them through the normal _depart machinery
            # (quorum check, rejoin auction) once supervision starts.
            members: list[str] = []
            for peer in active:
                if peer in ctx.handles or peer in dead_workers:
                    ctx.tracker.add_worker(peer, batch_sizes.get(peer, 1))
                    members.append(peer)
            ctx.membership = MembershipView(members)
            # Epoch continuity: resume PAST the journaled epoch so the
            # first post-restart push supersedes anything the PS adopted
            # from the dead scheduler (the PS epoch-gates updates).
            ctx.membership.epoch = int(member.get("epoch", 0)) + 1
            ctx.membership.departed = {
                str(p) for p in (member.get("departed") or [])
            }
            for handle in ctx.handles.values():
                handle.on_renew = ctx.detector.heartbeat
            self._make_adaptive(ctx, job)
            collected, progress_reg = self._start_control(
                ctx, job, num_shards, parts, generation=dur.generation
            )
            ctx.router = StatusRouter(self.node)
            # Fast-forward, never rewind: each adopted shard's AdoptAck
            # round is an UPDATED the predecessor processed (or that died
            # with it) — credit them and re-advance the frontier.
            shard_rounds = {
                k: running[ps_job_id].round
                for k, ps_job_id in enumerate(ctx.ps_job_ids)
                if ps_job_id in running
            }
            assert ctx.batch_scheduler is not None
            # adopt_round also puts the rebuilt straggler controller in
            # WARMUP, seeded from the journaled EWMA snapshot: base
            # assignments, no drop penalty, until one full measured round
            # (the arrivals the dead scheduler never saw are not evidence
            # of slowness).
            adopted_round = ctx.batch_scheduler.adopt_round(
                res.round, shard_rounds, ctrl=res.ctrl
            )
            ctx.round_journaled = adopted_round
            await asyncio.to_thread(
                ctx.dur.note_round, adopted_round,
                ctx.adaptive.snapshot() if ctx.adaptive is not None else None,
            )
            FT_METRICS.scheduler_recoveries.add(1)
            FLIGHT.record(
                "scheduler.recovered", node="scheduler",
                generation=dur.generation, round=adopted_round,
                adopted=len(running), journal_round=res.round,
            )
            log.warning(
                "scheduler recovery: adopted %d/%d executions, "
                "fast-forwarded round %d -> %d",
                len(running), len(hello_records), res.round, adopted_round,
            )
            # Watch the adopted executions' job statuses on the existing
            # channels (no re-dispatch: the jobs are already running).
            for job_id in running:
                tasks.append(Task.attach(ctx.router, job_id))
            # Refresh the fleet's membership view under the new epoch (and
            # hand the PS the new inner-step state: None, warmup).
            self._notify_membership_soon(ctx)

            async def prelude(add) -> None:
                for peer in list(ctx.membership.active):
                    job_id = train_jobs.get(peer)
                    adopted = job_id is not None and job_id in running
                    if not adopted:
                        await self._depart(
                            ctx, peer, "no adoption ack", add
                        )
                for k, ps_job_id in enumerate(ctx.ps_job_ids):
                    if ps_job_id not in running:
                        self._request_ps_restart(
                            ctx, k, "no adoption ack", add
                        )

            await self._supervise(ctx, tasks, prelude=prelude)
            ft_summary = None
            if ctx.membership is not None:
                snap = ctx.membership.snapshot()
                ft_summary = {
                    "epoch": snap.epoch,
                    "active": snap.active,
                    "suspected": snap.suspected,
                    "departed": snap.departed,
                    "rejoins": ctx.rejoin_count,
                }
            await asyncio.to_thread(ctx.dur.complete)
            return JobResult(
                ctx.base_id, ctx.tracker.round, collected, ft=ft_summary
            )
        finally:
            trace.finish(recovery_span)
            for task in ctx.notify_tasks:
                task.cancel()
            if ctx.notify_tasks:
                await asyncio.gather(
                    *list(ctx.notify_tasks), return_exceptions=True
                )
            await asyncio.to_thread(ctx.dur.close)
            if ctx.metrics is not None:
                await ctx.metrics.close()
            if progress_reg is not None:
                progress_reg.close()
            if ctx.data_scheduler is not None:
                ctx.data_scheduler.stop()
            if ctx.router is not None:
                ctx.router.close()
            for handle in ctx.handles.values():
                await handle.release()
            for ps_handle in ctx.ps_handles:
                if ps_handle is not None:
                    await ps_handle.release()
            await self.metrics_bridge.close()

    # ------------------------------------------------------------ supervision

    def _effective_timeout(self, ctx: _RunContext) -> float:
        """Per-round no-progress deadline.

        Explicit ``status_timeout`` wins. Otherwise, once every tracked
        worker has batch-timing statistics, the synchronization simulation
        projects a full round from scratch and the deadline is
        ``clamp(5 × projected + PS round deadline, 60 s, 600 s)`` —
        recomputed every tick, so it tracks membership and speed changes.
        """
        if ctx.status_timeout is not None:
            return ctx.status_timeout
        tracker = ctx.tracker
        if tracker is None or not tracker.has_full_stats():
            return DEFAULT_STATUS_TIMEOUT
        projection = project(
            tracker.update_target,
            tracker.sims(fresh=True),
            time_cap_ms=float("inf"),
            updates_cap=1_000_000_000,
        )
        deadline = ROUND_DEADLINE_FACTOR * projection.time_ms / 1000.0
        if ctx.ft is not None:
            deadline += ctx.ft.round_deadline_s
        return min(max(deadline, ROUND_DEADLINE_FLOOR_S), DEFAULT_STATUS_TIMEOUT)

    async def _watch_status(self, task: Task) -> tuple[str, str, str]:
        """Resolve when ``task`` reports failed/cancelled on some worker."""
        while True:
            peer, status = await task.next_status()
            log.info("job %s on %s: %s %s",
                     status.job_id, peer, status.state, status.message)
            if status.state == "failed":
                return peer, status.job_id, status.message or "failed"
            if status.state == "cancelled":
                return peer, status.job_id, "cancelled"

    async def _supervise(
        self, ctx: _RunContext, tasks: list[Task], prelude=None
    ) -> None:
        """Wait for completion; tolerate train-worker loss when elastic.

        Failure signals: per-task failed/cancelled job statuses, per-handle
        lease-renewal failures, and (elastic only) φ-accrual suspicion
        polled every tick. Without ``job.ft`` any failure aborts the attempt
        exactly like the seed (hypha-scheduler.rs:372-412 select loop).
        The no-PROGRESS watchdog resets on every progress message, so a
        long but steadily-reporting job is never killed."""
        assert ctx.complete is not None
        waiters: dict[asyncio.Task, tuple[str, Any]] = {}

        def add(kind: str, payload: Any, coro) -> None:
            waiters[asyncio.create_task(coro, name=kind)] = (kind, payload)

        add("complete", None, ctx.complete.wait())
        for task in tasks:
            add("status", task, self._watch_status(task))
        for handle in ctx.handles.values():
            add("worker", handle, _await_failure(handle))
        for ps_handle in ctx.ps_handles:
            if ps_handle is not None:
                add("ps-worker", ps_handle, _await_failure(ps_handle))
        loop = asyncio.get_running_loop()
        try:
            if prelude is not None:
                # Adoption aftermath (scheduler crash recovery): executions
                # whose AdoptAck never arrived enter the normal failure
                # machinery here — depart/rejoin for train workers,
                # per-shard restart for PS shards — with the same `add`
                # the loop below uses, so their replacements are watched.
                await prelude(add)
            while True:
                timeout_s = self._effective_timeout(ctx)
                last = ctx.activity[0] if ctx.activity else loop.time()
                remaining = (last + timeout_s) - loop.time()
                if remaining <= 0:
                    raise JobFailed(f"no progress in {timeout_s:.0f}s")
                done, _ = await asyncio.wait(
                    waiters,
                    timeout=min(remaining, 1.0),
                    return_when=asyncio.FIRST_COMPLETED,
                )
                if ctx.membership is not None:
                    self._poll_suspicion(ctx)
                if not done:
                    continue  # re-check the watchdog, keep waiting
                # Completion wins ties: when a worker's lease-renewal failure
                # lands in the same asyncio.wait round as job completion
                # (plausible during teardown), the job must not be reported
                # failed and re-executed.
                if any(waiters[t][0] == "complete" for t in done):
                    return
                for t in done:
                    kind, payload = waiters.pop(t)
                    if t.cancelled():
                        # A released handle's failure future was cancelled
                        # (its peer already departed via another signal).
                        continue
                    if kind == "status":
                        peer, job_id, reason = t.result()
                        if job_id in ctx.ps_job_ids:
                            self._request_ps_restart(
                                ctx, ctx.ps_job_ids.index(job_id),
                                f"{job_id} failed on {peer}: {reason}", add,
                            )
                        elif ctx.ft is None:
                            raise JobFailed(f"{job_id} failed on {peer}: {reason}")
                        else:
                            await self._depart(ctx, peer, f"{job_id}: {reason}", add)
                    elif kind == "ps-worker":
                        failure = t.result()
                        if payload not in ctx.ps_handles:
                            # A released shard handle's stale signal (its
                            # restart is already in flight on a new handle).
                            continue
                        self._request_ps_restart(
                            ctx, ctx.ps_handles.index(payload),
                            str(failure), add,
                        )
                    elif kind == "worker":
                        failure = t.result()
                        peer = getattr(failure, "peer_id", "")
                        if ctx.ft is None:
                            raise JobFailed(str(failure))
                        await self._depart(ctx, peer, str(failure), add)
                    elif kind == "ps-restart":
                        ctx.ps_restarting.discard(payload)
                        revived = t.result()
                        if revived is None:
                            raise JobFailed(
                                f"parameter server shard {payload} restart "
                                f"failed (after {ctx.ps_restarts} attempt(s))"
                            )
                        handle, task = revived
                        add("status", task, self._watch_status(task))
                        add("ps-worker", handle, _await_failure(handle))
                    elif kind == "rejoin":
                        joined = t.result()
                        if joined is not None:
                            handle, task = joined
                            add("status", task, self._watch_status(task))
                            add("worker", handle, _await_failure(handle))
                        else:
                            log.warning(
                                "rejoin gave up; continuing degraded at "
                                "%d active workers",
                                len(ctx.membership.active)
                                if ctx.membership
                                else -1,
                            )
        finally:
            for t in waiters:
                t.cancel()
            await asyncio.gather(*waiters, return_exceptions=True)

    # ----------------------------------------------------- PS crash recovery

    def _request_ps_restart(
        self, ctx: _RunContext, shard: int, reason: str, add
    ) -> None:
        """PS shard failure signal → queue a restart attempt for THAT
        shard only, or fail the attempt.

        Eligible only when the job is elastic, has ``ps_restart_attempts``
        left, and carries a checkpoint_dir — without the durable journal
        (ft.durable) a re-dispatched PS would restart the round counter
        while workers sit mid-round, which is worse than the full restart.
        A second failure signal for the same outage (lease failure + failed
        job status) folds into the in-flight attempt. The OTHER shards are
        untouched throughout: they keep closing the rounds they own while
        this one recovers.
        """
        if shard in ctx.ps_restarting:
            log.info(
                "ps shard %d failure signal during restart (%s); ignored",
                shard, reason,
            )
            return
        eligible = (
            ctx.ft is not None
            and ctx.ft.ps_restart_attempts > 0
            and ctx.ps_restarts < ctx.ft.ps_restart_attempts
            and ctx.job is not None
            and bool(ctx.job.checkpoint_dir)
            and len(ctx.ps_specs) > shard
        )
        if not eligible:
            raise JobFailed(
                f"parameter server shard {shard} failed: {reason}"
            )
        ctx.ps_restarts += 1
        ctx.ps_restarting.add(shard)
        if getattr(ctx, "dur", None) is not None:
            # Journal the spent attempt: a recovered scheduler resumes the
            # restart budget instead of handing a persistently-failing
            # shard a fresh one after every scheduler crash.
            aio.spawn(
                asyncio.to_thread(ctx.dur.note_ps_restarts, ctx.ps_restarts),
                tasks=ctx.notify_tasks,
                what="scheduler journal ps-restart",
                logger=log,
            )
        log.warning(
            "parameter server shard %d failed (%s); restart attempt %d/%d",
            shard, reason, ctx.ps_restarts, ctx.ft.ps_restart_attempts,
        )
        add("ps-restart", shard, self._restart_ps(ctx, shard))

    async def _restart_ps(
        self, ctx: _RunContext, shard: int
    ) -> tuple[WorkerHandle, Task] | None:
        """Re-auction the SAME peer and re-dispatch one shard's aggregate
        job.

        The peer id must match the failed shard's: every worker's
        updates/results reference (and the ShardMap placement) was wired
        to it at dispatch, so recovery models the process restarting on
        its host (the classic parameter-server deployment), not a
        migration. The re-dispatched job (same job id + shard tag) finds
        its durable journal under its own checkpoint_dir and resumes the
        interrupted round (ps_executor recovery path).
        """
        assert ctx.ft is not None and ctx.job is not None
        assert len(ctx.ps_specs) > shard
        failed = ctx.ps_handles[shard]
        # The planned placement names the peer even when no live handle
        # exists (a shard that died alongside the scheduler has only its
        # journal record — scheduler crash recovery's re-auction path).
        old_peer = failed.peer_id if failed is not None else (
            ctx.ps_peers[shard] if shard < len(ctx.ps_peers) else ""
        )
        if failed is not None:
            await failed.release()
            ctx.ps_handles[shard] = None
        res = ctx.job.resources
        ps_spec = WorkerSpec(
            resources=res.parameter_server,
            executor=[
                ExecutorDescriptor(
                    executor_class="aggregate", name=AGGREGATE_EXECUTOR_NAME
                )
            ],
        )
        # The restarted node needs a beat to bind + re-register before it
        # can hear the auction.
        deadline = (
            asyncio.get_running_loop().time()
            + max(ctx.ft.ps_restart_backoff_s, 0.1) * 20
        )
        attempt = 0
        while asyncio.get_running_loop().time() < deadline:
            if attempt:
                await asyncio.sleep(ctx.ft.ps_restart_backoff_s)
            attempt += 1
            try:
                offers = await self.allocator.request(
                    ps_spec, res.parameter_server_price, ctx.auction_timeout, 8
                )
            except Exception as e:
                log.warning("ps restart auction failed: %s", e)
                continue
            same = [o for o in offers if o.peer_id == old_peer]
            if not same:
                log.info(
                    "ps restart: no offer from %s yet (%d others)",
                    old_peer, len(offers),
                )
                continue
            handle: WorkerHandle | None = None
            try:
                handle = await WorkerHandle.create(self.node, same[0])
                if ctx.batch_scheduler is not None:
                    handle.round_ctx = ctx.batch_scheduler.round_ctx
                task = await Task.dispatch(
                    self.node, ctx.router, ctx.ps_specs[shard], [handle]
                )
            except asyncio.CancelledError:
                if handle is not None:
                    await handle.release()
                raise
            except (RequestError, DispatchError) as e:
                log.warning("ps shard %d restart dispatch failed: %s", shard, e)
                if handle is not None:
                    await handle.release()
                continue
            ctx.ps_handles[shard] = handle
            await self._journal_dispatch(
                ctx, ctx.ps_specs[shard].job_id, handle, "aggregate",
                shard=shard,
            )
            if ctx.membership is not None:
                # Bring the recovered shard's (checkpoint-restored) view up
                # to date, including any rejoiners it still owes catch-ups.
                self._notify_membership_soon(ctx)
            log.warning(
                "parameter server shard %d restarted on %s", shard, old_peer
            )
            return handle, task
        return None

    # ------------------------------------------------------- elastic details

    def _poll_suspicion(self, ctx: _RunContext) -> None:
        """φ threshold crossings → suspected; heartbeats again → reinstated.

        Suspicion is advisory (the PS stops *waiting* for suspected peers
        beyond quorum but still accepts their deltas); the hard departure
        signal stays the lease renewal failure / failed job status.

        Only peers that SHOULD be heartbeating are judged: a worker that
        shipped its delta (UPDATING) or finished (DONE) is protocol-silent
        while it waits on the parameter server — φ over that silence would
        suspect the whole fleet at every round boundary."""
        assert ctx.membership is not None and ctx.detector is not None
        assert ctx.tracker is not None
        changed = False
        for peer in list(ctx.membership.active):
            if peer in ctx.tracker.peers and ctx.tracker.state(peer) in (
                WorkerState.UPDATING,
                WorkerState.DONE,
            ):
                continue
            if ctx.detector.suspected(peer):
                if ctx.membership.suspect(peer):
                    FT_METRICS.suspected_peers.add(1)
                    log.warning(
                        "worker %s suspected (phi=%.1f >= %.1f)",
                        peer, ctx.detector.phi(peer), ctx.detector.threshold,
                    )
                    changed = True
            elif ctx.membership.reinstate(peer):
                log.info("worker %s re-healed (phi=%.1f)", peer, ctx.detector.phi(peer))
                changed = True
        if changed:
            self._notify_membership_soon(ctx)

    def _notify_membership_soon(self, ctx: _RunContext, joined: list[str] | None = None) -> None:
        """Fire-and-forget membership push to the PS (never blocks the
        supervision loop; a lost update is repaired by the next one).
        aio.spawn retains the task and logs/counts a failed push — the
        PR-1 form dropped the exception with the task reference."""
        aio.spawn(
            self._notify_membership(ctx, joined),
            tasks=ctx.notify_tasks,
            what="membership notify",
            logger=log,
        )

    async def _notify_membership(
        self, ctx: _RunContext, joined: list[str] | None = None
    ) -> bool:
        """Push the current membership snapshot to every PS shard; False
        when ANY shard's push failed.

        Plain suspicion/departure updates tolerate a loss (the next update
        carries the full snapshot, and the PS epoch-gates stale ones), but
        a ``joined`` notification is load-bearing: it is the only message
        that queues the rejoiner's catch-up — and a sharded job's rejoiner
        needs one catch-up from EVERY shard, so its caller must check."""
        assert ctx.membership is not None and ctx.ps_handles
        ok = True
        snapshot = ctx.membership.snapshot()
        if getattr(ctx, "dur", None) is not None:
            # Journal the epoch change BEFORE pushing it: a restarted
            # scheduler must never adopt an OLDER epoch than one the PS
            # already saw (the PS epoch-gates membership updates).
            await asyncio.to_thread(
                ctx.dur.note_member,
                {
                    "epoch": snapshot.epoch,
                    "active": list(snapshot.active),
                    "departed": list(snapshot.departed),
                },
                ctx.rejoin_count,
            )
        if getattr(ctx, "adaptive", None) is not None:
            # Publish the straggler controller's per-worker inner-step
            # assignment with the membership (RoundMembership.inner_steps,
            # epoch-tagged). None when empty: the wire stays byte-compatible
            # until the first adaptive assignment exists.
            assignments = ctx.adaptive.assignments()
            snapshot.inner_steps = assignments or None
        # Encode once per shard payload, OFF-loop (the snapshot's active
        # list is O(fleet); at N=128 serial per-shard re-encodes on the
        # event loop were the membership path's CPU), then fan the
        # requests out with bounded concurrency instead of awaiting each
        # shard in turn — the sweep's wall-clock stops scaling with the
        # shard count. The wire bytes are identical to encoding at each
        # call site (messages.PreEncoded).
        live: list[tuple[int, WorkerHandle]] = []
        for k, handle in enumerate(ctx.ps_handles):
            if handle is None:
                # Shard mid-restart: a plain snapshot loss is repaired by
                # the next (epoch-gated) update after re-dispatch, but a
                # JOINED notification is load-bearing — this shard would
                # never queue the rejoiner's catch-up and the rejoiner
                # would wait on it forever. Report failure so the rejoin
                # attempt rolls back and retries once the shard is back.
                if joined:
                    ok = False
                continue
            live.append((k, handle))
        joined_list = list(joined or [])
        updates = [
            MembershipUpdate(
                job_id=ctx.ps_job_ids[k],
                membership=snapshot,
                joined=joined_list,
            )
            for k, _ in live
        ]

        def encode_all():
            try:
                return [messages.PreEncoded.of(u) for u in updates]
            except Exception:
                # Snapshot not wire-encodable (test doubles drive this
                # path with fakes): fall back to in-request encoding.
                return updates

        payloads = await asyncio.to_thread(encode_all)

        async def push_one(k: int, handle: WorkerHandle, payload) -> bool:
            try:
                await self.node.request(
                    handle.peer_id, PROTOCOL_FT, payload, timeout=10
                )
                return True
            except RequestError as e:
                log.warning(
                    "membership update to PS shard %d failed: %s", k, e
                )
                return False

        results = await aio.gather_bounded(
            [
                (lambda k=k, h=handle, p=payload: push_one(k, h, p))
                for (k, handle), payload in zip(live, payloads)
            ],
            limit=8,
        )
        return ok and all(results)

    async def _depart(self, ctx: _RunContext, peer: str, reason: str, add) -> None:
        """A train worker is gone: degrade the round set, maybe rejoin."""
        assert ctx.membership is not None and ctx.tracker is not None
        assert ctx.ft is not None and ctx.job is not None
        if peer not in ctx.membership.active:
            return  # double signal (lease failure + failed status)
        log.warning("worker %s departed (%s); degrading round set", peer, reason)
        ctx.membership.depart(peer)
        if ctx.detector is not None:
            ctx.detector.remove(peer)
        handle = ctx.handles.pop(peer, None)
        if handle is not None:
            await handle.release()
        if peer in ctx.tracker.peers:
            ctx.tracker.remove_worker(peer)
        if ctx.data_scheduler is not None:
            ctx.data_scheduler.remove_worker(peer)
        # The job bought num_workers replicas; falling below the quorum of
        # THAT number means the round average has lost statistical meaning
        # for this job — last-resort restart (run()'s max_attempts).
        floor = quorum_size(ctx.ft.quorum_fraction, ctx.job.resources.num_workers)
        if len(ctx.membership.active) < floor:
            raise JobFailed(
                f"quorum lost: {len(ctx.membership.active)} active < {floor} "
                f"(of {ctx.job.resources.num_workers} bought)"
            )
        self._notify_membership_soon(ctx)
        if ctx.tracker.rounds_left > 1 and ctx.ft.rejoin_attempts > 0:
            departed_at = asyncio.get_running_loop().time()
            add("rejoin", peer, self._rejoin_worker(ctx, peer, departed_at))
        else:
            log.info(
                "not rejoining for %s (%d rounds left)",
                peer, ctx.tracker.rounds_left,
            )

    async def _rejoin_worker(
        self, ctx: _RunContext, departed_peer: str, departed_at: float
    ) -> tuple[WorkerHandle, Task] | None:
        """Auction a replacement and re-enter it at the next epoch.

        The replacement initializes from the model seed and catches up from
        the PS's cumulative update (ft/rejoin.py) — no job restart. Returns
        (handle, task) or None after ``rejoin_attempts`` failed tries.
        """
        assert ctx.ft is not None and ctx.job is not None
        assert ctx.membership is not None and ctx.tracker is not None
        spec_ws = self._train_worker_spec(ctx.job)
        loop = asyncio.get_running_loop()
        for attempt in range(ctx.ft.rejoin_attempts):
            if attempt:
                await asyncio.sleep(ctx.ft.rejoin_backoff_s)
            try:
                offers = await self.allocator.request(
                    spec_ws,
                    ctx.job.resources.worker_price,
                    ctx.auction_timeout,
                    len(ctx.membership.active) + 1,
                )
            except Exception as e:
                log.warning("rejoin auction failed: %s", e)
                continue
            candidates = [
                o for o in offers if o.peer_id not in ctx.membership.active
            ]
            if not candidates:
                log.info(
                    "rejoin %d/%d: no fresh offers (got %d)",
                    attempt + 1, ctx.ft.rejoin_attempts, len(offers),
                )
                continue
            offer = candidates[0]
            peer = offer.peer_id
            handle: WorkerHandle | None = None
            added = False
            try:
                handle = await WorkerHandle.create(self.node, offer)
                handle.batch_size = self.batch_size_for(
                    offer.resources, ctx.job.resources.worker,
                    ctx.job.rounds.max_batch_size,
                )
                if ctx.detector is not None:
                    handle.on_renew = ctx.detector.heartbeat
                if ctx.batch_scheduler is not None:
                    handle.round_ctx = ctx.batch_scheduler.round_ctx
                # Tracker + membership BEFORE dispatch: the worker's first
                # Status must find it tracked, and the PS must have queued
                # its catch-up before the executor starts waiting for it.
                ctx.tracker.add_worker(peer, handle.batch_size)
                ctx.membership.join(peer)
                added = True
                if not await self._notify_membership(ctx, joined=[peer]):
                    # Without this update the PS never sends the catch-up
                    # and the dispatched worker would block forever while
                    # holding a tracker slot that must reach DONE.
                    raise RequestError("join notification to PS failed")
                spec = self._train_spec(
                    ctx, f"r{ctx.rejoin_count}", handle, rejoin=True
                )
                task = await Task.dispatch(self.node, ctx.router, spec, [handle])
            except asyncio.CancelledError:
                # Supervision ended mid-rejoin (completion / attempt
                # failure): a leaked handle would renew the lease forever,
                # pinning the worker's capacity.
                await self._rollback_rejoin(ctx, peer, handle, added)
                raise
            except (RequestError, DispatchError) as e:
                log.warning("rejoin: attempt with %s failed: %s", peer, e)
                await self._rollback_rejoin(ctx, peer, handle, added)
                continue
            ctx.handles[peer] = handle
            ctx.rejoin_count += 1
            await self._journal_dispatch(ctx, spec.job_id, handle, "train")
            latency_ms = (loop.time() - departed_at) * 1000.0
            FT_METRICS.rejoins.add(1)
            FT_METRICS.rejoin_latency_ms.record(latency_ms)
            log.info(
                "worker %s rejoined for %s at epoch %d (%.0f ms after departure)",
                peer, departed_peer, ctx.membership.epoch, latency_ms,
            )
            return handle, task
        return None

    async def _rollback_rejoin(
        self,
        ctx: _RunContext,
        peer: str,
        handle: WorkerHandle | None,
        added: bool,
    ) -> None:
        """Undo a half-done rejoin attempt (failed or cancelled)."""
        if added:
            assert ctx.tracker is not None and ctx.membership is not None
            if peer in ctx.tracker.peers:
                ctx.tracker.remove_worker(peer)
            ctx.membership.depart(peer)
            self._notify_membership_soon(ctx)
        if handle is not None:
            await handle.release()


async def _await_failure(handle: WorkerHandle):
    return await asyncio.shield(handle.failed)
