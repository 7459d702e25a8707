"""The training executor: the DiLoCo inner loop on JAX, driven by the bridge.

Parity with the reference's accelerate executor
(executors/accelerate/src/hypha/accelerate_executor/training.py:28-147):

  * parse the job spec, open a bridge Session, fetch model artifacts;
  * build model / AdamW / LR schedule / streaming slice dataset;
  * snapshot the round anchor θ₀ (the reference's ``0_global_weights.pt``);
  * loop: jitted train step → per-batch ``Status`` heartbeat → on
    ``ScheduleUpdate{counter}`` run ``counter`` more batches → send
    ``update`` status → save Δθ = θ_t − θ₀ SafeTensors → ship to the
    parameter server (tagged with the round's sample count for the
    weighted mean) → send round metrics → await the broadcast update →
    merge (θ ← θ + update) → ``update-received`` → Continue | Done.

TPU-native differences: the whole inner step is ONE jit-compiled function
(forward+loss+backward+AdamW fused by XLA, bf16 activations on the MXU);
optional intra-replica sharding lays the step out over a device mesh
(dp/fsdp/tp/sp/ep axes) so collectives ride ICI; Δθ extraction and the
merge are jitted tree ops (hypha_tpu.executor.diloco).

Launch (the worker's process executor substitutes the placeholders —
crates/worker/src/executor/process.rs:124-137):

    python -m hypha_tpu.executor.training \
        --socket {SOCKET_PATH} --work-dir {WORK_DIR} --job {JOB_JSON}
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import math
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import numpy as np

from .. import messages
from ..messages import (
    CODEC_KEY,
    SHARD_KEY,
    TRACEPARENT_KEY,
    FragmentTag,
    JobSpec,
    Loss,
    ModelType,
    Progress,
    ProgressKind,
    ProgressResponse,
    ProgressResponseKind,
    TrainExecutorConfig,
)
from .. import compress
from ..ft.durable import RESYNC_KEY, restart_signal, stale_scheduler_response
from ..ft.rejoin import CATCHUP_KEY
from ..stream import SYNC_MODES, effective_fragments, fragment_due, merge_corrected
from ..stream.accum import SumBuffers
from ..stream.partition import partition_names, shard_of
from ..worker.connectors import claim_spare, shard_route
from ..telemetry import trace
from ..telemetry.ft_metrics import (
    DATA_METRICS,
    FT_METRICS,
    HET_METRICS,
    STREAM_METRICS,
)
from .diloco import (
    apply_updates,
    extract_delta,
    merge_update,
    merge_update_f32,
    relaid,
)
from .serialization import flat_leaf_map, flatten_tree, replace_leaves, unflatten_like
from .train import (
    ROUTING_FIELDS,
    TrainState,
    aux_fields,
    build_optimizer,
    make_chunked_train_step,
    make_routed_train_step,
    make_train_step,
)

__all__ = ["run_training", "main", "TrainResult"]

log = logging.getLogger("hypha.executor.training")

# Multihost liveness bound: a lost follower process leaves the leader's
# cross-process collectives (and therefore the loss fetch) blocked forever
# — jax.distributed's own heartbeat detection is minutes away and may hard-
# kill the process instead of failing the job. Any collective-bearing phase
# exceeding this raises, so the bridge reports a clean job failure the
# scheduler can re-auction. Overridable for tests / long compiles.
_MH_STEP_TIMEOUT_ENV = "HYPHA_MULTIHOST_STEP_TIMEOUT"
_MH_STEP_TIMEOUT_DEFAULT = 600.0
# The FIRST dispatch of each jitted multihost program compiles on every
# process — minutes at 7B scale — so the liveness bound only tightens once
# a program has run end-to-end at least once.
_MH_COMPILE_GRACE_ENV = "HYPHA_MULTIHOST_COMPILE_GRACE"
_MH_COMPILE_GRACE_DEFAULT = 1800.0


def _with_deadline(fn: Callable[[], Any], timeout: float, what: str):
    """Run ``fn`` in a daemon thread with a wall-clock bound.

    On timeout the worker thread is abandoned (a thread blocked inside a
    collective cannot be cancelled) and the caller raises — the executor
    process is about to exit over the bridge's failure path anyway, and a
    daemon thread cannot keep it alive.
    """
    import threading

    box: dict[str, Any] = {}

    def work() -> None:
        try:
            box["value"] = fn()
        except BaseException as e:  # hypha-lint: disable=swallowed-cancel
            box["error"] = e  # thread-bridge: re-raised on the caller thread

    t = threading.Thread(target=work, daemon=True, name="mh-step")
    t.start()
    t.join(timeout)
    if t.is_alive():
        raise RuntimeError(
            f"multihost {what} did not complete within {timeout:.0f}s — "
            "follower process lost? (job fails instead of hanging; "
            f"tune ${_MH_STEP_TIMEOUT_ENV})"
        )
    if "error" in box:
        raise box["error"]
    return box["value"]


def _mh_done_bounded(mh) -> None:
    """Best-effort OP_DONE: with a follower already dead, the done
    broadcast itself blocks — never let the cleanup path hang the job."""
    try:
        _with_deadline(mh.done, 30.0, "done broadcast")
    except Exception as e:
        log.warning("multihost done broadcast failed: %s", e)

def _non_causal_types():
    from ..models.heads import HEAD_TYPES

    return {
        ModelType.IMAGE_CLASSIFICATION,
        ModelType.SEQUENCE_CLASSIFICATION,
        ModelType.TOKEN_CLASSIFICATION,
    } | HEAD_TYPES


# Streaming sync poll wait (seconds): how long the inner loop blocks on the
# in-flight sync before each batch. 0 (default) = pure overlap — never wait,
# keep stepping. Positive values degrade toward blocking semantics; tests
# use a large value to pin "zero flight drift == blocking bit-exactly".
_STREAM_POLL_WAIT_ENV = "HYPHA_STREAM_POLL_WAIT"


class _RoundTrace:
    """Worker-side round-trace bookkeeping (every method no-ops when
    tracing is off — call sites never branch on config).

    The scheduler's per-round root context arrives on SCHEDULE_UPDATE /
    Continue responses (:class:`~hypha_tpu.messages.ProgressResponse.
    traceparent`); the worker parents its ``inner_steps`` / ``encode`` /
    ``upload`` / ``merge`` spans under it, stamps it into delta push
    headers so the parameter server's spans join the same trace, and
    attaches it to its round-tagged Progress messages.
    """

    def __init__(self, node: str | None) -> None:
        self.node = node
        self.tp: str | None = None  # the round context last handed down
        self.tp_round = -1
        self.inner: "trace.TraceSpan | None" = None
        self.inner_round = -1

    @property
    def on(self) -> bool:
        return trace.active() is not None

    def adopt(self, resp, round_num: int) -> None:
        """Record the context a scheduler response handed down."""
        tp = getattr(resp, "traceparent", None)
        if tp:
            self.tp, self.tp_round = tp, round_num

    def ctx(self, round_num: int) -> str | None:
        """The context for ``round_num`` (None when off / not yet seen)."""
        return self.tp if self.tp_round == round_num else None

    def stamp(self, meta: dict, round_num: int) -> dict:
        """Inject the round context into a push header (no-op when off)."""
        return trace.inject(meta, self.ctx(round_num))

    def clock_mark(self, round_num: int) -> None:
        """One moment on three clocks: a zero-length profiler annotation
        ``hypha_clock wall_ns=… mono_ns=…`` (a flag check while no profiler
        session is open) whose name carries the two clocks the spans are
        written in, read immediately before it is entered, and an instant
        span ``clock_mark`` with the same two numbers. A profiler trace that
        holds one gives the offset between the device's time base and the
        spans' clocks to the cost of one call."""
        if not self.on:
            return
        import jax

        wall_ns, mono_ns = time.time_ns(), time.monotonic_ns()
        with jax.profiler.TraceAnnotation(
            f"hypha_clock wall_ns={wall_ns} mono_ns={mono_ns}"
        ):
            pass
        trace.instant(
            "clock_mark", parent=self.ctx(round_num), node=self.node,
            attrs={"round": round_num, "wall_ns": wall_ns, "mono_ns": mono_ns},
        )

    def batch(self, round_num: int) -> None:
        """First batch of a round opens its ``inner_steps`` span."""
        if not self.on:
            return
        if self.inner is None or self.inner_round != round_num:
            self.close_inner()
            self.clock_mark(round_num)
            self.inner = trace.begin(
                "inner_steps",
                parent=self.ctx(round_num),
                attrs={"round": round_num},
                node=self.node,
            )
            self.inner_round = round_num

    def close_inner(self) -> None:
        if self.inner is not None:
            trace.finish(self.inner)
            self.inner = None


class _WorkerStream:
    """Worker-side streaming outer sync: at most ONE fragment in flight.

    ``begin`` snapshots the due fragment (θ_s), extracts Δ = θ_s − anchor
    and hands encode → upload → await-broadcast to a daemon thread while
    the inner loop keeps stepping; ``poll``/``finish`` (main thread) apply
    the delayed-update correction when the broadcast lands:

        θ ← θ_l + u          (live params keep the in-flight drift)
        anchor ← θ_s + u     (anchor excludes it → next Δ ships the drift)

    Updates for fragments NOT in flight (broadcasts this worker missed or
    that raced ahead) are absorbed into params AND anchor — leaving
    Δ = θ − anchor untouched, because an outer update is not local
    progress. That rule keeps the worker live across lost broadcasts, the
    failure the blocking path tolerates by merging whatever file arrives
    next.

    Error feedback is per fragment: ErrorFeedback.absorb replaces the
    whole residual tree, so one shared instance would drop every other
    fragment's residual each sync.
    """

    def __init__(
        self, session, cfg, work_dir: Path, sync_mode: str, wire_codec: str,
        rtrace: "_RoundTrace | None" = None,
    ) -> None:
        self.session = session
        self.cfg = cfg
        self.work_dir = Path(work_dir)
        self.codec = wire_codec
        self.rtrace = rtrace
        self.F = effective_fragments(
            sync_mode, int(getattr(cfg, "fragments", 0) or 0)
        )
        self.fragments: list[tuple[str, ...]] | None = None
        self.efs = [
            compress.ErrorFeedback()
            if wire_codec in compress.QUANT_CODECS
            else None
            for _ in range(self.F)
        ]
        self.flight: dict[str, Any] | None = None
        self.poll_wait_s = float(
            os.environ.get(_STREAM_POLL_WAIT_ENV, "0") or 0.0
        )
        # Last PS generation observed on the results stream, PER shard
        # (flight-thread confined): a change means that parameter-server
        # shard restarted and an in-flight delta it owned may have died
        # unjournaled — re-send it. The unsharded PS is shard 0.
        self._gens: dict[int, Any] = {}
        # Sharded parameter service: the placement map this worker routes
        # each fragment's push by (None = single PS, the pre-shard wire).
        shard_map = getattr(cfg, "ps_shards", None)
        if shard_map is not None and not getattr(shard_map, "shards", None):
            shard_map = None
        self.shard_map = shard_map
        self.reduce_via = getattr(cfg, "reduce_via", None)

    @property
    def in_flight(self) -> bool:
        return self.flight is not None

    # ------------------------------------------------------------- begin

    def begin(self, round_num: int, params, anchor, num_samples: float) -> None:
        """Snapshot + extract the due fragment; spawn the flight thread."""
        import jax
        import jax.numpy as jnp

        if self.flight is not None:
            raise RuntimeError(
                "stream sync scheduled while a fragment is still in flight"
            )
        anchor_flat = flat_leaf_map(anchor)
        if self.fragments is None:
            # Deterministic by (name, size) only — the parameter server
            # derives the identical partition from the delta frames.
            self.fragments = partition_names(
                {n: int(leaf.size) for n, leaf in anchor_flat.items()}, self.F
            )
        frag = fragment_due(round_num, self.F)
        owner = (
            shard_of(frag, len(self.shard_map.shards))
            if self.shard_map is not None
            else 0
        )
        names = self.fragments[frag]
        params_flat = flat_leaf_map(params)
        # Deep copy, not an alias: the jitted step donates its input state,
        # so live buffers die on the next inner step.
        snap = {n: jnp.copy(params_flat[n]) for n in names}
        delta = extract_delta(snap, {n: anchor_flat[n] for n in names})
        host_delta = jax.device_get(delta)
        tag = FragmentTag(round=round_num, fragment_id=frag, fragments=self.F)
        flight: dict[str, Any] = {
            "round": round_num,
            "frag": frag,
            "owner": owner,
            "names": names,
            "snap": snap,
            "path": self.work_dir / f"delta-{round_num}-f{frag}.safetensors",
            "box": {"absorbed": []},
            "t0": time.monotonic(),
            "compute_s": 0.0,
            "bytes": 0,
            "samples": float(num_samples),
            # Round-trace context at flight launch, carried into the
            # flight thread's encode/upload spans and push headers.
            "tp": (
                self.rtrace.ctx(round_num)
                if self.rtrace is not None
                else None
            ),
        }
        thread = threading.Thread(
            target=self._flight_main,
            args=(flight, host_delta, tag, float(num_samples)),
            daemon=True,
            name=f"stream-sync-r{round_num}",
        )
        flight["thread"] = thread
        self.flight = flight
        thread.start()

    # ----------------------------------------------------- flight thread

    def _flight_main(
        self, flight: dict, host_delta: dict, tag: FragmentTag, samples: float
    ) -> None:
        box = flight["box"]
        tnode = self.rtrace.node if self.rtrace is not None else None
        try:
            # host_delta is already wire-flat: {stable_name: np.ndarray}.
            with trace.span(
                "encode", parent=flight["tp"],
                attrs={
                    "round": flight["round"], "fragment": flight["frag"],
                    "codec": self.codec,
                },
                node=tnode,
            ):
                compress.write_delta(
                    flight["path"],
                    host_delta,
                    self.codec,
                    ef=self.efs[flight["frag"]],
                    tag=tag.header(),
                )
            nbytes = flight["path"].stat().st_size
            flight["bytes"] = nbytes
            STREAM_METRICS.flight_started(nbytes)
            with trace.span(
                "upload", parent=flight["tp"],
                attrs={
                    "round": flight["round"], "fragment": flight["frag"],
                    "bytes": nbytes,
                },
                node=tnode,
            ):
                self._send_flight(flight, tag, samples)
            box["completion"] = self._await_broadcast(flight)
        except BaseException as e:  # hypha-lint: disable=swallowed-cancel
            box["error"] = e  # thread-bridge: re-raised at finish()
        finally:
            # Success or failure, this thread is done with the wire —
            # release the gauge here so an errored/abandoned flight can
            # never read as mid-upload for the rest of the process.
            STREAM_METRICS.flight_landed(flight["bytes"])

    def _send_flight(
        self, flight: dict, tag: FragmentTag, samples: float
    ) -> None:
        """Ship the flight's wire file — to the single PS, or routed to
        the fragment's owning shard (via the group reducer with ANY
        failover when tree-reduce is on)."""
        meta: dict[str, Any] = {"num_samples": samples, **tag.header()}
        trace.inject(meta, flight.get("tp"))
        if self.shard_map is None:
            self.session.send_resource(
                self.cfg.updates,
                flight["path"].name,
                resource=self.cfg.updates.ref.resource or "updates",
                meta=meta,
            )
            return
        send, owner, res_tag = shard_route(
            self.shard_map, flight["frag"], self.reduce_via
        )
        if len(self.shard_map.shards) > 1:
            meta[SHARD_KEY] = owner
        self.session.send_resource(
            send, flight["path"].name, resource=res_tag, meta=meta
        )

    def _resend(self, flight: dict) -> None:
        """The PS (shard) restarted: our un-acknowledged fragment delta may
        have died with it unjournaled — re-push the wire file (the PS's
        journal dedup makes the copy idempotent when the original DID
        land)."""
        if not flight["path"].is_file():
            return
        tag = FragmentTag(
            round=flight["round"], fragment_id=flight["frag"], fragments=self.F
        )
        log.warning(
            "stream sync: ps restart detected; re-sending round %d fragment %d",
            flight["round"], flight["frag"],
        )
        self._send_flight(flight, tag, flight["samples"])

    def _await_broadcast(self, flight: dict) -> dict:
        """Consume results-stream events until OUR fragment's update lands.

        Other fragments' updates are recorded for the main thread's absorb
        pass; stale rebroadcasts of our fragment are dropped. A LATER
        round of our fragment completes the flight too (our round's
        broadcast was lost — waiting for it would hang the worker where
        blocking mode's merge-whatever-arrives keeps going). A PS
        generation change (or an explicit resync announcement) re-sends
        the in-flight delta — the restart may have lost it.
        """
        with self.session.receive(self.cfg.results) as events:
            for event in events:
                meta = event.get("meta") or {}
                try:
                    shard_id = int(meta.get(SHARD_KEY, 0))
                except (TypeError, ValueError):
                    shard_id = 0
                self._gens[shard_id], resend = restart_signal(
                    meta, self._gens.get(shard_id)
                )
                if resend and shard_id == flight.get("owner", 0):
                    # Only the restarted shard's own in-flight part can
                    # have died unjournaled; re-sending to the healthy
                    # shards would just churn their journals' dedup.
                    self._resend(flight)
                if meta.get(RESYNC_KEY):
                    (self.work_dir / event["path"]).unlink(missing_ok=True)
                    continue
                if meta.get(CATCHUP_KEY):
                    # Catch-ups target rejoiners; their content is folded
                    # into every later broadcast — drop defensively.
                    (self.work_dir / event["path"]).unlink(missing_ok=True)
                    continue
                etag = FragmentTag.from_header(meta)
                try:
                    eround = int(meta.get("round", flight["round"]))
                except (TypeError, ValueError):
                    eround = flight["round"]
                if eround < flight["round"]:
                    # Stale for ANY fragment, ours or not: the worker only
                    # ships round r after merging every round < r, so an
                    # older broadcast (a redelivery, or a round already
                    # folded into this worker's rejoin catch-up) is applied
                    # state — absorbing it would double-apply the update.
                    (self.work_dir / event["path"]).unlink(missing_ok=True)
                    continue
                if etag is not None and etag.fragment_id != flight["frag"]:
                    # A FUTURE round's other fragment (the quorum PS ran
                    # ahead without us): genuinely unseen — absorb.
                    flight["box"]["absorbed"].append(event)
                    continue
                if eround > flight["round"]:
                    log.warning(
                        "stream sync: round %d broadcast lost; completing "
                        "with round %d's", flight["round"], eround,
                    )
                return event
        raise RuntimeError(
            "results stream ended before the fragment's update broadcast"
        )

    # ---------------------------------------------------------- progress

    def poll(self) -> bool:
        """True when the in-flight sync is ready to finish (non-blocking
        unless $HYPHA_STREAM_POLL_WAIT asks to degrade toward blocking)."""
        flight = self.flight
        if flight is None:
            return False
        if self.poll_wait_s > 0:
            flight["thread"].join(self.poll_wait_s)
        return not flight["thread"].is_alive()

    def note_compute(self, seconds: float) -> None:
        """One inner step ran while the sync was in flight (overlap win)."""
        if self.flight is not None:
            self.flight["compute_s"] += seconds

    # ------------------------------------------------------------ finish

    def finish(self, params, anchor):
        """Apply the landed broadcast; returns (params, anchor) trees."""
        flight = self.flight
        assert flight is not None
        self.flight = None
        flight["thread"].join()
        box = flight["box"]
        if "error" in box:
            flight["path"].unlink(missing_ok=True)
            raise box["error"]
        for event in box["absorbed"]:
            params, anchor = self._absorb(event, params, anchor)
        event = box["completion"]
        meta = event.get("meta") or {}
        merge_span = trace.begin(
            "merge",
            parent=meta.get(TRACEPARENT_KEY) or flight.get("tp"),
            attrs={"round": flight["round"], "fragment": flight["frag"]},
            node=self.rtrace.node if self.rtrace is not None else None,
        )
        update_file = self.work_dir / event["path"]
        flat = compress.read_delta(update_file)
        names = flight["names"]
        if set(flat) != set(names):
            raise ValueError(
                f"fragment {flight['frag']} partition mismatch: update "
                f"carries {sorted(flat)}, worker expects {sorted(names)}"
            )
        params_flat = flat_leaf_map(params)
        new_live, new_anchor = merge_corrected(
            {n: params_flat[n] for n in names}, flight["snap"], flat
        )
        params = replace_leaves(params, new_live)
        anchor = replace_leaves(anchor, new_anchor)
        trace.finish(merge_span)
        update_file.unlink(missing_ok=True)
        flight["path"].unlink(missing_ok=True)
        STREAM_METRICS.flight_finished(
            time.monotonic() - flight["t0"], flight["compute_s"]
        )
        return params, anchor

    def _absorb(self, event: dict, params, anchor):
        """θ_q ← θ_q + u AND anchor_q ← anchor_q + u for a fragment not in
        flight: Δ_q = θ_q − anchor_q is unchanged, because an outer update
        is global progress, not this worker's."""
        update_file = self.work_dir / event["path"]
        flat = compress.read_delta(update_file)
        params_flat = flat_leaf_map(params)
        anchor_flat = flat_leaf_map(anchor)
        unknown = set(flat) - set(params_flat)
        if unknown:
            raise ValueError(
                f"broadcast update names unknown tensors: {sorted(unknown)}"
            )
        new_live = merge_update({n: params_flat[n] for n in flat}, flat)
        new_anchor = merge_update({n: anchor_flat[n] for n in flat}, flat)
        update_file.unlink(missing_ok=True)
        return (
            replace_leaves(params, new_live),
            replace_leaves(anchor, new_anchor),
        )

    def abort(self) -> None:
        """Loop is exiting with a sync still out: bounded join, then
        abandon the daemon thread (the bridge teardown severs its SSE)."""
        flight = self.flight
        self.flight = None
        if flight is None:
            return
        flight["thread"].join(5.0)
        if flight["thread"].is_alive():
            log.warning(
                "stream sync round %d abandoned (broadcast never landed)",
                flight["round"],
            )
            return
        flight["path"].unlink(missing_ok=True)


class TrainResult:
    """What the loop did — surfaced for tests and the in-process executor."""

    def __init__(self) -> None:
        self.rounds = 0
        self.batches = 0
        self.losses: list[float] = []

    @property
    def last_loss(self) -> float:
        return self.losses[-1] if self.losses else math.nan


def _build_mesh(sharding: dict | None):
    """Optional intra-replica mesh (TrainExecutorConfig.sharding extension)."""
    if not sharding:
        return None
    import jax

    from ..parallel import create_mesh

    sizes = {a: int(sharding.get(a, 1)) for a in ("dp", "fsdp", "tp", "sp", "ep")}
    total = math.prod(sizes.values())
    if total <= 1:
        return None
    if total > len(jax.devices()):
        raise ValueError(
            f"sharding {sharding} needs {total} devices, "
            f"this process sees {len(jax.devices())}"
        )
    return create_mesh(sizes)


def _init_model(cfg: TrainExecutorConfig, session, work_dir: Path, first_batch):
    """Build the model and its initial params (fetched weights or seeded)."""
    import jax

    from ..models import Mixtral, build_model
    from ..models.registry import resolve_model_type

    model_spec = dict(cfg.model)
    if cfg.lora:
        # Adapter-only fine-tuning: inject the LoRA fields into the model
        # config (the Llama family's _proj picks them up). The LlamaConfig
        # constructor validates rank/targets; unsupported families have no
        # lora_rank field and fail loudly in their config constructor.
        model_spec["config"] = dict(
            model_spec.get("config", {}),
            lora_rank=int(cfg.lora.get("rank", 8)),
            lora_alpha=float(cfg.lora.get("alpha", 16.0)),
            lora_targets=tuple(cfg.lora.get("targets", ("q_proj", "v_proj"))),
        )
    # On TPU the pluggable-attention families run the pallas flash kernel by
    # default (sequence-parallel jobs swap in the ring kernel instead, via
    # _build_mesh), compiled — interpret=False, so a kernel Mosaic refuses
    # fails the job instead of running interpreted. Off-TPU: XLA dense.
    attn_impl = None
    from ..hw import is_accelerator

    if is_accelerator() and not cfg.sharding:
        import functools

        from ..ops.flash_attention import flash_attention

        attn_impl = functools.partial(flash_attention, interpret=False)
        log.info(
            "attention path: pallas flash kernel, compiled (backend=%s)",
            jax.default_backend(),
        )
    else:
        log.info("attention path: XLA dense (backend=%s)", jax.default_backend())

    source = model_spec.get("source")
    if model_spec.get("family") == "hf" and source is not None and not model_spec.get("path"):
        # The hf family loads weights via from_pretrained, so the checkpoint
        # dir must exist BEFORE the model is built (the native families
        # init-then-overwrite below instead).
        fetch = messages.from_json_dict(source) if isinstance(source, dict) else source
        rels = session.fetch(fetch)
        cfg_file = next((r for r in rels if r.endswith("config.json")), None)
        model_spec["path"] = str(
            (work_dir / cfg_file).parent if cfg_file else work_dir
        )
        source = None  # weights are loaded by the builder; skip the overwrite

    model, _mcfg = build_model(model_spec, attn_impl)
    kinds = getattr(_mcfg, "layer_types", None)
    if isinstance(kinds, (list, tuple)) and kinds:
        # A stack of more than one kind of layer says what it holds. The
        # fallbacks' configurations are a library's: no field is taken for granted.
        kinds = list(kinds)
        sizes = {k: getattr(_mcfg, k, None) for k in (
            "head_dim", "value_dim", "scan_chunk", "ssd_chunk", "expert_form",
            "index_heads", "index_head_dim", "index_topk", "router")}
        log.info(
            "operators: %s%s",
            " ".join(f"{k}={kinds.count(k)}" for k in dict.fromkeys(kinds)),
            "".join(f" {k}={v}" for k, v in sizes.items() if isinstance(v, (int, str))),
        )
    model_type = resolve_model_type(model_spec.get("model_type", ModelType.CAUSAL_LM))
    causal_lm = model_type not in _non_causal_types()
    has_aux = isinstance(model, Mixtral)

    inputs = first_batch["input_ids"] if "input_ids" in first_batch else first_batch["inputs"]
    seed = int(model_spec.get("seed", 0))
    params = model.init(jax.random.key(seed), inputs)

    if source is not None:
        fetch = messages.from_json_dict(source) if isinstance(source, dict) else source
        rels = session.fetch(fetch)
        weight_files = [
            r for r in rels if r.endswith((".safetensors", ".bin", ".pt", ".pth"))
        ]
        if weight_files:
            from ..models.convert import convert_state_dict, load_checkpoint_files

            state = load_checkpoint_files([work_dir / r for r in weight_files])
            target = params
            if cfg.lora:
                # Checkpoints carry the BASE weights only; adapters keep
                # their seed init (B=0 -> exact base behavior at step 0).
                from .lora import merge_lora, split_lora

                adapters_t, target = split_lora(params)
            try:
                # Native flat names (our own checkpoints/exports)…
                loaded = unflatten_like(state, target)
            except KeyError:
                # …or an HF-format state dict for this family.
                family = model_spec.get("family", "gpt2")
                loaded = convert_state_dict(family, state, target)
            params = merge_lora(adapters_t, loaded) if cfg.lora else loaded
            log.info("loaded %d initial tensors from %s", len(state), weight_files)
    return model, params, causal_lm, has_aux


def adopt_schedule(resp: ProgressResponse, countdown: "int | None") -> "int | None":
    """Adopt a SCHEDULE_UPDATE's counter — idempotently.

    A countdown already in progress stands: a restarted scheduler that
    re-adopted this execution mid-round has a tracker that forgot the
    first issue and re-schedules on the next Status, but re-adopting its
    counter would re-run (or skip) inner steps the round already
    accounted. Only a worker with NO active countdown (round start, or
    just merged) takes the counter.
    """
    if resp.kind != ProgressResponseKind.SCHEDULE_UPDATE:
        return countdown
    if countdown is None:
        return resp.counter
    return countdown


def _tree_nbytes(tree) -> int:
    """Bytes of a host tree's leaves (a span attribute: rate = bytes / s)."""
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def run_training(
    session,
    work_dir: Path | str,
    spec: JobSpec,
    *,
    max_batches: int | None = None,
    should_stop: Callable[[], bool] | None = None,
    trace_node: str | None = None,
) -> TrainResult:
    """Run the DiLoCo inner loop to completion over the given bridge session.

    ``session`` implements the bridge client API (fetch / send_resource /
    send_status / receive — hypha_tpu.executor.bridge_client.Session).
    ``max_batches`` is a safety valve for tests. ``should_stop`` is polled
    between batches — the in-process executor's cooperative cancellation.
    ``trace_node`` labels this worker's round-trace spans (telemetry.trace;
    the in-process executor passes its peer id, a subprocess executor the
    ``--trace-node`` its worker handed it) — ignored while tracing is off.
    """
    import jax
    import jax.numpy as jnp

    t_enter = time.monotonic()
    work_dir = Path(work_dir)
    cfg = spec.executor.train
    if cfg is None:
        raise ValueError(f"job {spec.job_id} is not a train job")

    from .dataset import stream_batches

    def fetch_slice() -> str:
        t0 = time.monotonic()
        rels = session.fetch(cfg.data)
        path = work_dir / rels[0]
        DATA_METRICS.note_fetch(time.monotonic() - t0)
        return str(path)

    # End-to-end round tracing (telemetry.trace): all no-ops when off.
    # Created before the stream so input_wait spans can join round traces.
    rtrace = _RoundTrace(trace_node)

    def input_span_ctx():
        # The most recent round context handed down by the scheduler —
        # good enough to attribute a mid-round input stall to its round.
        return rtrace.tp, rtrace.node, rtrace.tp_round if rtrace.tp else None

    model_spec = dict(cfg.model)
    input_names = model_spec.get("input_names")
    preprocessor = None
    if cfg.preprocessor:
        from .preprocess import build_preprocessor

        preprocessor = build_preprocessor(cfg.preprocessor, session, work_dir)
    # Async input pipeline (executor.dataset, ISSUE 15): slice prefetch +
    # zero-copy assembly + the deferred device sync below. None/False (the
    # default) takes the original synchronous path, bit-identically.
    pipeline_on = bool(getattr(cfg, "input_pipeline", None))
    stream = stream_batches(
        fetch_slice, cfg.batch_size, input_names, preprocessor,
        pipeline=pipeline_on,
        prefetch=getattr(cfg, "prefetch_slices", None),
        span_ctx=input_span_ctx,
        unlink_consumed=pipeline_on,
    )

    first_batch = next(stream)
    # The process that runs the step says which device it has
    # (chip_smoke.py reports this line, not its own view).
    devices = jax.devices()
    log.info(
        "device: platform=%s kind=%r count=%d",
        devices[0].platform, devices[0].device_kind, len(devices),
    )
    model, params, causal_lm, has_aux = _init_model(cfg, session, work_dir, first_batch)
    mesh = _build_mesh(cfg.sharding)

    # LoRA jobs train (ship, checkpoint, merge) the ADAPTER tree only; the
    # frozen base rides along as a constant input to every step.
    frozen = None
    if cfg.lora:
        from .lora import split_lora

        adapters, frozen = split_lora(params)
        if not jax.tree_util.tree_leaves(adapters):
            raise ValueError(
                f"job {spec.job_id}: lora={cfg.lora!r} produced no adapters "
                f"(family {dict(cfg.model).get('family')!r})"
            )
        params = adapters

    # A model may keep variable collections beside ``params`` that its own
    # step updates (a routed model's selection bias, ``moe_state``): they ride in
    # ``state.extras``, outside the gradient, AdamW and the pseudo-gradient
    # (anchor, delta and merge below only ever see ``state.params``). They are
    # worker-local and, today, not part of a train checkpoint.
    extras = None
    if isinstance(params, dict) and set(params) - {"params"}:
        extras = {k: v for k, v in params.items() if k != "params"}
        params = {"params": params["params"]}
        if cfg.lora or cfg.sharding:
            raise ValueError(
                f"job {spec.job_id}: a model with {sorted(extras)} state runs "
                "unsharded and without LoRA"
            )

    tx = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = TrainState.create(params, tx, extras)

    # Resume (net-new vs reference): a re-dispatched executor picks up the
    # last completed round's params + optimizer state instead of θ₀.
    ckpt_dir = None
    ckpt_every = 1
    round_offset = 0  # completed rounds restored from a checkpoint
    if cfg.checkpoint and cfg.checkpoint.get("dir"):
        from .checkpoint import load_train_checkpoint, save_train_checkpoint

        ckpt_every = int(cfg.checkpoint.get("every_rounds", 1))
        if ckpt_every > 0:  # <= 0 disables checkpointing
            ckpt_dir = Path(cfg.checkpoint["dir"])
            restored = load_train_checkpoint(ckpt_dir, state.params, state.opt_state)
            if restored is not None:
                r_params, r_opt, r_step, r_round, _extra = restored
                state = state.replace(
                    params=r_params, opt_state=r_opt, step=jnp.int32(r_step)
                )
                round_offset = r_round
                log.info(
                    "resumed from %s: step %d, %d completed rounds",
                    ckpt_dir, r_step, r_round,
                )

    # Multi-process replica (pod-as-one-replica): process 0 — this loop —
    # owns the control plane and broadcasts each collective-bearing action
    # so follower processes (executor.multihost_coord.run_training_follower)
    # mirror the dispatches over the same global mesh. The init broadcast
    # runs BEFORE mesh placement: it device_gets the state, which must
    # still be host/single-device arrays (global arrays spanning another
    # process cannot be fetched locally).
    mh = None
    host_anchor = None
    if jax.process_count() > 1:
        if mesh is None:
            # Fail fast HERE: the follower asserts a mesh exists, and a
            # leader training unsharded while followers expect lockstep
            # dispatches would deadlock on the first step broadcast.
            raise ValueError(
                f"job {spec.job_id}: {jax.process_count()} processes need a "
                f"sharding config spanning all {len(jax.devices())} global "
                f"devices; got {cfg.sharding!r}"
            )
        from .multihost_coord import LeaderCoordination

        mh = LeaderCoordination()
        mh.init(
            json.dumps(messages.to_json_dict(spec)), state, first_batch,
            frozen=frozen,
        )
        # θ₀ on the HOST, captured while state is still host/single-device
        # arrays: cross-process meshes shard params onto devices this
        # process cannot address, so a device anchor would be unreadable at
        # delta time (refreshed each round from the OP_GATHER allgather +
        # the merged update).
        host_anchor = jax.tree.map(np.asarray, jax.device_get(state.params))
        log.info(
            "multihost leader: %d processes, %d global devices",
            jax.process_count(), len(jax.devices()),
        )

    try:
        # From the init broadcast on, ANY leader exit without OP_DONE
        # leaves followers blocked in recv — this guard plus the loop's
        # finally below cover every path.
        loss_kind = cfg.loss or Loss.CROSS_ENTROPY
        from ..models.hf import _DECODER_TYPES

        step_kwargs = dict(
            causal_lm=causal_lm,
            has_aux=has_aux,
            # Models that declare an ``rng`` kwarg (the hf family) train
            # with live dropout, keyed per-step from the job seed — the
            # reference trains its torch models in train() mode
            # (training.py:106-116).
            dropout_seed=int(dict(cfg.model).get("seed", 0)),
            # Seq2seq hf models shift labels into decoder inputs
            # internally, so their logits are already aligned with the
            # labels stream.
            labels_aligned=getattr(model, "model_type", None) in _DECODER_TYPES,
            # Heads-family tasks with structured objectives (CTC,
            # detection, contrastive, span…) carry their own loss.
            loss_override=getattr(model, "custom_loss", None),
        )
        if frozen is not None:
            from .lora import make_lora_train_step

            lora_step = make_lora_train_step(model.apply, loss_kind, **step_kwargs)

            def step(state, batch):
                return lora_step(state, frozen, batch)
        elif extras is not None:
            step = make_routed_train_step(model)
        elif (
            getattr(model, "head_leaf", None) and causal_lm
            and loss_kind == Loss.CROSS_ENTROPY and step_kwargs["loss_override"] is None
        ):
            # A dense model that names its head and can stop before it: the
            # chunked loss, as the routed step's, and no [B, S, vocab] logits.
            step = make_chunked_train_step(model)
        else:
            step = make_train_step(model.apply, loss_kind, **step_kwargs)

        if mesh is not None:
            from jax.sharding import NamedSharding

            from ..parallel import param_sharding
            from ..parallel.sharding import batch_spec

            state = jax.device_put(state, param_sharding(state, mesh))
            if frozen is not None:
                frozen = jax.device_put(frozen, param_sharding(frozen, mesh))
            batch_sharding = NamedSharding(mesh, batch_spec())

            if mh is not None:

                def place(batch):
                    # Multi-controller: build global arrays shard-by-shard
                    # (device_put may refuse shardings spanning devices
                    # this process cannot address). Every process holds the
                    # same host batch — the leader just broadcast it.
                    return {
                        k: jax.make_array_from_callback(
                            np.shape(v), batch_sharding,
                            lambda idx, v=v: np.asarray(v)[idx],
                        )
                        for k, v in batch.items()
                    }
            else:

                def place(batch):
                    return {
                        k: jax.device_put(v, batch_sharding)
                        for k, v in batch.items()
                    }
        else:

            def place(batch):
                return batch

        def snapshot(tree):
            # A deep copy, NOT an alias: the jitted step donates its input
            # state, so aliased buffers would be deleted on the next step.
            return jax.tree.map(jnp.copy, tree)

        # Multihost keeps its anchor on the host (captured at mh.init above,
        # while state was still addressable); single-process keeps the
        # jitted device anchor.
        anchor = None if mh is not None else snapshot(state.params)
    except BaseException:
        if mh is not None:
            _mh_done_bounded(mh)  # followers must never hang on a dead leader
        raise
    result = TrainResult()
    countdown: int | None = None
    round_num = 0
    round_samples = 0
    round_losses: list[float] = []
    # Live metrics plane (telemetry.metrics_plane): reporting jobs attach
    # round-tagged training-quality keys (loss EWMA, delta norm, tokens/s,
    # inner steps) to the METRICS progress they already send per round.
    # Off (the default) leaves the metrics dict — and the wire — exactly
    # as it is today.
    report_quality = bool(getattr(cfg, "report_metrics_s", None))
    _EWMA_BETA = 0.7
    qstate: dict[str, Any] = {
        "ewma": None, "t0": time.monotonic(), "tokens": 0.0, "batches": 0,
    }

    def quality_metrics(mean_loss: float) -> dict:
        """One round's quality keys; resets the per-round accumulators."""
        now = time.monotonic()
        dur = max(now - qstate["t0"], 1e-9)
        ewma = qstate["ewma"]
        if not math.isnan(mean_loss):
            ewma = (
                mean_loss
                if ewma is None
                else _EWMA_BETA * ewma + (1.0 - _EWMA_BETA) * mean_loss
            )
            qstate["ewma"] = ewma
        out = {
            "loss_ewma": float(ewma) if ewma is not None else mean_loss,
            "tokens_per_s": float(qstate["tokens"]) / dur,
            "inner_steps": float(qstate["batches"]),
        }
        qstate.update(t0=now, tokens=0.0, batches=0)
        return out

    def note_quality_batch(batch: Any) -> None:
        qstate["batches"] += 1
        ids = batch.get("input_ids") if isinstance(batch, dict) else None
        qstate["tokens"] += (
            float(np.asarray(ids).size)
            if ids is not None
            else float(cfg.batch_size)
        )

    def delta_norm_of(flat: dict) -> float:
        """L2 norm of the shipped (post-EF) delta — one definition for
        every sync path's quality report."""
        return float(
            np.sqrt(sum(float(np.vdot(v, v)) for v in flat.values()))
        )

    # Last PS generation seen on the results stream (ft.durable): a change
    # mid-wait means the parameter server restarted — the shipped delta may
    # have died with it unjournaled, so the worker re-pushes it.
    ps_generation: Any = None
    # Last SCHEDULER generation adopted from stamped responses
    # (ft.durable DurableScheduler). A response stamped with an OLDER
    # generation is a zombie predecessor's control decision — dropped,
    # never acted on; the live scheduler answers the re-send. Unstamped
    # responses (every job that never restarts its scheduler) skip the
    # gate entirely.
    sched_gen: dict[str, Any] = {"v": None}

    def send_status_gated(progress: Progress) -> ProgressResponse:
        """session.send_status + the scheduler-generation gate."""
        for _attempt in range(64):
            gen = sched_gen["v"]
            if gen is not None and int(gen) >= 2:
                progress.scheduler_generation = int(gen)
            resp = session.send_status(progress)
            new_gen, stale = stale_scheduler_response(resp, sched_gen["v"])
            sched_gen["v"] = new_gen
            if not stale:
                return resp
            FT_METRICS.stale_generation_dropped.add(1)
            log.warning(
                "dropping %s response from stale scheduler generation %s "
                "(adopted %s); re-sending",
                progress.kind.value, getattr(resp, "generation", None),
                sched_gen["v"],
            )
            time.sleep(0.2)
        raise RuntimeError(
            "scheduler kept answering from a stale generation"
        )
    # Outer-round wire codec (hypha_tpu.compress): delta_codec wins, the
    # legacy delta_dtype="bfloat16" maps onto the bf16 codec. Quantized
    # codecs carry an error-feedback residual across rounds so the
    # compressed trajectory tracks the uncompressed one.
    wire_codec = compress.effective_codec(
        getattr(cfg, "delta_codec", "none"), cfg.delta_dtype
    )
    delta_ef = (
        compress.ErrorFeedback() if wire_codec in compress.QUANT_CODECS else None
    )

    def apply_codec_hint(meta: dict) -> None:
        """Per-link codec selection (ft.adaptive): an adaptive parameter
        server stamps the codec it picked for THIS worker's link into the
        broadcast header — switch the next upload to it. The error-
        feedback residual carries across the switch (it is plain f32
        error, codec-independent), so a degrading link keeps tracking the
        uncompressed trajectory; a worker newly switched to a quantized
        codec starts a fresh residual. Static jobs never see the key."""
        nonlocal wire_codec, delta_ef
        hint = meta.get(CODEC_KEY) if isinstance(meta, dict) else None
        if (
            not isinstance(hint, str)
            or hint not in compress.CODECS
            or hint == wire_codec
        ):
            return
        log.info(
            "per-link codec hint: switching upload codec %s -> %s",
            wire_codec, hint,
        )
        HET_METRICS.codec_switches.add(1)
        wire_codec = hint
        if wire_codec in compress.QUANT_CODECS and delta_ef is None:
            delta_ef = compress.ErrorFeedback()
    # Streaming outer sync (hypha_tpu.stream): overlap/stream replace the
    # blocking do_update with a background flight + delayed-update merge.
    # The default stays "blocking" and takes the exact code path below.
    sync_mode = getattr(cfg, "sync_mode", "blocking") or "blocking"
    if sync_mode not in SYNC_MODES:
        raise ValueError(
            f"job {spec.job_id}: sync_mode must be {'|'.join(SYNC_MODES)}, "
            f"got {sync_mode!r}"
        )
    # Sharded parameter service (hypha_tpu.stream placement): the worker
    # routes each part's delta to its owning shard. None = single PS, the
    # pre-shard wire.
    shard_map = getattr(cfg, "ps_shards", None)
    if shard_map is not None and not getattr(shard_map, "shards", None):
        shard_map = None
    if shard_map is not None and sync_mode == "overlap":
        # Overlap's single whole-tree flight has no per-part schedule to
        # route by; sharding composes with pipelining via sync_mode=stream.
        raise ValueError(
            f"job {spec.job_id}: ps_shards requires sync_mode blocking or "
            "stream"
        )
    if shard_map is not None and mh is not None:
        _mh_done_bounded(mh)
        raise ValueError(
            f"job {spec.job_id}: sharded parameter service is not supported "
            "for multihost replicas"
        )
    if pipeline_on and mh is not None:
        # The deferred loss read assumes this process can observe the step
        # asynchronously; multihost lockstep broadcasts cannot.
        _mh_done_bounded(mh)
        raise ValueError(
            f"job {spec.job_id}: input_pipeline is not supported for "
            "multihost replicas"
        )
    stream_state: _WorkerStream | None = None
    if sync_mode != "blocking":
        if mh is not None:
            # Multihost delta extraction is a collective gather the flight
            # thread cannot drive; fail loudly like rejoin does.
            _mh_done_bounded(mh)
            raise ValueError(
                f"job {spec.job_id}: streaming sync is not supported for "
                "multihost replicas"
            )
        stream_state = _WorkerStream(
            session, cfg, work_dir, sync_mode, wire_codec, rtrace=rtrace
        )
        log.info(
            "streaming outer sync: mode=%s fragments=%d", sync_mode,
            stream_state.F,
        )

    if getattr(cfg, "rejoin", False):
        # Elastic rejoin (hypha_tpu.ft.rejoin): this replica was dispatched
        # mid-job. θ₀ above is the seed init every original worker started
        # from; the parameter server owes us one catch-up push carrying
        # Σ updates so far plus the authoritative next round number. Regular
        # round broadcasts racing in first are safe to drop — their content
        # is folded into any later cumulative sum.
        if mh is not None:
            _mh_done_bounded(mh)
            raise ValueError("rejoin is not supported for multihost replicas")
        from ..ft.rejoin import await_catchup

        log.info("rejoin: waiting for the parameter server's catch-up")

        def _drop(event: dict) -> None:
            (work_dir / event["path"]).unlink(missing_ok=True)

        if shard_map is not None and len(shard_map.shards) > 1:
            # One catch-up PER shard: each covers only its own fragments'
            # cumulative Σ (disjoint tensors), and the authoritative next
            # round is the most advanced shard's frontier.
            want = len(shard_map.shards)
            got: dict[int, dict] = {}
            with session.receive(cfg.results) as events:
                while len(got) < want:
                    catchup = await_catchup(events, on_skip=_drop)
                    meta = catchup.get("meta") or {}
                    try:
                        sid = int(meta.get(SHARD_KEY, 0))
                    except (TypeError, ValueError):
                        sid = 0
                    if sid in got:
                        _drop(catchup)
                        continue
                    got[sid] = catchup
            round_num = 0
            epoch = "?"
            merged: dict = {}
            for sid, catchup in sorted(got.items()):
                meta = catchup.get("meta") or {}
                catchup_file = work_dir / catchup["path"]
                # Shards own disjoint tensors, so the per-shard Σs union
                # into one flat map — applied in a SINGLE tree pass below
                # instead of P parameter-sized flatten/rebuild rounds.
                merged.update(compress.read_delta(catchup_file))
                catchup_file.unlink(missing_ok=True)
                round_num = max(round_num, int(meta.get("round", 0)))
                epoch = meta.get("epoch", epoch)
            merged_tensors = len(merged)
            if merged:
                params_flat = flat_leaf_map(state.params)
                # f32 accumulation — the unsharded catch-up's
                # apply_updates discipline (a long Σ cast to bf16 before
                # the add would compound rounding the other path avoids).
                new_live = merge_update_f32(
                    {n: params_flat[n] for n in merged}, merged
                )
                state = state.replace(
                    params=replace_leaves(state.params, new_live)
                )
            anchor = snapshot(state.params)
            log.info(
                "rejoin: caught up to round %d from %d shards (membership "
                "epoch %s, %d tensors)",
                round_num, want, epoch, merged_tensors,
            )
        else:
            with session.receive(cfg.results) as events:
                catchup = await_catchup(events, on_skip=_drop)
            meta = catchup.get("meta") or {}
            catchup_file = work_dir / catchup["path"]
            flat = compress.read_delta(catchup_file)
            if flat:
                update = unflatten_like(flat, state.params)
                state = state.replace(params=apply_updates(state.params, [update]))
            anchor = snapshot(state.params)
            catchup_file.unlink(missing_ok=True)
            round_num = int(meta.get("round", 0))
            log.info(
                "rejoin: caught up to round %d (membership epoch %s, %d tensors)",
                round_num, meta.get("epoch", "?"), len(flat),
            )

    # Host-side moments of the step being timed: the generator wait that
    # handed it its batch, and when its step() returned (set by run_one).
    step_clock = {"input_wait_s": 0.0, "dispatched": 0.0}

    def batches() -> Iterator[Any]:
        yield first_batch
        while True:
            t0 = time.monotonic()
            batch = next(stream, None)
            # Total input wait: host assembly + any slice acquisition that
            # ran inline — the fraction databench asserts the pipeline
            # shrinks (recording only; values and order are untouched).
            # The step that takes this batch carries it as ``input_wait_s``.
            step_clock["input_wait_s"] = time.monotonic() - t0
            DATA_METRICS.note_input_wait(step_clock["input_wait_s"])
            if batch is None:
                return
            yield batch

    # One timer a phase of the blocking outer sync (telemetry.trace.phase):
    # the seconds go into ``sync`` for the ``sync done`` line, tracing on or
    # off, and the same interval into a span when it is on. This process
    # holds the chip, so a phase also enters the profiler's trace under its
    # span's name (a flag check while no profiler session is open).
    # ``usage``: the span carries the process's CPU seconds and page faults
    # over the phase (telemetry.trace); the phases that move or touch a
    # parameter-sized payload ask for it.
    sync: dict[str, float] = {}

    def sync_phase(
        name: str, *, parent=None, key: str | None = None, min_s: float = 0.0,
        usage: bool = False, **attrs,
    ):
        return trace.phase(
            name, parent=parent, attrs=attrs, node=rtrace.node,
            into=sync if key else None, key=key, min_s=min_s,
            annotation=jax.profiler.TraceAnnotation(name), usage=usage,
        )

    def log_sync(
        done_round: int, bytes_up: int, read: compress.ReadStats, pages: str = "fresh"
    ) -> None:
        """``pages``: whether the delta was written over the file the last
        round's left (``recycled``) or into a new one (``fresh``)."""
        log.info(
            "sync done: round=%d encode_s=%.3f upload_s=%.3f wait_s=%.3f "
            "merge_s=%.3f cleanup_s=%.3f bytes_up=%d bytes_down=%d "
            "leaves=%d direct=%d resident=%d pages=%s",
            done_round, sync.get("encode_s", 0.0), sync.get("upload_s", 0.0),
            sync.get("wait_s", 0.0), sync.get("merge_s", 0.0),
            sync.get("cleanup_s", 0.0), bytes_up, read.bytes,
            read.leaves, read.direct, read.resident, pages,
        )

    # Where the blocking syncs keep the broadcast update on the host: one
    # f32 buffer a leaf for the life of the job. Round 0 allocates them and
    # faults them in; every later ``merge.read`` finds pages that exist. THE
    # INVARIANT: these buffers are written in ``merge.read`` and nowhere
    # else, and the tree ``read_update`` returns IS these buffers, so
    # nothing may hold it past the round. merge_update is dispatched with
    # them as host arguments and returns before the device has taken them
    # (a CPU backend may alias them outright); they are next written in the
    # NEXT round's ``merge.read``, which runs after that round's
    # ``encode.extract`` has fetched (``device_get``; ``mh.gather`` on a
    # multi-process replica) values computed from the merged parameters, so
    # the transfer is long over. ``mh.merge`` encodes the tree as it sends
    # it, and a follower merges the copy it received.
    update_buffers = SumBuffers()

    def read_update(path: Path, read: compress.ReadStats) -> dict[str, np.ndarray]:
        """``merge.read``: one broadcast update file onto the host, its
        counts added to ``read``. An all-F32 SafeTensors file goes a leaf at
        a time into ``update_buffers``; anything else (an HQD1 frame
        dequantizes to f32, bf16 loads as bf16) is decoded into a fresh
        tree as ever. The file says which."""
        flat, stats = compress.read_delta_into(path, update_buffers.lease)
        read.add(stats)
        if stats.direct:
            # Lent, not handed over: the next round's read leases them again.
            update_buffers.give_back(flat)
        return flat

    def note_read(ph, read: compress.ReadStats) -> None:
        for name in ("bytes", "leaves", "direct", "resident"):
            ph.set(name, getattr(read, name))

    def note_relaid(ph, params) -> None:
        """On ``encode.extract``: the leaves the device transposes for the
        delta, and their bytes of it (``diloco.extract_delta``)."""
        leaves, nbytes = relaid(params)
        ph.set("relaid_leaves", leaves)
        ph.set("relaid_bytes", nbytes)

    # The blocking sync's delta of the last round, still under the name it
    # was sent by: the file this round's ``encode.write`` writes over, so
    # that 4 B a parameter land in pages that exist and not in fresh ones.
    # Kept only where the node held a second name through every send of it
    # (``push_delta``), so that its link count says whether one is still
    # open (``claim_spare``); it goes with the job's work directory.
    spare_delta: Path | None = None

    def push_delta(delta_path: Path) -> bool:
        """Hand the node this round's delta by name (it sends in the
        background). True where the node holds a second name for the file
        until that send has ended, whatever this process does with its own
        (``Bridge._send``); a session that says nothing holds none."""
        return bool(
            session.send_resource(
                cfg.updates,
                delta_path.name,
                # The Send reference's resource tag routes the stream to the
                # right consumer on the PS node (job-unique, set by the
                # scheduler's orchestrator).
                resource=cfg.updates.ref.resource or "updates",
                # round tags the delta so an elastic parameter server can
                # reject a stale one (arriving after its round aggregated at
                # quorum) instead of folding it into the wrong mean. Traced
                # jobs additionally stamp the round context so the parameter
                # server's spans join the round's trace.
                meta=rtrace.stamp(
                    {"num_samples": float(round_samples), "round": round_num},
                    round_num,
                ),
            )
        )

    def await_round_update(delta_path: Path) -> tuple[dict, dict, bool]:
        """Results-stream events until this round's update broadcast:
        the event, its meta, and whether every re-send of the delta on the
        way was held (``push_delta``)."""
        nonlocal ps_generation
        held = True
        with session.receive(cfg.results) as events:
            while True:
                # Not bare next(): a severed bridge ends the SSE stream,
                # and a StopIteration escaping through asyncio.to_thread
                # turns into an unraisable TypeError instead of a clean
                # job failure.
                event = next(events, None)
                if event is None:
                    raise RuntimeError(
                        "results stream ended before the round's update "
                        "broadcast"
                    )
                meta = event.get("meta") or {}
                ps_generation, resend = restart_signal(meta, ps_generation)
                if resend and delta_path.is_file():
                    # PS restart: the shipped delta may have died with it
                    # unjournaled. Re-push — the PS's journal dedup makes
                    # the copy idempotent when it DID land.
                    log.warning(
                        "ps restart detected (generation %s); re-sending "
                        "round %d delta", ps_generation, round_num,
                    )
                    held &= push_delta(delta_path)
                if meta.get(RESYNC_KEY) or meta.get(CATCHUP_KEY):
                    # Resync announcements carry no tensor payload; stray
                    # catch-ups target rejoiners and are folded into every
                    # later broadcast anyway.
                    (work_dir / event["path"]).unlink(missing_ok=True)
                    continue
                try:
                    eround = int(meta.get("round", round_num))
                except (TypeError, ValueError):
                    eround = round_num
                if eround < round_num:
                    # A recovered PS re-broadcasts its last committed round
                    # so un-wedged workers can proceed; this worker already
                    # merged it — absorbing again would double-apply.
                    (work_dir / event["path"]).unlink(missing_ok=True)
                    continue
                return event, meta, held

    def do_update() -> bool:
        """Ship Δθ, wait for the PS broadcast, merge. True = next round."""
        nonlocal state, anchor, host_anchor, round_num, round_samples, spare_delta
        rtrace.close_inner()
        rtrace.clock_mark(round_num)
        round_tp = rtrace.ctx(round_num)
        send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE, job_id=spec.job_id,
                traceparent=round_tp,
            )
        )
        sync.clear()
        host_params = None
        with sync_phase(
            "encode", parent=round_tp, key="encode_s", usage=True,
            round=round_num, codec=wire_codec,
        ) as enc:
            # Ends when the bytes are on the host: device_get blocks on the
            # subtraction and the device-to-host copy.
            with sync_phase("encode.extract", parent=enc.span, usage=True) as ph:
                if mh is not None:
                    # Collective Δθ: the allgather every process joins
                    # (OP_GATHER), then host-side subtraction against the
                    # host anchor — param shards on other processes' devices
                    # cannot be device_get here.
                    host_params = _with_deadline(
                        lambda: mh.gather(state.params), mh_bound("gather"),
                        "param gather",
                    )
                    compiled_once["gather"] = True
                    host_delta = jax.tree.map(
                        lambda p, a: p - a, host_params, host_anchor
                    )
                else:
                    note_relaid(ph, state.params)
                    delta = extract_delta(state.params, anchor)
                    host_delta = jax.device_get(delta)
                ph.set("bytes", _tree_nbytes(host_delta))
            delta_path = work_dir / f"delta-{round_num}.safetensors"
            with sync_phase("encode.write", parent=enc.span, usage=True) as ph:
                # One send-side entry point for every codec
                # (hypha_tpu.compress): int8/int4 ship Q(Δθ + e) as an HQD1
                # frame and keep e' = (Δθ + e) − Q(Δθ + e) for the next
                # round (quantization error is re-shipped, never dropped);
                # bf16 halves the upload; the PS widens/accumulates in f32
                # in every case.
                wire_flat = flatten_tree(host_delta)
                if (
                    delta_ef is not None
                    and wire_codec not in compress.QUANT_CODECS
                    and delta_ef.tensors
                ):
                    # The link recovered (per-link hint switched quant ->
                    # base codec) with a residual still pending: fold it
                    # into this upload — EF's promise is that quantization
                    # error is re-shipped, never dropped, and an
                    # uncompressed wire can carry it exactly.
                    wire_flat = delta_ef.compensate(wire_flat)
                    delta_ef.reset()
                # Over the file the last round's delta left, if no send of
                # it is open; a tree that is not all f32 on an uncompressed
                # wire is written as ever and the spare unlinked
                # (``write_delta`` decides by what it is given). Held open,
                # the spare says afterwards which it was: it has a name.
                over = claim_spare(spare_delta) if spare_delta else None
                spare_delta = None
                # What ``write_delta`` has to copy before it can write a
                # leaf's own memory: ``extract_delta`` leaves it nothing.
                copied_bytes = sum(
                    int(v.nbytes) for v in wire_flat.values() if not v.flags.c_contiguous
                )
                with open(over, "rb") if over else contextlib.nullcontext() as was:
                    compress.write_delta(
                        delta_path, wire_flat, wire_codec, ef=delta_ef, over=over
                    )
                    named = was is not None and os.fstat(was.fileno()).st_nlink
                pages = "recycled" if named else "fresh"
                bytes_up = delta_path.stat().st_size
                ph.set("bytes", bytes_up)
                ph.set("leaves", len(wire_flat))
                ph.set("pages", pages)
                ph.set("copied_bytes", copied_bytes)
        with sync_phase(
            "upload", parent=round_tp, key="upload_s",
            round=round_num, codec=wire_codec, bytes=bytes_up,
        ):
            held = push_delta(delta_path)
        # What the worker waits for transport and parameter server together:
        # from the upload's end to the broadcast event that ends the loop.
        with sync_phase(
            "await_update", parent=round_tp, key="wait_s", round=round_num
        ):
            mean_loss = float(np.mean(round_losses)) if round_losses else math.nan
            round_metrics = {"loss": mean_loss, "samples": float(round_samples)}
            if report_quality:
                round_metrics.update(quality_metrics(mean_loss))
                round_metrics["delta_norm"] = delta_norm_of(wire_flat)
            send_status_gated(
                Progress(
                    kind=ProgressKind.METRICS,
                    job_id=spec.job_id,
                    round=round_num,
                    metrics=round_metrics,
                    traceparent=round_tp,
                )
            )
            event, meta, resends_held = await_round_update(delta_path)
        apply_codec_hint(meta)
        update_file = work_dir / event["path"]
        with sync_phase(
            "merge",
            # Parent under the broadcast's context when the PS stamped
            # one (the same round trace), else the scheduler's round.
            parent=meta.get(TRACEPARENT_KEY) or round_tp, key="merge_s",
            usage=True, round=round_num,
        ) as mrg:
            read = compress.ReadStats()
            with sync_phase("merge.read", parent=mrg.span, usage=True) as ph:
                flat = read_update(update_file, read)
                note_read(ph, read)
            # Ends when the merge and the new anchor are DISPATCHED: nothing
            # here waits for the device, and a span must not add a
            # synchronisation. What the device still owes is paid in the
            # next round's first step (its fetch_s).
            with sync_phase(
                "merge.apply", parent=mrg.span, usage=True,
                ends_at="dispatch", leaves=len(flat),
            ):
                if mh is not None:
                    # followers mirror the merge dispatch; bounded like the
                    # step broadcasts — a lost follower must fail the job,
                    # not hang it
                    _with_deadline(
                        lambda: mh.merge(flat), mh_bound("merge"),
                        "merge broadcast",
                    )
                    compiled_once["merge"] = True
                update = unflatten_like(flat, state.params)
                state = state.replace(params=merge_update(state.params, update))
                if mh is not None:
                    # New anchor = merged params, assembled on the host from
                    # the round's gathered params + the same update the
                    # device merge applied — no second collective needed.
                    host_anchor = jax.tree.map(
                        lambda p, u: p + np.asarray(u, p.dtype), host_params, update
                    )
                else:
                    anchor = snapshot(state.params)
        with sync_phase(
            "cleanup", parent=round_tp, key="cleanup_s",
            min_s=trace.SLOW_CLEANUP_S, round=round_num,
        ):
            if held and resends_held:
                # Stays under its name, for the next round to write over.
                spare_delta = delta_path
            else:
                delta_path.unlink(missing_ok=True)
            # The broadcast update is merged — drop it, or a long job
            # accumulates one full-parameter-sized file per round under
            # work_dir/incoming.
            update_file.unlink(missing_ok=True)
        log_sync(round_num, bytes_up, read, pages)
        resp = send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE_RECEIVED, job_id=spec.job_id,
                traceparent=round_tp,
            )
        )
        round_num += 1
        rtrace.adopt(resp, round_num)
        result.rounds = round_num
        round_samples = 0
        round_losses.clear()
        if ckpt_dir is not None and round_num % ckpt_every == 0:
            if mh is not None:
                # Sharded opt_state spans non-addressable devices; a full
                # host gather of params+opt per round is not worth wiring
                # until a job needs it (sharded orbax-style checkpointing
                # is the real fix). Resume still works via the PS momentum
                # checkpoint + re-dispatch from θ of the last round.
                log.warning(
                    "checkpointing skipped: multihost replicas do not yet "
                    "support train-state checkpoints"
                )
            else:
                # Manifest round counts CUMULATIVE completed rounds across
                # resumes, not just this execution's.
                save_train_checkpoint(
                    ckpt_dir,
                    state.params,
                    state.opt_state,
                    int(state.step),
                    round_offset + round_num,
                )
        return resp.kind == ProgressResponseKind.CONTINUE

    # Sharded blocking sync state: the deterministic part partition, one
    # error-feedback residual per part (absorb replaces the whole residual
    # tree, so parts must not share one), and the last seen generation per
    # PS shard.
    shard_ctx: dict[str, Any] = {"parts": None, "efs": None, "gens": {}}

    def _push_part(p: int, path: Path, samples: float) -> None:
        tag = FragmentTag(
            round=round_num, fragment_id=p,
            fragments=len(shard_ctx["parts"]),
        )
        send, owner, res_tag = shard_route(
            shard_map, p, getattr(cfg, "reduce_via", None)
        )
        meta = {"num_samples": samples, "round": round_num, **tag.header()}
        if len(shard_map.shards) > 1:
            meta[SHARD_KEY] = owner
        session.send_resource(
            send, path.name, resource=res_tag,
            meta=rtrace.stamp(meta, round_num),
        )

    def do_update_sharded() -> bool:
        """Blocking sync against the sharded parameter service: split Δθ
        into placement parts, push each part to its owning shard (via the
        group reducer with ANY failover when tree-reduce is on), await
        EVERY part's update broadcast, merge, re-anchor. True = continue.
        """
        nonlocal state, anchor, round_num, round_samples
        assert shard_map is not None
        rtrace.close_inner()
        round_tp = rtrace.ctx(round_num)
        send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE, job_id=spec.job_id,
                traceparent=round_tp,
            )
        )
        sync.clear()
        with sync_phase(
            "encode", parent=round_tp, key="encode_s", usage=True,
            round=round_num, codec=wire_codec,
        ) as enc:
            with sync_phase("encode.extract", parent=enc.span, usage=True) as ph:
                note_relaid(ph, state.params)
                delta = extract_delta(state.params, anchor)
                host_delta = jax.device_get(delta)
                ph.set("bytes", _tree_nbytes(host_delta))
            wire_flat = flatten_tree(host_delta)
        P = int(shard_map.fragments) or len(shard_map.shards)
        if shard_ctx["parts"] is None:
            # Deterministic by (name, size) only — shards, reducers and
            # rejoiners derive the identical partition with no manifest.
            shard_ctx["parts"] = partition_names(
                {n: int(np.asarray(v).size) for n, v in wire_flat.items()}, P
            )
            shard_ctx["efs"] = [
                compress.ErrorFeedback()
                if wire_codec in compress.QUANT_CODECS
                else None
                for _ in range(P)
            ]
        parts = shard_ctx["parts"]
        samples = float(round_samples)
        paths: dict[int, Path] = {}
        # Here the parts are written as they are pushed, so the wire write
        # is inside ``upload`` and ``encode`` has no ``encode.write``.
        with sync_phase(
            "upload", parent=round_tp, key="upload_s",
            round=round_num, codec=wire_codec, parts=len(parts),
        ):
            for p, names in enumerate(parts):
                tag = FragmentTag(round=round_num, fragment_id=p, fragments=P)
                path = work_dir / f"delta-{round_num}-p{p}.safetensors"
                compress.write_delta(
                    path, {n: wire_flat[n] for n in names}, wire_codec,
                    ef=shard_ctx["efs"][p], tag=tag.header(),
                )
                paths[p] = path
                _push_part(p, path, samples)
        bytes_up = sum(path.stat().st_size for path in paths.values())
        with sync_phase(
            "await_update", parent=round_tp, key="wait_s", round=round_num
        ):
            mean_loss = float(np.mean(round_losses)) if round_losses else math.nan
            round_metrics = {"loss": mean_loss, "samples": samples}
            if report_quality:
                round_metrics.update(quality_metrics(mean_loss))
                round_metrics["delta_norm"] = delta_norm_of(wire_flat)
            send_status_gated(
                Progress(
                    kind=ProgressKind.METRICS,
                    job_id=spec.job_id,
                    round=round_num,
                    metrics=round_metrics,
                    traceparent=round_tp,
                )
            )
            gens = shard_ctx["gens"]
            got: dict[int, Path] = {}
            with session.receive(cfg.results) as events:
                while len(got) < P:
                    event = next(events, None)
                    if event is None:
                        raise RuntimeError(
                            "results stream ended before every part's update "
                            "broadcast"
                        )
                    meta = event.get("meta") or {}
                    try:
                        sid = int(meta.get(SHARD_KEY, 0))
                    except (TypeError, ValueError):
                        sid = 0
                    gens[sid], resend = restart_signal(meta, gens.get(sid))
                    if resend:
                        # That shard restarted: re-send its still-un-acked
                        # parts — the shard's journal dedup absorbs any copy
                        # whose original did land.
                        for p, path in paths.items():
                            if (
                                p in got
                                or not path.is_file()
                                or shard_of(p, len(shard_map.shards)) != sid
                            ):
                                continue
                            log.warning(
                                "ps shard %d restart detected; re-sending "
                                "round %d part %d", sid, round_num, p,
                            )
                            _push_part(p, path, samples)
                    if meta.get(RESYNC_KEY) or meta.get(CATCHUP_KEY):
                        (work_dir / event["path"]).unlink(missing_ok=True)
                        continue
                    try:
                        eround = int(meta.get("round", round_num))
                    except (TypeError, ValueError):
                        eround = round_num
                    if eround < round_num:
                        # A recovered shard's re-broadcast of a merged round.
                        (work_dir / event["path"]).unlink(missing_ok=True)
                        continue
                    etag = FragmentTag.from_header(meta)
                    p = int(etag.fragment_id) if etag is not None else sid
                    if p in got or p not in paths:
                        (work_dir / event["path"]).unlink(missing_ok=True)
                        continue
                    got[p] = work_dir / event["path"]
        # Merge every part — disjoint tensors, so their flat maps union
        # into ONE combined merge/replace pass (P separate passes would
        # re-flatten and rebuild the whole parameter tree per part) —
        # then re-anchor ONCE (blocking semantics: no drift correction).
        with sync_phase(
            "merge", parent=round_tp, key="merge_s", usage=True,
            round=round_num,
        ) as mrg:
            combined: dict = {}
            read = compress.ReadStats()
            with sync_phase("merge.read", parent=mrg.span, usage=True) as ph:
                for p in sorted(got):
                    flat = read_update(got[p], read)
                    if set(flat) != set(parts[p]):
                        raise ValueError(
                            f"part {p} placement mismatch: update carries "
                            f"{len(flat)} tensors, worker expects {len(parts[p])}"
                        )
                    combined.update(flat)
                    got[p].unlink(missing_ok=True)
                note_read(ph, read)
            # Ends at dispatch, as in do_update.
            with sync_phase(
                "merge.apply", parent=mrg.span, usage=True,
                ends_at="dispatch", leaves=len(combined),
            ):
                params_flat = flat_leaf_map(state.params)
                new_live = merge_update(
                    {n: params_flat[n] for n in combined}, combined
                )
                state = state.replace(
                    params=replace_leaves(state.params, new_live)
                )
                anchor = snapshot(state.params)
        with sync_phase(
            "cleanup", parent=round_tp, key="cleanup_s",
            min_s=trace.SLOW_CLEANUP_S, round=round_num,
        ):
            for path in paths.values():
                path.unlink(missing_ok=True)
        log_sync(round_num, bytes_up, read)
        resp = send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE_RECEIVED, job_id=spec.job_id,
                traceparent=round_tp,
            )
        )
        round_num += 1
        rtrace.adopt(resp, round_num)
        result.rounds = round_num
        round_samples = 0
        round_losses.clear()
        if ckpt_dir is not None and round_num % ckpt_every == 0:
            save_train_checkpoint(
                ckpt_dir,
                state.params,
                state.opt_state,
                int(state.step),
                round_offset + round_num,
            )
        return resp.kind == ProgressResponseKind.CONTINUE

    def begin_stream_sync() -> None:
        """Ship the due fragment's Δ in the background; keep stepping.

        Round accumulators reset HERE, not at merge time: batches run
        while the sync is in flight belong to the NEXT delta (that is the
        drift the correction preserves), so their samples and losses must
        not be re-reported for this round.
        """
        nonlocal round_samples
        assert stream_state is not None
        rtrace.close_inner()
        round_tp = rtrace.ctx(round_num)
        send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE, job_id=spec.job_id,
                traceparent=round_tp,
            )
        )
        stream_state.begin(round_num, state.params, anchor, round_samples)
        mean_loss = float(np.mean(round_losses)) if round_losses else math.nan
        round_metrics = {"loss": mean_loss, "samples": float(round_samples)}
        if report_quality:
            # No delta norm here: the due fragment's delta belongs to the
            # background flight thread (stream mode).
            round_metrics.update(quality_metrics(mean_loss))
        send_status_gated(
            Progress(
                kind=ProgressKind.METRICS,
                job_id=spec.job_id,
                round=round_num,
                metrics=round_metrics,
                traceparent=round_tp,
            )
        )
        round_samples = 0
        round_losses.clear()

    def finish_stream_sync() -> bool:
        """The broadcast landed: merge with correction. True = continue."""
        nonlocal state, anchor, round_num
        assert stream_state is not None
        new_params, new_anchor = stream_state.finish(state.params, anchor)
        state = state.replace(params=new_params)
        anchor = new_anchor
        resp = send_status_gated(
            Progress(
                kind=ProgressKind.UPDATE_RECEIVED, job_id=spec.job_id,
                traceparent=rtrace.ctx(round_num),
            )
        )
        round_num += 1
        rtrace.adopt(resp, round_num)
        result.rounds = round_num
        if ckpt_dir is not None and round_num % ckpt_every == 0:
            save_train_checkpoint(
                ckpt_dir,
                state.params,
                state.opt_state,
                int(state.step),
                round_offset + round_num,
            )
        return resp.kind == ProgressResponseKind.CONTINUE

    mh_timeout = float(
        os.environ.get(_MH_STEP_TIMEOUT_ENV, _MH_STEP_TIMEOUT_DEFAULT)
    )
    mh_grace = max(
        mh_timeout,
        float(os.environ.get(_MH_COMPILE_GRACE_ENV, _MH_COMPILE_GRACE_DEFAULT)),
    )
    compiled_once = {"step": False, "merge": False, "gather": False}

    def mh_bound(what: str) -> float:
        return mh_timeout if compiled_once[what] else mh_grace

    # A routed model's step packs its counters beside the loss
    # (``metrics["host"]``, ROUTING_FIELDS): the one transfer that fetches the
    # loss fetches them, and the round sums them for a line of its own.
    # Where the model has a second objective the vector goes on (aux_fields).
    aux = aux_fields(model)
    routing = dict.fromkeys(("steps", *ROUTING_FIELDS[1:], *aux), 0)

    def fetch_loss(metrics) -> float:
        host = metrics.get("host")
        if host is None:
            return float(metrics["loss"])
        got = dict(zip(ROUTING_FIELDS + aux, np.asarray(host).tolist()))
        routing["steps"] += 1
        for key in ROUTING_FIELDS[1:]:
            if key != "load_max":
                routing[key] += int(got[key])
        for key in aux:  # summed here, a mean over the steps on the round's line
            routing[key] += got[key]
        routing["load_max"] = max(routing["load_max"], int(got["load_max"]))
        return got["loss"]

    def run_one(batch):
        """Broadcast + dispatch + host fetch: every phase that can block on
        a dead follower, so the deadline covers all of them."""
        if mh is not None:
            mh.step(batch)  # followers dispatch the same step
        new_state, metrics = step(state, place(batch))
        step_clock["dispatched"] = time.monotonic()
        return new_state, metrics, fetch_loss(metrics)

    def run_one_deferred(batch):
        """Device double-buffering (input_pipeline): dispatch the step and
        return WITHOUT forcing the loss — the host thread goes straight on
        to assemble and place batch n+1 while step n computes on device.
        The metrics land in ``pending_metrics``; ``flush_pending_loss``
        reads them one step later (same values, same order)."""
        new_state, metrics = step(state, place(batch))
        step_clock["dispatched"] = time.monotonic()
        return new_state, metrics

    # One-step-deferred loss reads (input_pipeline only; empty otherwise).
    # Flushed before every round-boundary action that reports or resets
    # ``round_losses``, and after the loop — the loss SEQUENCE is
    # bit-identical to the synchronous read, just observed later.
    pending_metrics: list[Any] = []

    def flush_pending_loss() -> None:
        while pending_metrics:
            metrics = pending_metrics.pop(0)
            loss = fetch_loss(metrics)
            round_losses.append(loss)
            result.losses.append(loss)

    # Per-round facts for the log. Step times are host-clock and end in the
    # loss fetch (run_one), so they cover the device work; with the input
    # pipeline's deferred read they cover step n-1's instead.
    step_times: list[float] = []
    round_mark = {
        "t0": time.monotonic(), "rounds": 0, "losses": 0, "tokens": 0,
        "status_s": 0.0, "input_wait_s": 0.0,
    }

    def log_round() -> None:
        if result.rounds == round_mark["rounds"] or not step_times:
            return
        losses = result.losses[round_mark["losses"]:]
        stats = jax.local_devices()[0].memory_stats() or {}
        now = time.monotonic()
        log.info(
            "round %d done: batch=%d steps=%d tokens=%d wall_s=%.3f "
            "first_step_s=%.3f median_step_s=%.4f loss_first=%.4f "
            "loss_last=%.4f loss_mean=%.4f nonfinite=%d peak_bytes=%s "
            "steps_sum_s=%.4f max_step_s=%.4f status_s=%.4f input_wait_s=%.4f",
            result.rounds - 1, cfg.batch_size, len(step_times),
            round_mark["tokens"],
            now - round_mark["t0"], step_times[0],
            float(np.median(step_times)),
            losses[0] if losses else math.nan,
            losses[-1] if losses else math.nan,
            float(np.mean(losses)) if losses else math.nan,
            sum(not math.isfinite(x) for x in losses),
            stats.get("peak_bytes_in_use"),
            sum(step_times), max(step_times),
            round_mark["status_s"], round_mark["input_wait_s"],
        )
        if routing["steps"]:
            # The routed experts' counters, on a line of their own: pairs
            # computed must equal pairs routed (nothing dropped); a held
            # expert's mean load is pairs over steps x expert layers x held;
            # a trip of the backward walk adds into grad_experts_per_trip
            # experts' gradient rows, and the trips' rows went onto the tokens
            # in combines batches (ops/grouped_matmul.py plan_trips).
            mcfg = model.config
            layers = mcfg.num_expert_layers
            cells = routing["steps"] * layers
            load_mean = routing["pairs_computed"] / max(cells * mcfg.held, 1)
            log.info(
                "round %d routing: steps=%d expert_layers=%d experts_held=%d "
                "pairs_routed=%d pairs_computed=%d pairs_per_token=%.4f "
                "load_max=%d load_mean=%.2f load_max_over_mean=%.3f "
                "tokens_elsewhere=%d trips=%d grad_experts_per_trip=%.3f combines=%d",
                result.rounds - 1, routing["steps"], layers, mcfg.held,
                routing["pairs_routed"], routing["pairs_computed"],
                routing["pairs_computed"] / max(round_mark["tokens"] * layers, 1),
                routing["load_max"], load_mean,
                routing["load_max"] / max(load_mean, 1e-9),
                routing["tokens_elsewhere"], routing["trips"],
                routing["grad_experts"] / max(routing["trips"], 1), routing["combines"],
            )
            if aux:
                # The second objective, apart from the loss, under the name the
                # model gives it, and what else the model named: means over
                # the round's steps.
                log.info(
                    "round %d objective: steps=%d %s=%.6f%s",
                    result.rounds - 1, routing["steps"], model.aux_name,
                    routing["aux_loss"] / routing["steps"],
                    "".join(f" {k}={routing[k] / routing['steps']:.4f}" for k in aux[1:]),
                )
            routing.update(dict.fromkeys(routing, 0))
        step_times.clear()
        round_mark.update(
            t0=now, rounds=result.rounds, losses=len(result.losses), tokens=0,
            status_s=0.0, input_wait_s=0.0,
        )

    t0 = time.monotonic()
    log.info(
        "setup done: setup_s=%.3f (first slice, model init, placement)",
        t0 - t_enter,
    )
    try:
        for batch in batches():
            if should_stop is not None and should_stop():
                log.info("cooperative stop requested; ending training loop")
                break
            # Merge a landed broadcast BEFORE the next step: a sync that
            # completed with no intervening batch has zero drift and is
            # bit-identical to blocking mode's merge.
            if stream_state is not None and stream_state.poll():
                if not finish_stream_sync():
                    break
            rtrace.batch(round_num)
            tokens = (
                int(np.size(batch["input_ids"]))
                if isinstance(batch, dict) and "input_ids" in batch else 0
            )
            input_wait_s, step_clock["input_wait_s"] = step_clock["input_wait_s"], 0.0
            # The per-step record: one ``step`` span with the boundaries of
            # ``step_times`` (to after the loss fetch), written once the
            # status round trip that follows the step is known.
            with trace.phase(
                "step", parent=rtrace.inner, node=rtrace.node,
                attrs={
                    "round": round_num, "step": len(step_times),
                    "tokens": tokens, "input_wait_s": input_wait_s,
                },
                annotation=jax.profiler.StepTraceAnnotation(
                    "inner_step", step_num=result.batches
                ),
                defer=True,
            ) as step_rec:
                step_t0 = time.monotonic()
                if mh is not None:
                    state, metrics, loss = _with_deadline(
                        lambda b=batch: run_one(b), mh_bound("step"), "train step"
                    )
                    compiled_once["step"] = True
                    round_losses.append(loss)
                    result.losses.append(loss)
                else:
                    overlapping = (
                        stream_state is not None and stream_state.in_flight
                    )
                    if pipeline_on and not overlapping:
                        # Deferred sync: dispatch step n, then read step
                        # n-1's loss (already done on device) — never this
                        # step's. Skipped while a stream flight is up:
                        # note_compute's overlap accounting needs the
                        # synchronous read.
                        state, metrics = run_one_deferred(batch)
                        flush_pending_loss()
                        pending_metrics.append(metrics)
                    else:
                        bt0 = time.monotonic() if overlapping else 0.0
                        state, metrics, loss = run_one(batch)
                        if overlapping:
                            stream_state.note_compute(time.monotonic() - bt0)
                        flush_pending_loss()  # older deferred losses first
                        round_losses.append(loss)
                        result.losses.append(loss)
                step_rec.set("dispatch_s", step_clock["dispatched"] - step_t0)
                step_rec.set("fetch_s", time.monotonic() - step_clock["dispatched"])
            step_times.append(step_rec.seconds)
            round_mark["tokens"] += tokens
            round_mark["input_wait_s"] += input_wait_s
            result.batches += 1
            round_samples += cfg.batch_size
            if report_quality:
                note_quality_batch(batch)

            status_t0 = time.monotonic()
            resp = send_status_gated(
                Progress(
                    kind=ProgressKind.STATUS,
                    job_id=spec.job_id,
                    batch_size=cfg.batch_size,
                )
            )
            status_s = time.monotonic() - status_t0
            round_mark["status_s"] += status_s
            step_rec.set("status_s", status_s)
            step_rec.write()
            if resp.kind == ProgressResponseKind.DONE:
                break
            if resp.kind == ProgressResponseKind.SCHEDULE_UPDATE:
                adopted = adopt_schedule(resp, countdown)
                if adopted is not countdown:
                    countdown = adopted
                    rtrace.adopt(resp, round_num)
            if countdown is not None:
                if countdown <= 0:
                    countdown = None
                    # Round boundary: the round's LAST loss may still be
                    # deferred — it must land in round_losses before the
                    # sync reports/reset them.
                    flush_pending_loss()
                    if stream_state is not None:
                        begin_stream_sync()
                    elif shard_map is not None:
                        if not do_update_sharded():
                            break
                    elif not do_update():
                        break
                else:
                    countdown -= 1
            log_round()
            if max_batches is not None and result.batches >= max_batches:
                log.warning("max_batches=%d reached; stopping", max_batches)
                break
        flush_pending_loss()
        log_round()  # a round closed on a break path
    finally:
        rtrace.close_inner()
        # Stop the input pipeline's prefetch thread NOW (the generator's
        # finally owns it) instead of at GC — its next fetch would race
        # the bridge teardown. No-op for the synchronous stream.
        try:
            stream.close()
        except Exception:  # never let input teardown mask the real error
            pass
        if stream_state is not None:
            stream_state.abort()
        if mh is not None:
            _mh_done_bounded(mh)  # followers must never hang on a dead leader
    log.info(
        "training done: %d rounds, %d batches, %.1fs, last loss %.4f",
        result.rounds, result.batches, time.monotonic() - t0, result.last_loss,
    )
    return result


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="hypha-training-executor",
        description="hypha-tpu DiLoCo training executor",
    )
    parser.add_argument("--socket", required=True, help="bridge unix socket path")
    parser.add_argument("--work-dir", required=True)
    parser.add_argument("--job", required=True, help="job spec JSON (inline or @file)")
    parser.add_argument("--max-batches", type=int, default=None)
    parser.add_argument(
        "--trace-dir", default="",
        help="round-trace span directory, handed down by the worker; empty = off",
    )
    parser.add_argument("--trace-node", default="", help="node label of the spans")
    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")

    raw = args.job
    if raw.startswith("@"):
        raw = Path(raw[1:]).read_text()
    spec = messages.from_json_dict(json.loads(raw))
    if not isinstance(spec, JobSpec):
        raise SystemExit(f"--job does not decode to a JobSpec: {type(spec)}")

    from ..hw import enable_compile_cache
    from .bridge_client import Session

    enable_compile_cache()
    if args.trace_dir:
        trace.enable(args.trace_dir, node=args.trace_node or f"pid{os.getpid()}")
    with Session(args.socket) as session:
        run_training(
            session, args.work_dir, spec, max_batches=args.max_batches,
            trace_node=args.trace_node or None,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
