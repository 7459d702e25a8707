"""Fault-tolerance chaos harness: an orchestrated DiLoCo run under fire.

Stands up the full in-process topology (gateway + data node + N train
workers + parameter server + scheduler on the memory fabric — the same
harness as tests/test_e2e.py) with elastic membership enabled, injects a
scripted fault via :mod:`hypha_tpu.ft.chaos`, and reports:

  * ``rounds_completed``      — outer rounds finished (must equal the plan)
  * ``full_restarts``         — job re-runs (0 = elastic recovery worked)
  * ``degraded_rounds``       — rounds aggregated below the bought replica
                                count (quorum + deadline path)
  * ``stale_deltas_dropped``  — late deltas rejected by round tag
  * ``rejoins`` / ``rejoin_latency_ms`` — replacement workers caught up via
                                the cumulative-update protocol

PS scenarios (``kill-ps:<round>`` / ``partition-ps:<round>:<seconds>``)
target the parameter server instead: the job runs with a checkpoint dir
(durable journal, hypha_tpu.ft.durable), the harness restarts the PS node
under the same peer id after a kill, and the result additionally reports
``ps_recoveries`` / ``retry_attempts`` / ``ps_journal_bytes`` /
``recovery_wall_s`` (chaos fire → the next round closing).

Called by ``tests/test_durable.py``, ``tests/test_sched_recovery.py`` and
``tests/test_metrics_plane.py``.
"""

from __future__ import annotations

import asyncio
import sys
import tempfile
import time
from pathlib import Path

import numpy as np


def _log(msg: str) -> None:
    print(f"[ftbench] {msg}", file=sys.stderr, flush=True)


def run_chaos_scenario(
    spec: "str | None" = "kill-worker:1",
    num_workers: int = 4,
    rounds: int = 4,
    quorum_fraction: float = 0.75,
    round_deadline_s: float = 6.0,
    trace_dir: "str | None" = None,
    model_scale: int = 1,
    metrics_plane: bool = False,
    metrics_dir: "str | None" = None,
    slo_rules: "list | None" = None,
    metrics_interval_s: float = 0.25,
    samples_per_round: int = 24,
) -> dict:
    # Scheduler scenarios run the dedicated two-pass harness (no-kill
    # baseline + chaos run, final weights compared bit-for-bit).
    parts_probe = [p.strip() for p in (spec or "").split(",") if p.strip()]
    if any(
        p.startswith(("kill-scheduler", "partition-scheduler"))
        for p in parts_probe
    ):
        return run_scheduler_scenario(
            spec or "kill-scheduler:2", rounds=rounds, trace_dir=trace_dir
        )
    return _run_worker_ps_scenario(
        spec, num_workers, rounds, quorum_fraction, round_deadline_s,
        trace_dir, model_scale, metrics_plane, metrics_dir, slo_rules,
        metrics_interval_s, samples_per_round,
    )


def _run_worker_ps_scenario(
    spec: "str | None",
    num_workers: int,
    rounds: int,
    quorum_fraction: float,
    round_deadline_s: float,
    trace_dir: "str | None",
    model_scale: int,
    metrics_plane: bool = False,
    metrics_dir: "str | None" = None,
    slo_rules: "list | None" = None,
    metrics_interval_s: float = 0.25,
    samples_per_round: int = 24,
) -> dict:
    """Run one chaos scenario; returns the result dict.

    ``spec=None`` runs the same orchestrated topology with NO fault
    injected. ``trace_dir`` turns on end-to-end round tracing
    (telemetry.trace) and flight-recorder spill into that directory for
    the run's duration. ``model_scale`` multiplies the toy model's width
    so the delta grows (obsbench's bw-cap run needs uploads that dwarf
    compute). ``metrics_plane`` turns on the live metrics plane
    (telemetry.metrics_plane): every node reports registry deltas to the
    scheduler's collector, training-quality series ride the round
    metrics, and the result grows a ``metrics_plane`` section (fleet
    rollups, loss curves, SLO state, journal path).
    """
    from safetensors.numpy import save_file

    from hypha_tpu.aio import wait_quiet
    from hypha_tpu.data_node import DataNode
    from hypha_tpu.ft import ChaosController, FTConfig, parse_chaos_spec
    from hypha_tpu.gateway import Gateway
    from hypha_tpu.messages import Adam, ModelType, Nesterov, PriceRange
    from hypha_tpu.network import MemoryTransport, Node
    from hypha_tpu.resources import Resources
    from hypha_tpu.scheduler.job_config import DiLoCoJob, DiLoCoRounds, JobResources
    from hypha_tpu.scheduler.metrics_bridge import CallbackConnector
    from hypha_tpu.scheduler.orchestrator import Orchestrator
    from hypha_tpu.telemetry.ft_metrics import FT_METRICS, HET_METRICS
    from hypha_tpu.worker.arbiter import OfferConfig
    from hypha_tpu.worker.runtime import WorkerNode

    from hypha_tpu.telemetry import trace
    from hypha_tpu.telemetry.flight import FLIGHT

    FT_METRICS.reset()
    HET_METRICS.reset()
    if trace_dir is not None:
        trace.enable(trace_dir, node="bench")
        FLIGHT.clear()
        FLIGHT.configure(node="bench", spill_dir=trace_dir)
    # PS scenarios (kill-ps / partition-ps) target the parameter server's
    # worker node; worker scenarios target the second allocated worker.
    # The spec may compose several comma-separated actions (degrade modes
    # like bw-cap name their peer inline and ride along with an event).
    parts = [p.strip() for p in (spec or "").split(",") if p.strip()]
    ps_scenario = any(
        p.startswith(("kill-ps", "partition-ps")) for p in parts
    )
    actions = [
        parse_chaos_spec(
            p, "psw" if p.startswith(("kill-ps", "partition-ps")) else "w1"
        )
        for p in parts
    ]
    kill_actions = [a for a in actions if a.kind == "kill"]
    victim = (
        next((a.target for a in actions if a.kind.endswith("ps")), None)
        or (kill_actions[0].target if kill_actions else None)
        or (actions[0].target if actions else None)
    )
    tmp = Path(tempfile.mkdtemp(prefix="hypha-ftbench-"))

    vocab, seq = 32, 16

    def make_dataset() -> Path:
        d = tmp / "toy"
        d.mkdir()
        rng = np.random.default_rng(0)
        for i in range(4):
            ids = rng.integers(0, vocab, (8, seq)).astype(np.int32)
            save_file({"input_ids": ids}, str(d / f"slice_{i:04d}.safetensors"))
        return d

    async def main() -> dict:
        hub = MemoryTransport()
        gw = Gateway(hub.shared(), peer_id="gw")
        await gw.start()
        boot = [gw.node.listen_addrs[0]]
        data = DataNode(hub.shared(), {"toy": make_dataset()}, peer_id="data",
                        bootstrap=boot)
        await data.start()

        def mk_worker(name: str) -> WorkerNode:
            return WorkerNode(
                hub.shared(),
                resources=Resources(tpu=2.0, cpu=8, memory=1000),
                peer_id=name,
                offer=OfferConfig(price=1.0, strategy="whole"),
                bootstrap=boot,
                work_root=tmp / name,
            )

        workers = {f"w{i}": mk_worker(f"w{i}") for i in range(num_workers)}
        for w in workers.values():
            await w.start()
        psw = WorkerNode(
            hub.shared(), resources=Resources(cpu=2, memory=200),
            peer_id="psw", bootstrap=boot, work_root=tmp / "psw",
        )
        await psw.start()
        sched = Node(hub.shared(), peer_id="sched", bootstrap=boot)
        await sched.start()
        await sched.wait_for_bootstrap()

        chaos = ChaosController(list(actions), {**workers, "psw": psw})
        rounds_seen: set[int] = set()
        metric_times: list[tuple[int, float]] = []

        def on_metric(w, r, n, v):
            metric_times.append((r, time.monotonic()))
            chaos.on_round_metrics(r)
            rounds_seen.add(r)

        orch = Orchestrator(sched, metrics_connector=CallbackConnector(on_metric))
        job = DiLoCoJob(
            model={
                "model_type": ModelType.CAUSAL_LM,
                "family": "gpt2",
                "config": {
                    "vocab_size": vocab, "n_positions": seq,
                    "n_embd": 16 * max(int(model_scale), 1),
                    "n_layer": 1, "n_head": 2,
                },
                "seed": 7,
            },
            dataset="toy",
            rounds=DiLoCoRounds(
                update_rounds=rounds,
                avg_samples_between_updates=max(int(samples_per_round), 1),
                max_batch_size=4,
            ),
            inner_optimizer=Adam(lr=1e-3),
            outer_optimizer=Nesterov(lr=0.7, momentum=0.9),
            resources=JobResources(
                num_workers=num_workers,
                worker=Resources(tpu=1.0, cpu=1.0, memory=10),
                parameter_server=Resources(cpu=1.0, memory=10),
                worker_price=PriceRange(bid=1.0, max=10.0),
                parameter_server_price=PriceRange(bid=1.0, max=10.0),
            ),
            ft=FTConfig(
                quorum_fraction=quorum_fraction,
                round_deadline_s=round_deadline_s,
                rejoin_attempts=8,
                rejoin_backoff_s=1.0,
                ps_restart_attempts=4,
                ps_restart_backoff_s=0.5,
            ),
            # Durable PS state lives under the checkpoint dir — required
            # for the kill-ps recovery path (journal + outer checkpoint).
            checkpoint_dir=str(tmp / "ckpt") if ps_scenario else None,
            metrics_plane=metrics_plane,
            metrics_interval_s=metrics_interval_s,
            metrics_dir=metrics_dir,
            slo_rules=list(slo_rules or []),
        )

        replacement = mk_worker(f"{victim}b") if kill_actions else None
        ps_addr = None  # captured before the kill; the restart re-binds it
        replacement_ps: dict = {}

        async def restarter() -> None:
            if replacement is None and not any(
                a.kind == "kill-ps" for a in actions
            ):
                return  # degrade-only scenarios have nothing to restart
            # Degrade actions fire at attach (round 0); only a KILL firing
            # should trigger the restart machinery.
            while not any(a.kind in ("kill", "kill-ps") for a in chaos.fired):
                await asyncio.sleep(0.05)
            if replacement is not None:
                _log(f"restarting victim as {victim}b")
                await replacement.start([f"mem:restart-{victim}b"])
            if any(a.kind == "kill-ps" for a in chaos.fired):
                # The PS process "restarts": a fresh node under the SAME
                # peer id and listen address (workers' push targets were
                # wired to it at dispatch). Its durable journal under the
                # job checkpoint dir is what makes this a recovery, not a
                # round-zero restart.
                await asyncio.sleep(0.3)  # let the kill finish severing
                _log("restarting parameter server node psw")
                new_psw = WorkerNode(
                    hub.shared(), resources=Resources(cpu=2, memory=200),
                    peer_id="psw", bootstrap=boot, work_root=tmp / "psw2",
                )
                for _ in range(25):
                    try:
                        await new_psw.start([ps_addr] if ps_addr else None)
                        break
                    except OSError:
                        # The dying node still holds its listen address.
                        await asyncio.sleep(0.2)
                replacement_ps["node"] = new_psw

        ps_addr = psw.node.listen_addrs[0]
        restart_task = asyncio.create_task(restarter())
        t0 = time.monotonic()
        try:
            result = await orch.run(
                job, auction_timeout=1.5, status_timeout=60.0, max_attempts=1
            )
        finally:
            restart_task.cancel()
            stops = list(workers.values()) + [psw]
            if replacement is not None:
                stops.append(replacement)
            if replacement_ps.get("node") is not None:
                stops.append(replacement_ps["node"])
            for w in stops:
                await wait_quiet(w.stop())
            await data.stop()
            await sched.stop()
            await gw.stop()
        wall_s = time.monotonic() - t0
        # Recovery wall-clock: chaos fire -> the first metric of a round
        # that COMPLETED after the fire (a same-round metric racing the
        # fire is pre-fault progress, not recovery).
        fired_at = chaos.fired_at(victim)
        recovery_wall_s = None
        if fired_at is not None:
            floor = max(
                (r for r, t in metric_times if t <= fired_at), default=-1
            )
            after = [t for r, t in metric_times if t > fired_at and r > floor]
            if after:
                recovery_wall_s = after[0] - fired_at
        snap = FT_METRICS.snapshot()
        latency_ms = (
            snap["rejoin_latency_ms_sum"] / snap["rejoin_latency_ms_count"]
            if snap["rejoin_latency_ms_count"]
            else None
        )
        # Per-round walls from the FIRST metric event of each round (the
        # interval between successive round closes): what obsbench compares
        # traced vs untraced, immune to the auction/startup fixed cost.
        first_metric: dict[int, float] = {}
        for r, t in metric_times:
            first_metric.setdefault(r, t)
        ordered = sorted(first_metric)
        round_walls = [
            round(first_metric[b] - first_metric[a], 4)
            for a, b in zip(ordered, ordered[1:])
        ]
        metrics_summary = None
        if metrics_plane and orch.metrics is not None:
            store = orch.metrics.store
            # PEAK upload rate per peer: a blocking round drags every
            # peer's average down to the straggler's pace, but only the
            # capped link's burst rate never exceeds its cap — the rollup
            # the bw-cap outlier probe reads.
            peak_mbps = store.fleet_peak("node.bandwidth_out_mbps")
            outlier = store.outlier(
                "node.bandwidth_out_mbps", values=peak_mbps
            )
            metrics_summary = {
                "reports": orch.metrics.reports,
                "journal": (
                    str(orch.metrics.journal_path)
                    if orch.metrics.journal_path is not None
                    else None
                ),
                "bandwidth_out_mbps": {
                    p: round(v, 4) for p, v in peak_mbps.items()
                },
                "bandwidth_outlier": (
                    {"peer": outlier[0], "mbps": round(outlier[1], 4)}
                    if outlier is not None
                    else None
                ),
                "loss_rounds": {
                    str(r): {p: round(v, 6) for p, v in peers.items()}
                    for r, peers in store.quality_rounds("loss").items()
                },
                "slo": orch.metrics.watchdog.state(),
            }
        return {
            "metric": "ft_chaos_rounds_completed",
            "value": result.rounds,
            "unit": "rounds",
            "scenario": spec,
            "chaos_target": victim,
            "num_workers": num_workers,
            "planned_rounds": rounds,
            "rounds_completed": result.rounds,
            "full_restarts": result.attempt,
            "quorum_fraction": quorum_fraction,
            "round_deadline_s": round_deadline_s,
            "degraded_rounds": snap["degraded_rounds"],
            "quorum_drops": HET_METRICS.snapshot()["quorum_drops"],
            "stale_deltas_dropped": snap["stale_deltas_dropped"],
            "suspected_peers": snap["suspected_peers"],
            "rejoins": snap["rejoins"],
            "ps_recoveries": snap["ps_recoveries"],
            "retry_attempts": snap["retry_attempts"],
            "ps_journal_bytes": snap["ps_journal_bytes"],
            "recovery_wall_s": (
                round(recovery_wall_s, 2) if recovery_wall_s is not None else None
            ),
            "rejoin_latency_ms": round(latency_ms, 1) if latency_ms else None,
            "membership": result.ft,
            "wall_s": round(wall_s, 1),
            "round_walls_s": round_walls,
            "trace_dir": trace_dir,
            "metrics_plane": metrics_summary,
            "vs_baseline": None,  # the seed aborts the whole job here
        }

    try:
        return asyncio.run(asyncio.wait_for(main(), timeout=600))
    finally:
        if trace_dir is not None:
            FLIGHT.spill()
            FLIGHT.disarm()  # a later untraced run must not spill here
            trace.disable()


def _ps_final_state(ckpt: Path) -> "dict[str, bytes]":
    """The durable PS's final outer state, as raw bytes: every checkpoint
    tensor (momentum, catch-up Σ) plus each fragment's newest committed
    broadcast wire. Two runs whose dicts are equal aggregated every round
    bit-identically — the scheduler-outage acceptance criterion."""
    import json as _json

    from safetensors.numpy import load_file

    psdir = ckpt / "ps"
    meta = _json.loads((psdir / "ps-state.json").read_text())
    out: dict[str, bytes] = {}
    for key, value in load_file(str(psdir / meta["state_file"])).items():
        out[f"state/{key}"] = (
            str(value.dtype).encode()
            + str(value.shape).encode()
            + value.tobytes()
        )
    for frag, (rnd, name) in (meta.get("last_wires") or {}).items():
        out[f"wire/{frag}/{rnd}"] = (psdir / "wires" / name).read_bytes()
    return out


def run_scheduler_scenario(
    spec: str = "kill-scheduler:2",
    num_workers: int = 3,
    rounds: int = 4,
    round_deadline_s: float = 60.0,
    trace_dir: "str | None" = None,
) -> dict:
    """Scheduler-outage scenario (``kill-scheduler:<round>`` /
    ``partition-scheduler:<round>:<s>``), two passes:

      1. a NO-FAULT baseline of the identical job;
      2. the chaos run — for a kill, the scheduler node is severed
         mid-round, the ``orch.run`` coroutine is cancelled (process
         death), and a NEW node under the same peer id + listen address
         runs a fresh Orchestrator whose ``run`` finds the journal and
         re-adopts the live executions.

    The job is built for bit-exactness (3 workers, blocking f32,
    IDENTICAL dataset slices, sample budget == one batch so every worker
    runs exactly one inner batch per round regardless of timing): the
    final durable PS state of the two passes must match byte-for-byte —
    the outage cost wall-clock, never arithmetic. Asserted bounds: all
    rounds complete, zero full job restarts, weights bit-equal, added
    wall-clock at most one baseline round + a fixed restart budget.
    """
    from hypha_tpu.aio import wait_quiet
    from hypha_tpu.data_node import DataNode
    from hypha_tpu.ft import ChaosController, FTConfig, parse_chaos_specs
    from hypha_tpu.gateway import Gateway
    from hypha_tpu.messages import Adam, ModelType, Nesterov, PriceRange
    from hypha_tpu.network import MemoryTransport, Node
    from hypha_tpu.resources import Resources
    from hypha_tpu.scheduler.job_config import DiLoCoJob, DiLoCoRounds, JobResources
    from hypha_tpu.scheduler.metrics_bridge import CallbackConnector
    from hypha_tpu.scheduler.orchestrator import Orchestrator
    from hypha_tpu.telemetry import trace
    from hypha_tpu.telemetry.flight import FLIGHT
    from hypha_tpu.telemetry.ft_metrics import FT_METRICS, HET_METRICS
    from hypha_tpu.worker.arbiter import OfferConfig
    from hypha_tpu.worker.runtime import WorkerNode

    from safetensors.numpy import save_file

    if trace_dir is not None:
        trace.enable(trace_dir, node="bench")
        FLIGHT.clear()
        FLIGHT.configure(node="bench", spill_dir=trace_dir)
    actions_spec = spec
    kill = "kill-scheduler" in spec
    tmp = Path(tempfile.mkdtemp(prefix="hypha-schedbench-"))
    vocab, seq = 32, 16

    def make_dataset() -> Path:
        # IDENTICAL slices: slice assignment order varies run to run, so
        # bit-equality needs every worker to see the same data whichever
        # slice it draws (identical deltas also make the weighted fold's
        # float-addition order irrelevant).
        d = tmp / "toy"
        d.mkdir(exist_ok=True)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, vocab, (8, seq)).astype(np.int32)
        for i in range(4):
            save_file({"input_ids": ids}, str(d / f"slice_{i:04d}.safetensors"))
        return d

    dataset_dir = make_dataset()

    class _SchedProc:
        """Chaos target wrapper: .node + .stop(), the kill interface."""

        def __init__(self, node: Node) -> None:
            self.node = node

        async def stop(self) -> None:
            pass

    async def one_run(inject: bool, ckpt: Path) -> dict:
        hub = MemoryTransport()
        gw = Gateway(hub.shared(), peer_id="gw")
        await gw.start()
        boot = [gw.node.listen_addrs[0]]
        data = DataNode(hub.shared(), {"toy": dataset_dir}, peer_id="data",
                        bootstrap=boot)
        await data.start()

        def mk_worker(name: str) -> WorkerNode:
            return WorkerNode(
                hub.shared(),
                resources=Resources(tpu=2.0, cpu=8, memory=1000),
                peer_id=name,
                offer=OfferConfig(price=1.0, strategy="whole"),
                bootstrap=boot,
                work_root=tmp / f"{name}-{ckpt.name}",
            )

        workers = {f"w{i}": mk_worker(f"w{i}") for i in range(num_workers)}
        for w in workers.values():
            await w.start()
        psw = WorkerNode(
            hub.shared(), resources=Resources(cpu=2, memory=200),
            peer_id="psw", bootstrap=boot, work_root=tmp / f"psw-{ckpt.name}",
        )
        await psw.start()
        sched = Node(hub.shared(), peer_id="sched", bootstrap=boot)
        await sched.start()
        await sched.wait_for_bootstrap()
        sched_addr = sched.listen_addrs[0]

        rounds_seen: set[int] = set()
        metric_times: list[tuple[int, float]] = []
        chaos = None

        def on_metric(w, r, n, v):
            metric_times.append((r, time.monotonic()))
            if chaos is not None:
                chaos.on_round_metrics(r)
            rounds_seen.add(r)

        connector = CallbackConnector(on_metric)
        if inject:
            actions = parse_chaos_specs(actions_spec, "sched")
            chaos = ChaosController(
                actions,
                {**workers, "psw": psw, "sched": _SchedProc(sched)},
            )
        job = DiLoCoJob(
            model={
                "model_type": ModelType.CAUSAL_LM,
                "family": "gpt2",
                "config": {
                    "vocab_size": vocab, "n_positions": seq,
                    "n_embd": 16, "n_layer": 1, "n_head": 2,
                },
                "seed": 7,
            },
            dataset="toy",
            rounds=DiLoCoRounds(
                # Sample budget == ONE worker batch: the projection hands
                # every worker counter 0 at its first Status of the round,
                # pinning exactly one inner batch per worker per round —
                # timing (and the outage) cannot change the arithmetic.
                update_rounds=rounds, avg_samples_between_updates=2,
                max_batch_size=2,
            ),
            inner_optimizer=Adam(lr=1e-3),
            outer_optimizer=Nesterov(lr=0.7, momentum=0.9),
            resources=JobResources(
                num_workers=num_workers,
                worker=Resources(tpu=1.0, cpu=1.0, memory=10),
                parameter_server=Resources(cpu=1.0, memory=10),
                worker_price=PriceRange(bid=1.0, max=10.0),
                parameter_server_price=PriceRange(bid=1.0, max=10.0),
            ),
            ft=FTConfig(
                quorum_fraction=0.75,
                # Deadline far past the outage: no quorum-dropped delta
                # may change the mean between the two passes.
                round_deadline_s=round_deadline_s,
                rejoin_attempts=4,
                rejoin_backoff_s=1.0,
                ps_restart_attempts=2,
                ps_restart_backoff_s=0.5,
                scheduler_adopt_grace_s=60.0,
                scheduler_adopt_deadline_s=15.0,
            ),
            checkpoint_dir=str(ckpt),
            scheduler_recovery=True,
        )
        orch = Orchestrator(sched, metrics_connector=connector)
        t0 = time.monotonic()
        recovery_wall_s = None
        stops: list = []
        try:
            run_task = asyncio.create_task(
                orch.run(
                    job, auction_timeout=1.5, status_timeout=120.0,
                    max_attempts=1,
                )
            )
            if inject and kill:
                while not run_task.done() and not any(
                    a.kind == "kill-scheduler" for a in chaos.fired
                ):
                    await asyncio.sleep(0.05)
                if not run_task.done():
                    # Process death: the node is severed (chaos), the
                    # orchestrator coroutine dies with it.
                    await asyncio.sleep(0.3)
                    run_task.cancel()
                await asyncio.gather(run_task, return_exceptions=True)
                _log("scheduler killed; restarting under the same peer id")
                sched2 = Node(hub.shared(), peer_id="sched", bootstrap=boot)
                for _ in range(25):
                    try:
                        await sched2.start([sched_addr])
                        break
                    except OSError:
                        await asyncio.sleep(0.2)
                await sched2.wait_for_bootstrap()
                stops.append(sched2)
                orch2 = Orchestrator(sched2, metrics_connector=connector)
                result = await orch2.run(
                    job, auction_timeout=1.5, status_timeout=120.0,
                    max_attempts=1,
                )
            else:
                result = await run_task
        finally:
            for w in list(workers.values()) + [psw]:
                await wait_quiet(w.stop())
            for n in stops:
                await wait_quiet(n.stop())
            await data.stop()
            await wait_quiet(sched.stop())
            await gw.stop()
        wall_s = time.monotonic() - t0
        fired_at = chaos.fired_at("sched") if chaos is not None else None
        if fired_at is not None:
            floor = max(
                (r for r, t in metric_times if t <= fired_at), default=-1
            )
            after = [t for r, t in metric_times if t > fired_at and r > floor]
            if after:
                recovery_wall_s = after[0] - fired_at
        first_metric: dict[int, float] = {}
        for r, t in metric_times:
            first_metric.setdefault(r, t)
        ordered = sorted(first_metric)
        round_walls = [
            round(first_metric[b] - first_metric[a], 4)
            for a, b in zip(ordered, ordered[1:])
        ]
        return {
            "rounds": result.rounds,
            "attempt": result.attempt,
            "wall_s": wall_s,
            "round_walls_s": round_walls,
            "recovery_wall_s": recovery_wall_s,
            "membership": result.ft,
        }

    FT_METRICS.reset()
    HET_METRICS.reset()
    baseline = asyncio.run(
        asyncio.wait_for(one_run(False, tmp / "ckpt-base"), timeout=300)
    )
    base_state = _ps_final_state(tmp / "ckpt-base")
    FT_METRICS.reset()
    HET_METRICS.reset()
    try:
        chaos_run = asyncio.run(
            asyncio.wait_for(one_run(True, tmp / "ckpt-chaos"), timeout=300)
        )
    finally:
        if trace_dir is not None:
            FLIGHT.spill()
            FLIGHT.disarm()
            trace.disable()
    chaos_state = _ps_final_state(tmp / "ckpt-chaos")
    snap = FT_METRICS.snapshot()
    bit_equal = base_state == chaos_state
    added_wall_s = chaos_run["wall_s"] - baseline["wall_s"]
    max_round_wall = max(baseline["round_walls_s"] or [1.0])
    # One round of added wall-clock + a fixed restart budget (node rebind,
    # journal replay, adoption handshake) — the acceptance bound.
    restart_budget_s = 10.0
    line = {
        "metric": "sched_chaos_rounds_completed",
        "value": chaos_run["rounds"],
        "unit": "rounds",
        "scenario": spec,
        "num_workers": num_workers,
        "planned_rounds": rounds,
        "rounds_completed": chaos_run["rounds"],
        "baseline_rounds": baseline["rounds"],
        "full_restarts": chaos_run["attempt"],
        "scheduler_recoveries": snap["scheduler_recoveries"],
        "adopted_executions": snap["adopted_executions"],
        "stale_generation_dropped": snap["stale_generation_dropped"],
        "retry_attempts": snap["retry_attempts"],
        "weights_bit_equal": bit_equal,
        "recovery_wall_s": (
            round(chaos_run["recovery_wall_s"], 2)
            if chaos_run["recovery_wall_s"] is not None
            else None
        ),
        "baseline_wall_s": round(baseline["wall_s"], 1),
        "wall_s": round(chaos_run["wall_s"], 1),
        "added_wall_s": round(added_wall_s, 2),
        "max_baseline_round_wall_s": round(max_round_wall, 3),
        "added_wall_bound_s": round(max_round_wall + restart_budget_s, 2),
        "round_walls_s": chaos_run["round_walls_s"],
        "membership": chaos_run["membership"],
        "trace_dir": trace_dir,
        "vs_baseline": None,  # the seed loses the whole job here
    }
    assert chaos_run["rounds"] == rounds, (
        f"lost rounds: {chaos_run['rounds']}/{rounds}"
    )
    assert baseline["rounds"] == rounds
    assert chaos_run["attempt"] == 0, "job was fully restarted"
    assert bit_equal, "final weights differ from the no-kill run"
    if kill:
        assert snap["scheduler_recoveries"] >= 1, "no scheduler recovery ran"
        assert snap["adopted_executions"] >= num_workers, (
            "adoption handshake reached too few executions"
        )
    assert added_wall_s <= max_round_wall + restart_budget_s, (
        f"outage cost {added_wall_s:.1f}s > one round "
        f"({max_round_wall:.1f}s) + {restart_budget_s:.0f}s budget"
    )
    return line
