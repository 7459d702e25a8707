"""Heterogeneous-pool harness: WAN-adaptive vs static outer rounds.

Stands up the full in-process topology (gateway + data node + 4 train
workers + parameter server + scheduler on the memory fabric — the same
harness as ``ft_chaos.py``) with elastic membership enabled and a
reproducibly heterogeneous pool (hypha_tpu.ft.chaos degrade modes):

  * ``w1`` bandwidth-capped to a fraction of a megabit — its f32 delta
    upload cannot fit inside the round deadline;
  * ``w2`` slow-CPU by 4x — every inner batch takes 4x its natural
    wall-clock.

``tests/test_het.py::test_quorum_drop_vs_adapt_e2e`` (slow) runs it twice:

  * **static**   — `adaptive_steps: off`, one job-wide codec: the capped
    peer is quorum-dropped every round (its compute is wasted) and every
    round stalls to the deadline waiting for it;
  * **adaptive** — straggler-adaptive inner steps + per-link codec
    selection (hypha_tpu.ft.adaptive): the slow-CPU peer is assigned
    ~k/4 steps, the capped link degrades to int4 (8x fewer bytes), and
    every delta lands inside the deadline.

The data slices are deliberately IDENTICAL so run-to-run loss differences
isolate the scheduling/codec changes, not data-order luck; ``chaos=None``
gives the uniform no-chaos pool.
"""

from __future__ import annotations

import asyncio
import sys
import tempfile
import time
from pathlib import Path

import numpy as np


def _log(msg: str) -> None:
    print(f"[hetbench] {msg}", file=sys.stderr, flush=True)


# The heterogeneity under test: one link capped so an f32 delta upload
# takes ~9 s (far past the round deadline — but inside the adaptive
# first-round measurement grace), one CPU 4x slower. The deadline sits
# comfortably ABOVE benign in-process skew (4 workers share one Python
# process; jit compiles and the GIL add seconds of jitter), so the only
# peer that can ever miss it is the capped one — in the uniform reference
# pool every delta lands early and rounds close on arrival, deadline
# untouched.
DEFAULT_CHAOS = "bw-cap:w1:0.015,slow-worker:w2:4"


def run_het_scenario(
    adaptive: bool,
    chaos: "str | None" = DEFAULT_CHAOS,
    num_workers: int = 4,
    rounds: int = 4,
    quorum_fraction: float = 0.75,
    round_deadline_s: float = 5.0,
) -> dict:
    """One orchestrated run; returns the per-run metrics dict."""
    from safetensors.numpy import save_file

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
    from hypha_tpu.telemetry.ft_metrics import FT_METRICS, HET_METRICS

    FT_METRICS.reset()
    HET_METRICS.reset()
    tmp = Path(tempfile.mkdtemp(prefix="hypha-hetbench-"))
    vocab, seq = 32, 16

    def make_dataset() -> Path:
        d = tmp / "toy"
        d.mkdir()
        # IDENTICAL slices on purpose: every worker sees the same tokens
        # in every run, so the final-loss comparison isolates the
        # scheduling/codec changes instead of slice-assignment luck.
        rng = np.random.default_rng(0)
        ids = rng.integers(0, vocab, (8, seq)).astype(np.int32)
        for i in range(4):
            save_file({"input_ids": ids}, str(d / f"slice_{i:04d}.safetensors"))
        return d

    async def main() -> dict:
        # The whole topology shares ONE process and ONE asyncio default
        # executor; its size is cpu_count+4, and the 4 in-process training
        # loops each hold a slot for the entire job (worker.train_executor
        # runs run_training via to_thread). On a small host that starves
        # every other to_thread (PS folds, file reads) for seconds and
        # corrupts the timing this bench exists to measure — give the
        # harness a real pool.
        from concurrent.futures import ThreadPoolExecutor

        asyncio.get_running_loop().set_default_executor(
            ThreadPoolExecutor(max_workers=24, thread_name_prefix="hetbench")
        )
        hub = MemoryTransport()
        gw = Gateway(hub.shared(), peer_id="gw")
        await gw.start()
        boot = [gw.node.listen_addrs[0]]
        data = DataNode(hub.shared(), {"toy": make_dataset()}, peer_id="data",
                        bootstrap=boot)
        await data.start()

        from hypha_tpu.worker.arbiter import OfferConfig
        from hypha_tpu.worker.runtime import WorkerNode

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

        if chaos:
            actions = parse_chaos_specs(chaos, "w1")
            ChaosController(actions, {**workers, "psw": psw})

        metric_times: list[tuple[int, float]] = []
        losses: dict[str, dict[int, float]] = {}

        def on_metric(w, r, n, v):
            metric_times.append((r, time.monotonic()))
            if n == "loss" and np.isfinite(v):
                losses.setdefault(str(w), {})[int(r)] = float(v)

        orch = Orchestrator(sched, metrics_connector=CallbackConnector(on_metric))
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
                update_rounds=rounds, avg_samples_between_updates=128,
                max_batch_size=4,
            ),
            inner_optimizer=Adam(lr=2e-3),
            # Plain outer SGD at a small lr for the CONVERGENCE-PARITY
            # comparison: the adaptive and uniform runs differ ONLY
            # through their merged outer updates (outer lr -> 0 makes the
            # final losses bit-equal — measured), and momentum would
            # compound the bounded, intended per-run update differences
            # (straggler deltas at fewer steps, one int4 link) by
            # ~1/(1-mu). At this scale the 1e-3 parity bound measures the
            # adaptation's bias, not toy-trajectory chaos.
            outer_optimizer=Nesterov(lr=0.03, momentum=0.0),
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
                rejoin_attempts=0,
            ),
            adaptive_steps=adaptive,
            adaptive_codec=adaptive,
            # Loopback measures tens-to-hundreds of Mbit/s; the capped
            # link sits at 0.03 Mbit/s — thresholds well clear of both.
            codec_bw_hi_mbps=10.0,
            codec_bw_lo_mbps=1.0,
        )

        t0 = time.monotonic()
        try:
            result = await orch.run(
                job, auction_timeout=1.5, status_timeout=90.0, max_attempts=1
            )
        finally:
            for w in list(workers.values()) + [psw]:
                await wait_quiet(w.stop())
            await data.stop()
            await sched.stop()
            await gw.stop()
        wall_s = time.monotonic() - t0
        het = HET_METRICS.snapshot()
        ft = FT_METRICS.snapshot()
        # Convergence probe: the FASTEST worker's last-round loss. w0 runs
        # the full base step count on the identical data stream in every
        # scenario, so its trajectory isolates what the merged outer
        # updates did — a straggler's own reported loss would instead
        # reflect how few LOCAL steps it ran that round.
        w0 = losses.get("w0") or {}
        final_loss = w0[max(w0)] if w0 else None
        # Steady-state round wall: rounds AFTER the first metric — round 0
        # carries jit compile (and the adaptive run's one-time first-round
        # measurement grace), which neither mode can avoid.
        by_round = {}
        for r, t in metric_times:
            by_round[r] = max(t, by_round.get(r, 0.0))
        closes = [by_round[r] for r in sorted(by_round)]
        steady = np.diff(closes) if len(closes) > 1 else [wall_s / max(rounds, 1)]
        return {
            "adaptive": adaptive,
            "chaos": chaos,
            "rounds_completed": result.rounds,
            "wall_s": round(wall_s, 2),
            "round_wall_s": round(float(np.mean(steady)), 3),
            "quorum_drops": het["quorum_drops"],
            "quorum_drops_by_round": het["quorum_drops_by_round"],
            "stale_deltas_dropped": ft["stale_deltas_dropped"],
            "degraded_rounds": ft["degraded_rounds"],
            "assigned_steps": het["assigned_steps"],
            "peer_codecs": het["peer_codecs"],
            "codec_counts": het["codec_counts"],
            "codec_switches": het["codec_switches"],
            "bandwidth_bps": {
                p: round(b, 1) for p, b in het["bandwidth_bps"].items()
            },
            "final_loss": final_loss,
        }

    return asyncio.run(asyncio.wait_for(main(), timeout=600))
