"""Sharded parameter service: aggregate delta bytes/s and round wall-clock
at 1 / 2 / 4 PS shards, fixed worker count — plus a real-executor
``--chaos kill-ps`` recovery scenario against ONE shard.

Two measurements:

  * **round pipeline model** — per shard count N, one blocking DiLoCo
    round is replayed with MEASURED aggregation costs (real
    ``stream.accum.RoundAccum`` folds over real delta files, the real
    ``ParameterServerExecutor._outer_step`` Nesterov, real
    ``compress.write_delta`` broadcast encodes — each shard owning the
    real ``stream.partition`` part of a transformer-shaped tree) and a
    MODELED wire (per-peer NIC bandwidth + latency — the only
    non-measured term, parameters in the output, same convention as
    streambench). A single PS takes all W workers' deltas through ONE
    NIC; N shards each take W·S/N bytes and aggregate concurrently, so
    the round's wall-clock is the slowest shard's pipeline and the
    aggregate delta bandwidth scales with N instead of being pinned to
    one peer's NIC.

  * **chaos kill-ps** (``--chaos kill-ps``) — REAL
    ``ParameterServerExecutor`` shards over the memory fabric, stream
    F=2 over N=2: shard 1 is killed between its rounds, shard 0 closes
    its own round DURING the outage (zero restarts anywhere else), shard
    1 restarts from its own durable journal under a bumped generation,
    and every broadcast update is asserted BIT-equal to an uninterrupted
    run's. Recovery wall-clock is recorded.

Run:  python benchmarks/shardbench.py [--params-m 4] [--workers 4]
      [--chaos kill-ps] [--out SHARDBENCH_r08.json]

Asserts (the PR's acceptance criteria):
  * aggregate delta bytes/s at 4 shards >= 2.5x the single PS's,
  * round wall-clock at 4 shards <= 0.6x the single PS's,
  * (chaos) recovered updates bit-equal, surviving shard closed its
    round during the outage, zero full-job restarts.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from safetensors.numpy import load_file, save_file  # noqa: E402

from hypha_tpu.stream import partition_names, shard_of  # noqa: E402
from hypha_tpu.stream.accum import RoundAccum  # noqa: E402

# Modeled wire (the only non-measured term): every peer — worker or PS
# shard — sits on a 1 Gb/s NIC, 20 ms one-way latency (streambench's
# convention).
WIRE_BANDWIDTH_BPS = 1e9 / 8  # bytes/second per NIC
WIRE_LATENCY_S = 0.020


def transformer_shapes(params_m: float) -> dict[str, tuple[int, ...]]:
    """Transformer-shaped tree: an embedding + 12 evenly sized blocks
    (enough leaves that a 4-way partition balances within ~1/4)."""
    total = int(params_m * 1e6)
    emb = int((total * 0.25) ** 0.5)
    shapes: dict[str, tuple[int, ...]] = {"wte": (emb, emb)}
    per_block = (total - emb * emb) // 12
    side = max(int((per_block / 4) ** 0.5), 8)
    for i in range(12):
        shapes[f"h{i}/attn"] = (side, side)
        shapes[f"h{i}/mlp_in"] = (side, 2 * side)
        shapes[f"h{i}/mlp_out"] = (2 * side, side)
        shapes[f"h{i}/ln"] = (2 * side,)
    return shapes


def _worker_delta(shapes, seed):
    rng = np.random.default_rng(seed)
    return {
        n: rng.standard_normal(np.prod(s)).astype(np.float32).reshape(s)
        for n, s in shapes.items()
    }


def measure_shard_pipeline(
    work: Path, shapes: dict, workers: int, num_shards: int
) -> dict:
    """Measure ONE shard's real aggregation work for one blocking round:
    fold W part-deltas (real files, real RoundAccum), run the real outer
    step, encode the broadcast. Shards are symmetric (LPT-balanced
    parts), so shard 0's costs stand in for the round."""
    from hypha_tpu.worker.ps_executor import (
        ParameterServerExecutor,
        _OuterMomentum,
    )

    sizes = {n: int(np.prod(s)) for n, s in shapes.items()}
    parts = partition_names(sizes, num_shards)
    my_names = parts[0]  # shard 0's part (shard_of(0, N) == 0)
    assert shard_of(0, num_shards) == 0
    shard_dir = work / f"shard-{num_shards}"
    shard_dir.mkdir(parents=True)

    # workers' part-deltas on disk, as the wire would deliver them
    files = []
    part_bytes = 0
    for w in range(workers):
        delta = _worker_delta(shapes, seed=1000 + w)
        part = {n: delta[n] for n in my_names}
        f = shard_dir / f"delta-w{w}.safetensors"
        save_file(part, str(f))
        part_bytes = f.stat().st_size
        files.append((f, 8.0))

    t0 = time.perf_counter()
    accum = RoundAccum()
    for f, samples in files:
        accum.fold(f, samples)
    fold_s = time.perf_counter() - t0

    momentum = _OuterMomentum(shard_dir / "momentum.safetensors", save=False)
    received = {f"w{i}": e for i, e in enumerate(files)}
    t0 = time.perf_counter()
    update_path = ParameterServerExecutor._outer_step(
        None, received, momentum, 0.7, 0.9, shard_dir, 0, accum
    )
    step_s = time.perf_counter() - t0

    from hypha_tpu import compress

    t0 = time.perf_counter()
    wire = shard_dir / "bcast.safetensors"
    compress.write_delta(wire, dict(load_file(str(update_path))), "bf16")
    encode_s = time.perf_counter() - t0
    bcast_bytes = wire.stat().st_size

    return {
        "part_bytes_per_worker": part_bytes,
        "fold_s": fold_s,
        "outer_step_s": step_s,
        "encode_s": encode_s,
        "broadcast_bytes": bcast_bytes,
    }


def model_round(costs: dict, workers: int, num_shards: int) -> dict:
    """One blocking round's wall-clock through the slowest (== any) shard:
    ingress wire, measured aggregation, broadcast fan-out wire."""
    ingress_bytes = workers * costs["part_bytes_per_worker"]
    wire_in_s = WIRE_LATENCY_S + ingress_bytes / WIRE_BANDWIDTH_BPS
    wire_out_s = (
        WIRE_LATENCY_S + workers * costs["broadcast_bytes"] / WIRE_BANDWIDTH_BPS
    )
    compute_s = costs["fold_s"] + costs["outer_step_s"] + costs["encode_s"]
    round_s = wire_in_s + compute_s + wire_out_s
    total_delta_bytes = num_shards * ingress_bytes  # whole tree, all workers
    return {
        "num_shards": num_shards,
        "round_wall_s": round_s,
        "shard_ingress_bytes": ingress_bytes,
        "total_delta_bytes_per_round": total_delta_bytes,
        "aggregate_delta_bytes_per_s": total_delta_bytes / round_s,
        "wire_in_s": wire_in_s,
        "wire_out_s": wire_out_s,
        "measured_compute_s": compute_s,
        **{k: costs[k] for k in ("fold_s", "outer_step_s", "encode_s")},
    }


# ----------------------------------------------------------- chaos kill-ps


def run_chaos_kill_ps(work: Path) -> dict:
    """Real executors over the memory fabric: stream F=2 over N=2 shards,
    shard 1 killed and restarted from its own journal while shard 0
    closes its round during the outage. Asserts bit-equal updates."""
    from hypha_tpu.ft.durable import GENERATION_KEY, RESYNC_KEY
    from hypha_tpu.messages import (
        PROTOCOL_PROGRESS,
        SHARD_KEY,
        AggregateExecutorConfig,
        Executor,
        JobSpec,
        Nesterov,
        Progress,
        ProgressResponse,
        ProgressResponseKind,
        Receive,
        Reference,
        Send,
    )
    from hypha_tpu.network import MemoryTransport, Node
    from hypha_tpu.stream import fragment_due
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    sizes = {"a": 4096, "b": 1024, "c": 4096, "d": 1024}
    shapes = {n: (s,) for n, s in sizes.items()}
    frags = partition_names(sizes, 2)
    rounds = 4

    async def one_run(label: str, kill: bool):
        hub = MemoryTransport()
        nodes = {
            p: Node(hub.shared(), peer_id=p)
            for p in ("ps0", "ps1", "w1", "sched")
        }
        for n in nodes.values():
            await n.start()
        for a in nodes.values():
            for b in nodes.values():
                if a is not b:
                    a.add_peer_addr(b.peer_id, b.listen_addrs[0])

        async def on_progress(peer, progress):
            if progress.round >= rounds - 2:
                return ProgressResponse(kind=ProgressResponseKind.DONE)
            return ProgressResponse(kind=ProgressResponseKind.OK)

        reg = nodes["sched"].on(PROTOCOL_PROGRESS, Progress).respond_with(
            on_progress
        )

        def spec_for(k):
            return JobSpec(
                job_id=f"bench-k{k}",
                executor=Executor(
                    kind="aggregate",
                    name="parameter-server",
                    aggregate=AggregateExecutorConfig(
                        updates=Receive(
                            Reference.from_peers(["w1"], f"updates.s{k}")
                        ),
                        results=Send(Reference.from_peers(["w1"], "results")),
                        optimizer=Nesterov(lr=0.7, momentum=0.9),
                        num_workers=1,
                        sync_mode="stream",
                        fragments=2,
                        shard_index=k,
                        num_ps_shards=2,
                        checkpoint_dir=str(work / label / f"ps{k}"),
                    ),
                ),
            )

        executions = {}
        for k in (0, 1):
            pse = ParameterServerExecutor(nodes[f"ps{k}"], work / f"w-{label}-{k}")
            executions[k] = await pse.execute(f"bench-k{k}", spec_for(k), "sched")

        async def push_frag(r):
            f_id = fragment_due(r, 2)
            owner = shard_of(f_id, 2)
            delta = {
                n: _worker_delta(shapes, seed=r)[n] for n in frags[f_id]
            }
            f = work / f"d-{label}-{r}.st"
            save_file(delta, str(f))
            await nodes["w1"].push(
                f"ps{owner}",
                {
                    "resource": f"updates.s{owner}",
                    "name": f.name,
                    "round": r,
                    "num_samples": 8.0,
                    SHARD_KEY: owner,
                    "fragment_id": f_id,
                    "fragments": 2,
                },
                f,
            )

        seen: dict[int, tuple[dict, dict]] = {}
        counter = [0]

        async def drain(expect):
            while expect not in seen:
                push = await nodes["w1"].next_push(timeout=30)
                meta = dict(push.resource)
                counter[0] += 1
                dest = work / f"u-{label}-{counter[0]}.st"
                await push.save_to(dest)
                if meta.get(RESYNC_KEY):
                    continue
                rnd = int(meta.get("round", -1))
                if rnd >= 0 and rnd not in seen:
                    seen[rnd] = (meta, dict(load_file(str(dest))))
            return seen[expect]

        updates = []
        for r in (0, 1):
            await push_frag(r)
            _, upd = await drain(r)
            updates.append(upd)
        recovery_s = 0.0
        gen = 1
        if kill:
            await executions[1].cancel()
        # shard 0 closes ITS round during the outage
        await push_frag(2)
        meta2, upd2 = await drain(2)
        assert int(meta2.get(SHARD_KEY, -1)) == 0
        if kill:
            t0 = time.perf_counter()
            pse = ParameterServerExecutor(nodes["ps1"], work / f"w-{label}-1b")
            executions[1] = await pse.execute("bench-k1", spec_for(1), "sched")
        await push_frag(3)
        meta3, upd3 = await drain(3)
        if kill:
            recovery_s = time.perf_counter() - t0
            gen = int(meta3.get(GENERATION_KEY, 1))
            assert gen >= 2, "restarted shard must announce a bumped generation"
        updates.extend([upd2, upd3])
        for k in (0, 1):
            status = await asyncio.wait_for(executions[k].wait(), 30)
            assert status.state == "completed", (k, status.message)
        reg.close()
        for n in nodes.values():
            await n.stop()
        return updates, recovery_s, gen

    async def main():
        clean, _, _ = await one_run("clean", kill=False)
        killed, recovery_s, gen = await one_run("killed", kill=True)
        for i, (a, b) in enumerate(zip(clean, killed)):
            for name in a:
                assert np.array_equal(a[name], b[name]), (
                    f"update {i} tensor {name} diverged after shard kill"
                )
        return recovery_s, gen

    recovery_s, gen = asyncio.run(asyncio.wait_for(main(), 180))
    return {
        "scenario": "kill-ps (shard 1 of 2, stream F=2)",
        "rounds": rounds,
        "bit_equal_vs_no_kill": True,
        "surviving_shard_closed_round_during_outage": True,
        "full_job_restarts": 0,
        "recovery_wall_s": recovery_s,
        "restarted_shard_generation": gen,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--params-m", type=float, default=4.0)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--shards", type=int, nargs="*", default=[1, 2, 4])
    ap.add_argument(
        "--chaos", choices=["kill-ps"], default=None,
        help="also run the real-executor kill-one-shard recovery scenario",
    )
    ap.add_argument("--out", default="SHARDBENCH_r08.json")
    args = ap.parse_args(argv)

    shapes = transformer_shapes(args.params_m)
    work = Path(tempfile.mkdtemp(prefix="shardbench-"))
    try:
        results = []
        for n in args.shards:
            costs = measure_shard_pipeline(work, shapes, args.workers, n)
            results.append(model_round(costs, args.workers, n))
            r = results[-1]
            print(
                f"shards={n}: round {r['round_wall_s']*1e3:8.1f} ms, "
                f"aggregate {r['aggregate_delta_bytes_per_s']/1e6:8.1f} MB/s "
                f"(shard ingress {r['shard_ingress_bytes']/1e6:.1f} MB, "
                f"measured compute {r['measured_compute_s']*1e3:.1f} ms)"
            )
        by_n = {r["num_shards"]: r for r in results}
        out = {
            "bench": "shardbench",
            "params_m": args.params_m,
            "workers": args.workers,
            "wire_model": {
                "bandwidth_bps": WIRE_BANDWIDTH_BPS,
                "latency_s": WIRE_LATENCY_S,
                "note": (
                    "per-peer NIC; the only non-measured term — fold, outer "
                    "step and broadcast encode are measured on real files"
                ),
            },
            "rounds": results,
        }
        if 1 in by_n and 4 in by_n:
            speedup = (
                by_n[4]["aggregate_delta_bytes_per_s"]
                / by_n[1]["aggregate_delta_bytes_per_s"]
            )
            wall_ratio = by_n[4]["round_wall_s"] / by_n[1]["round_wall_s"]
            out["aggregate_bytes_per_s_speedup_4x_vs_1"] = speedup
            out["round_wall_ratio_4_vs_1"] = wall_ratio
            print(
                f"aggregate bytes/s speedup 4 shards vs 1: {speedup:.2f}x "
                f"(round wall {wall_ratio:.2f}x)"
            )
            assert speedup >= 2.5, (
                f"aggregate delta bandwidth must scale ~linearly: "
                f"{speedup:.2f}x < 2.5x at 4 shards"
            )
            assert wall_ratio <= 0.6, (
                f"round wall-clock must shrink with shards: {wall_ratio:.2f}"
            )
        if args.chaos == "kill-ps":
            print("chaos: kill-ps against shard 1 of 2 (real executors)...")
            out["chaos"] = run_chaos_kill_ps(work)
            print(
                f"chaos: recovered bit-exactly in "
                f"{out['chaos']['recovery_wall_s']:.2f}s "
                f"(generation {out['chaos']['restarted_shard_generation']}, "
                f"0 full restarts)"
            )
        Path(args.out).write_text(json.dumps(out, indent=2))
        print(f"wrote {args.out}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
