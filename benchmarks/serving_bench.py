"""Serving (inference) throughput on one chip: KV-cached decode tokens/s.

The reference ships no inference path at all (BASELINE.json's "inference
serving" entry is a north star, not a feature), so there is no reference
number to beat — this records what the TPU-native serving primitive
(executor/generate.py: one prefill forward + one compiled ``lax.scan``
decode loop) delivers on real hardware, per batch size.

Run on hardware:

    python benchmarks/serving_bench.py
"""

from __future__ import annotations

import json
import sys
import time


def _bench(B: int, prompt_len: int, new_tokens: int) -> dict:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.generate import generate
    from hypha_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config.small()
    model = GPT2(cfg)
    ids = jax.random.randint(
        jax.random.key(1), (B, prompt_len), 0, cfg.vocab_size
    )
    params = model.init(jax.random.key(0), ids)
    # Serve in bf16 like the infer executor (halves the per-step weight
    # read; at B=1 the gain can hide under dispatch latency — B≥8 rows
    # are the stable numbers here).
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params,
    )

    assert prompt_len == new_tokens, "chaining needs prompt_len == new_tokens"
    t0 = time.perf_counter()
    out = generate(model, params, ids, new_tokens)
    int(jax.device_get(out[0, 0]))  # value fetch = hard sync
    compile_s = time.perf_counter() - t0

    # Chain each rep on the previous output (generated tokens become the
    # next prompt): a data dependency plus a final value fetch proves
    # every rep actually executed.
    reps = 5
    x = ids
    t0 = time.perf_counter()
    for _ in range(reps):
        x = generate(model, params, x, new_tokens)
    _ = int(jax.device_get(x[0, -1]))
    dt = (time.perf_counter() - t0) / reps
    return {
        "batch": B,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_tokens_per_sec": round(B * new_tokens / dt, 1),
        "requests_per_sec": round(B / dt, 2),
        "latency_ms": round(dt * 1e3, 1),
        "compile_s": round(compile_s, 1),
    }


def _llama124m_spec() -> dict:
    """A GPT-2-small-sized Llama (pool-capable family) for scheduling
    comparisons: same depth/width as the headline model, llama lineage so
    the continuous pool engages."""
    return {"family": "llama", "config": {
        "vocab_size": 32000, "hidden_size": 768, "intermediate_size": 2048,
        "num_layers": 12, "num_heads": 12, "num_kv_heads": 12,
        "max_seq_len": 1024,
    }}


def _late_arrival(scheduling: str, reps: int = 3, pool_chunk: int = 8) -> dict:
    """VERDICT r4 weak #4 / r5 task 3: a request arriving MID-DECODE.

    One long request (256 new tokens) starts decoding; 0.3 s later four
    short requests (16 tokens) arrive. Under the window batcher they wait
    for the entire in-flight decode; under the continuous pool they admit
    into free KV rows at the next chunk boundary. Reports the shorts' p50
    latency and the long request's completion time.
    """
    import asyncio
    import statistics

    from hypha_tpu.messages import Executor, InferExecutorConfig, JobSpec
    from hypha_tpu.network.fabric import MemoryTransport
    from hypha_tpu.network.node import Node
    from hypha_tpu.worker.infer_executor import (
        InProcessInferExecutor,
        generate_remote,
    )

    LONG_NEW, SHORT_NEW = 256, 16
    spec_model = _llama124m_spec()
    vocab = spec_model["config"]["vocab_size"]

    async def run() -> dict:
        hub = MemoryTransport()
        gw = Node(hub.shared(), peer_id="gw", registry_server=True)
        await gw.start()
        worker = Node(hub.shared(), peer_id="w", bootstrap=[gw.listen_addrs[0]])
        client = Node(hub.shared(), peer_id="c", bootstrap=[gw.listen_addrs[0]])
        await worker.start(); await client.start()
        await worker.wait_for_bootstrap(5); await client.wait_for_bootstrap(5)
        ex = InProcessInferExecutor(worker)
        spec = JobSpec(
            job_id="bench-late",
            executor=Executor(
                kind="infer", name="generate",
                infer=InferExecutorConfig(
                    model=spec_model, serve_name="late",
                    max_batch=8, max_new_tokens=LONG_NEW,
                    scheduling=scheduling,
                    pool_slots=8, pool_max_len=512, pool_chunk=pool_chunk,
                    batch_window_ms=4.0,
                ),
            ),
        )
        execution = await ex.execute("bench-late", spec, "s")
        deadline = time.perf_counter() + 600
        while time.perf_counter() < deadline:
            if await client.find_providers("serve:late"):
                break
            await asyncio.sleep(1.0)
        long_prompt = [7 * j % vocab for j in range(16)]
        shorts = [[(11 * i + j) % vocab for j in range(16)] for i in range(4)]
        # Warm EVERY shape the measurement can hit: the long decode, a
        # single short, and the coalesced B=4 short (the window batcher
        # gathers the 4 concurrent shorts into one decode — unwarmed, its
        # ~14 s compile would masquerade as scheduling latency).
        await generate_remote(client, "late", [long_prompt], LONG_NEW, timeout=600)
        await generate_remote(client, "late", [shorts[0]], SHORT_NEW, timeout=600)
        await asyncio.gather(*(
            generate_remote(client, "late", [p], SHORT_NEW, timeout=600)
            for p in shorts
        ))

        short_lat: list[float] = []
        long_wall: list[float] = []
        for _ in range(reps):
            t0 = time.perf_counter()
            long_task = asyncio.create_task(
                generate_remote(client, "late", [long_prompt], LONG_NEW, timeout=600)
            )
            await asyncio.sleep(0.3)  # the long decode is now in flight

            async def timed(p):
                t = time.perf_counter()
                out = await generate_remote(client, "late", [p], SHORT_NEW, timeout=600)
                assert len(out[0]) == SHORT_NEW
                return time.perf_counter() - t

            lats = await asyncio.gather(*(timed(p) for p in shorts))
            short_lat.extend(lats)
            await long_task
            long_wall.append(time.perf_counter() - t0)
        await execution.cancel()
        await client.stop(); await worker.stop(); await gw.stop()
        return {
            "scheduling": scheduling,
            "pool_chunk": pool_chunk if scheduling == "continuous" else None,
            "short_p50_ms": round(statistics.median(short_lat) * 1e3, 1),
            "short_max_ms": round(max(short_lat) * 1e3, 1),
            "long_wall_s": round(statistics.median(long_wall), 2),
            "reps": reps,
            "protocol": f"1x{LONG_NEW}-tok decode in flight, 4x{SHORT_NEW}-tok "
                        "arrive 0.3s later",
        }

    return asyncio.run(run())


def _concurrent_clients(
    n_clients: int, batched: bool, model_spec=None, scheduling: str = "window",
    pool_chunk: int = 8,
) -> dict:
    """End-to-end through the infer executor over the in-memory fabric:
    ``n_clients`` concurrent requests, with the cross-request batching
    window on (one coalesced decode) or off (max_batch=1 — the pre-r4
    independent-decode behavior), or the continuous pool
    (``scheduling="continuous"``). The wall clock spans first request to
    last response, so queuing and response splitting are all in the number.
    """
    import asyncio

    from hypha_tpu.messages import Executor, InferExecutorConfig, JobSpec
    from hypha_tpu.network.fabric import MemoryTransport
    from hypha_tpu.network.node import Node
    from hypha_tpu.worker.infer_executor import (
        InProcessInferExecutor,
        generate_remote,
    )

    # 128 new tokens: long enough that the comparison measures DECODE
    # throughput — at 32 tokens both sides were dominated by per-dispatch
    # latency and the ratio understated the batching win.
    PROMPT_LEN, NEW = 16, 128
    if model_spec is None:
        model_spec = {"family": "gpt2", "config": {
            "vocab_size": 50257, "n_positions": 1024, "n_embd": 768,
            "n_layer": 12, "n_head": 12,
        }}
    vocab = model_spec["config"]["vocab_size"]

    async def run() -> dict:
        hub = MemoryTransport()
        gw = Node(hub.shared(), peer_id="gw", registry_server=True)
        await gw.start()
        worker = Node(hub.shared(), peer_id="w", bootstrap=[gw.listen_addrs[0]])
        client = Node(hub.shared(), peer_id="c", bootstrap=[gw.listen_addrs[0]])
        await worker.start(); await client.start()
        await worker.wait_for_bootstrap(5); await client.wait_for_bootstrap(5)
        ex = InProcessInferExecutor(worker)
        spec = JobSpec(
            job_id="bench-serve",
            executor=Executor(
                kind="infer", name="generate",
                infer=InferExecutorConfig(
                    model=model_spec, serve_name="bench",
                    max_batch=n_clients if batched else 1,
                    scheduling=scheduling,
                    pool_slots=n_clients, pool_max_len=512,
                    pool_chunk=pool_chunk,
                    # negative window = the true pre-r4 path: independent
                    # to_thread decodes under handler concurrency 4, no
                    # chip lock.
                    batch_window_ms=25.0 if batched else -1.0,
                ),
            ),
        )
        execution = await ex.execute("bench-serve", spec, "s")
        prompts = [[(7 * i + j) % vocab for j in range(PROMPT_LEN)]
                   for i in range(n_clients)]
        # Model load + first jit can take tens of seconds —
        # longer than generate_remote's 30 s discovery cap — so wait for
        # the serve announcement explicitly before the warmup.
        deadline = time.perf_counter() + 600
        while time.perf_counter() < deadline:
            if await client.find_providers("serve:bench"):
                break
            await asyncio.sleep(1.0)
        # Warm both decode shapes out of the measurement.
        await generate_remote(client, "bench", [prompts[0]], NEW, timeout=600)
        if batched:
            await asyncio.gather(*(
                generate_remote(client, "bench", [p], NEW, timeout=600)
                for p in prompts
            ))
        b = ex.batchers.get("bench-serve")
        before = (getattr(b, "decodes", 0), b.requests) if b else (0, 0)
        t0 = time.perf_counter()
        outs = await asyncio.gather(*(
            generate_remote(client, "bench", [p], NEW, timeout=600)
            for p in prompts
        ))
        wall = time.perf_counter() - t0
        assert all(len(o) == 1 and len(o[0]) == NEW for o in outs)
        # Deltas over the measured window only (warmups excluded).
        stats = (
            {"decodes": getattr(b, "decodes", 0) - before[0],
             "requests": b.requests - before[1]}
            if b else {"decodes": len(prompts), "requests": len(prompts)}
        )
        if hasattr(b, "chunks"):
            stats["pool_chunks"] = b.chunks
        await execution.cancel()
        await client.stop(); await worker.stop(); await gw.stop()
        return {
            "clients": n_clients,
            "batched": batched,
            "aggregate_tokens_per_sec": round(n_clients * NEW / wall, 1),
            "wall_s": round(wall, 2),
            **stats,
        }

    return asyncio.run(run())


def main() -> None:
    import jax

    dev = jax.devices()[0]
    results: dict = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "model": "gpt2-small 124M bf16",
    }
    for B in (1, 8, 32):
        try:
            results[f"decode_B{B}"] = _bench(B, prompt_len=128, new_tokens=128)
        except Exception as e:
            results[f"decode_B{B}"] = {"error": f"{type(e).__name__}: {e}"[:160]}
    # VERDICT r4 item 2: aggregate serving throughput at 16 concurrent
    # clients, batching window vs the old independent-decode behavior.
    for batched in (True, False):
        key = "clients16_batched" if batched else "clients16_independent"
        try:
            results[key] = _concurrent_clients(16, batched)
        except Exception as e:
            results[key] = {"error": f"{type(e).__name__}: {e}"[:160]}
    # VERDICT r5 task 3: continuous batching. Same 16-client burst through
    # the pool (aggregate must hold the window path's win), plus the
    # late-arrival protocol the window path structurally loses.
    # pool_chunk is the dispatch-amortization knob: each chunk pays one
    # host round-trip, so small
    # chunks favor admission latency and large chunks favor aggregate
    # throughput. Record both ends.
    for key, sched, chunk in (
        ("clients16_continuous_chunk8", "continuous", 8),
        ("clients16_continuous_chunk64", "continuous", 64),
        ("clients16_window_llama", "window", 8),
    ):
        try:
            results[key] = _concurrent_clients(
                16, True, model_spec=_llama124m_spec(), scheduling=sched,
                pool_chunk=chunk,
            )
        except Exception as e:
            results[key] = {"error": f"{type(e).__name__}: {e}"[:160]}
    for key, mode, chunk in (
        ("late_arrival_window", "window", 8),
        ("late_arrival_continuous", "continuous", 8),
        ("late_arrival_continuous_chunk32", "continuous", 32),
    ):
        try:
            results[key] = _late_arrival(mode, pool_chunk=chunk)
        except Exception as e:
            results[key] = {"error": f"{type(e).__name__}: {e}"[:160]}
    print(json.dumps(results))


if __name__ == "__main__":
    sys.exit(main())
