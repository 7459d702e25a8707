"""BASELINE.json config 4 — "Llama-2-7B inference serving" — on ONE chip.

The reference lists this as a north-star scenario and ships no inference
path at all; here it runs end to end on a single v5e: 6.74B params are
materialized directly on the device in bf16 (13.5 GB — an f32 tree would
not fit the 16 GB HBM),
then the serving primitive (executor/generate.py: KV-cached prefill + one
compiled ``lax.scan`` decode loop) generates with a 1024-token cache.

Weights are random — the measurement is the serving compute path: at
18.7 ms/token the decode reads 13.5 GB of weights per step ≈ 720 GB/s
effective, ~88% of the chip's HBM bandwidth spec — i.e. bandwidth-optimal
decode. Real checkpoints load through models/convert.py the same way the
eval-parity harness does; they only change the numbers in the logits.

Run:  python benchmarks/llama7b_serving.py
"""

from __future__ import annotations

import json
import sys
import time


def main() -> None:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.generate import generate
    from hypha_tpu.models import Llama
    from hypha_tpu.models.llama import LlamaConfig

    import dataclasses

    # llama2-7b architecture via its named constructor, cache capped at 1k.
    cfg = dataclasses.replace(LlamaConfig.llama2_7b(), max_seq_len=1024)
    model = Llama(cfg)
    B, P, N = 1, 128, 128
    ids = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)

    t0 = time.perf_counter()
    template = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    leaves, treedef = jax.tree.flatten(template)
    n_params = sum(l.size for l in leaves)
    key = jax.random.key(42)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        out.append(
            jax.jit(
                lambda k=k, shape=leaf.shape: jax.random.normal(
                    k, shape, jnp.bfloat16
                )
                * 0.02
            )()
        )
    params = jax.tree.unflatten(treedef, out)
    jax.block_until_ready(out[-1])
    materialize_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    o = generate(model, params, ids, N)
    int(jax.device_get(o[0, 0]))  # value fetch = hard sync
    compile_s = time.perf_counter() - t0

    x = ids
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        x = generate(model, params, x, N)  # chained on data dependency
    int(jax.device_get(x[0, -1]))
    dt = (time.perf_counter() - t0) / reps

    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "model": "llama2-7b architecture (random bf16 weights)",
                "params": n_params,
                "platform": dev.platform,
                "device_kind": getattr(dev, "device_kind", ""),
                "batch": B,
                "prompt_len": P,
                "new_tokens": N,
                "decode_tokens_per_sec": round(B * N / dt, 1),
                "ms_per_token": round(dt * 1e3 / N, 1),
                "effective_weight_read_gbps": round(n_params * 2 / (dt / N) / 1e9, 0),
                "materialize_s": round(materialize_s, 0),
                "compile_s": round(compile_s, 0),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
