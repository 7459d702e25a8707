"""Llama-2-7B LoRA fine-tune step on ONE chip, real converted weights.

The reference's fine-tune story is full-parameter torch/Accelerate — at 7B
that cannot fit a single accelerator (grads + AdamW moments for 6.7B
params). The TPU-native answer measured here: the frozen bf16 base streams
from the sharded HF repo straight to device (13.5 GB), rank-8 LoRA
adapters on q/v projections train in f32 (~4M params, executor/lora.py),
and the jitted step (forward + low-rank backward + AdamW on adapters,
remat per block) runs at S=512 within the 16 GB HBM.

Dataset: counting sequences (learnable), so the loss must actually fall —
this is a training proof, not a throughput fiction.

Run:  python benchmarks/llama7b_lora.py [ckpt_dir]
"""

from __future__ import annotations

import dataclasses
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent

B, S, STEPS, RANK = 1, 512, 12, 8


def main(ckpt: str = "/tmp/llama2_7b", smoke: str = "") -> None:
    import jax
    import jax.numpy as jnp

    from hypha_tpu.executor.lora import make_lora_train_step, split_lora
    from hypha_tpu.executor.train import TrainState, build_optimizer
    from hypha_tpu.messages import Adam
    from hypha_tpu.models import Llama
    from hypha_tpu.models.convert import convert_checkpoint
    from hypha_tpu.models.llama import LlamaConfig

    global S
    if smoke == "--smoke":
        # CPU wiring check: same code path over a tiny torch-written repo.
        jax.config.update("jax_platforms", "cpu")
        import tempfile

        import torch
        import transformers

        S = 32
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            tie_word_embeddings=False,
        )
        ckpt = tempfile.mkdtemp(prefix="lora_smoke_")
        transformers.LlamaForCausalLM(hf_cfg).save_pretrained(
            ckpt, safe_serialization=True
        )
        base = LlamaConfig.from_hf(hf_cfg.to_dict())
    else:
        base = LlamaConfig.llama2_7b()
    cfg = dataclasses.replace(
        base,
        max_seq_len=S,
        dtype="bfloat16",
        remat=True,
        lora_rank=RANK,
    )
    model = Llama(cfg)
    probe = np.zeros((B, S), np.int32)

    t0 = time.time()
    template = jax.eval_shape(lambda: model.init(jax.random.key(0), probe))
    adapters_t, frozen_t = split_lora(template)
    # Frozen base: streamed from the sharded repo to device in bf16.
    frozen = convert_checkpoint(
        "llama", Path(ckpt), frozen_t,
        dtype=jnp.bfloat16, put=lambda _n, a: jax.device_put(a),
    )
    # Adapters: tiny, seed-initialized on device in f32. A ~ N(0, 0.02),
    # B = 0 (the no-op-at-init invariant) — classified by leaf NAME, not
    # shape, so no rank/width coincidence can flip it.
    paths, treedef = jax.tree_util.tree_flatten_with_path(adapters_t)
    init = []
    for i, (path, leaf) in enumerate(paths):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.key(42), i)
        init.append(
            jax.jit(
                lambda k=k, shape=leaf.shape:
                jax.random.normal(k, shape, jnp.float32) * 0.02
            )()
            if name.endswith("_lora_a")
            else jnp.zeros(leaf.shape, jnp.float32)
        )
    adapters = jax.tree.unflatten(treedef, init)
    n_frozen = sum(x.size for x in jax.tree_util.tree_leaves(frozen))
    n_adapt = sum(x.size for x in jax.tree_util.tree_leaves(adapters))
    # Sync by value fetch, or load_s would time the enqueue.
    float(jnp.sum(init[-1]))
    float(jax.tree_util.tree_leaves(frozen)[-1].astype(jnp.float32).sum())
    load_s = time.time() - t0
    print(
        f"base {n_frozen/1e9:.2f}B bf16 on device in {load_s:.0f}s; "
        f"adapters {n_adapt/1e6:.2f}M f32 "
        f"({100 * n_adapt / n_frozen:.3f}% of base)",
        flush=True,
    )

    state = TrainState.create(adapters, build_optimizer(Adam(lr=3e-3)))
    step = make_lora_train_step(model.apply)

    # One FIXED counting batch: pure memorization signal, so the loss must
    # fall if and only if gradients actually reach the adapters.
    rng = np.random.default_rng(0)
    starts = rng.integers(0, cfg.vocab_size - S - 1, (B, 1))
    fixed = {
        "input_ids": (
            (starts + np.arange(S)[None, :]) % cfg.vocab_size
        ).astype(np.int32)
    }

    def batch():
        return fixed

    t0 = time.time()
    state, metrics = step(state, frozen, batch())
    first_loss = float(metrics["loss"])  # value fetch = hard sync
    compile_s = time.time() - t0

    losses = [first_loss]
    t0 = time.time()
    for _ in range(STEPS):
        state, metrics = step(state, frozen, batch())
        losses.append(float(metrics["loss"]))  # per-step sync: honest timing
    dt = (time.time() - t0) / STEPS

    dev = jax.devices()[0]
    out = {
        "model": "llama2-7b REAL converted weights, LoRA r=8 q/v, bf16 base",
        "checkpoint": str(ckpt),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "base_params": n_frozen,
        "adapter_params": n_adapt,
        "batch": B,
        "seq_len": S,
        "steps": STEPS,
        "load_s": round(load_s, 0),
        "compile_s": round(compile_s, 0),
        "step_ms": round(dt * 1e3, 1),
        "tokens_per_sec": round(B * S / dt, 1),
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "loss_fell": losses[-1] < losses[0],
        "peak_host_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2
        ),
        "note": "full-parameter 7B training needs grads+moments for 6.7B "
                "params (~81 GB f32) — impossible on one 16 GB chip; LoRA "
                "is the single-chip fine-tune path, multi-chip full tuning "
                "is the fsdp mesh (see MULTICHIP artifacts)",
    }
    if smoke != "--smoke":
        (REPO / "TRAIN7B_r04.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
