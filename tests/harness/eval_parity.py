"""Eval-loss parity: our jitted JAX train step vs the reference's torch loop.

BASELINE.json's metric line demands "eval-loss parity vs CUDA/accelerate
path". This harness trains the SAME model (GPT-2 architecture, identical
initial weights via the checkpoint converter) on the SAME token stream with
the SAME optimizer (AdamW, no clipping — the reference's loop is plain
zero_grad/backward/step, training.py:106-116) in BOTH stacks and compares
the loss trajectories step by step
(``tests/test_convert.py::test_training_loop_loss_parity_vs_torch``).
"""

from __future__ import annotations

import numpy as np

LR = 1e-3
WD = 0.01  # torch AdamW default; set explicitly in both stacks
BETAS = (0.9, 0.999)
EPS = 1e-8


def torch_losses(hf_model, ids: np.ndarray, steps: int) -> list[float]:
    import torch

    model = hf_model.train()
    opt = torch.optim.AdamW(
        model.parameters(), lr=LR, betas=BETAS, eps=EPS, weight_decay=WD
    )
    batch = torch.from_numpy(ids)
    out = []
    for _ in range(steps):
        opt.zero_grad()
        loss = model(input_ids=batch, labels=batch).loss
        loss.backward()
        opt.step()
        out.append(float(loss.detach()))
    return out


def jax_losses(hf_model, state_dict, ids: np.ndarray, steps: int) -> list[float]:
    import jax
    import optax

    from hypha_tpu.executor.train import TrainState, make_train_step
    from hypha_tpu.models import GPT2, GPT2Config
    from hypha_tpu.models.convert import convert_state_dict

    hf_cfg = hf_model.config
    cfg = GPT2Config(
        vocab_size=hf_cfg.vocab_size,
        n_positions=hf_cfg.n_positions,
        n_embd=hf_cfg.n_embd,
        n_layer=hf_cfg.n_layer,
        n_head=hf_cfg.n_head,
        dtype="float32",
    )
    model = GPT2(cfg)
    template = model.init(jax.random.key(0), ids)
    params = convert_state_dict("gpt2", state_dict, template)

    tx = optax.adamw(LR, b1=BETAS[0], b2=BETAS[1], eps=EPS, weight_decay=WD)
    state = TrainState.create(params, tx)
    step = make_train_step(model.apply)
    out = []
    batch = {"input_ids": ids}
    for _ in range(steps):
        state, metrics = step(state, batch)
        out.append(float(metrics["loss"]))
    return out
