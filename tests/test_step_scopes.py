"""The step's ``jax.named_scope`` names reach the lowered program, so a
device event's metadata says which part of the step it belongs to
(docs/observability.md, "Beside a profiler trace")."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from hypha_tpu.executor.train import TrainState, make_train_step
from hypha_tpu.models.llama import Llama, LlamaConfig
from hypha_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def op_names():
    model = Llama(LlamaConfig.tiny(), attn_impl=flash_attention)
    ids = jnp.zeros((2, 128), jnp.int32)
    state = TrainState.create(model.init(jax.random.key(0), ids), optax.adamw(1e-3))
    step = make_train_step(model.apply, donate=False)
    text = step.lower(state, {"input_ids": ids}).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("scope,under", [
    ("embed", "jvp(Llama)"), ("attention", "jvp(Llama)"),
    ("attention/flash_attention", "jvp(Llama)"),
    ("attention/flash_attention/flash_attention_bwd", "transpose(jvp(Llama))"),
    ("mlp", "jvp(Llama)"), ("lm_head", "jvp(Llama)"),
    ("jvp(loss)", "jit(step)"), ("transpose(jvp(loss))", "jit(step)"),
    ("optimizer", "jit(step)"),
])
def test_scope_names_an_operation_of_the_step(op_names, scope, under):
    assert any(f"/{scope}/" in n and under in n for n in op_names), scope
