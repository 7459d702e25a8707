"""Checkpoint/resume tests (net-new vs reference — SURVEY.md §5 records the
reference has none; BASELINE preemption configs require it)."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypha_tpu.executor.checkpoint import (
    latest_manifest,
    load_train_checkpoint,
    save_train_checkpoint,
)
from hypha_tpu.executor.train import TrainState, build_optimizer
from hypha_tpu.messages import Adam


def make_state(seed=0):
    from hypha_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=16, n_positions=8, n_embd=8, n_layer=1, n_head=2)
    model = GPT2(cfg)
    params = model.init(jax.random.key(seed), np.zeros((1, 8), np.int32))
    return model, TrainState.create(params, build_optimizer(Adam(lr=1e-3)))


def test_train_checkpoint_round_trip(tmp_path):
    model, state = make_state()
    # advance the optimizer so opt_state has non-trivial moments
    grads = jax.tree.map(jnp.ones_like, state.params)
    state = state.apply_gradients(grads)
    save_train_checkpoint(
        tmp_path / "ck", state.params, state.opt_state, int(state.step), 3,
        extra={"note": "x"},
    )
    _, fresh = make_state(seed=1)
    restored = load_train_checkpoint(tmp_path / "ck", fresh.params, fresh.opt_state)
    assert restored is not None
    r_params, r_opt, r_step, r_round, extra = restored
    assert r_step == 1 and r_round == 3 and extra == {"note": "x"}
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(r_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(state.opt_state), jax.tree.leaves(r_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_absent_checkpoint_returns_none(tmp_path):
    _, state = make_state()
    assert load_train_checkpoint(tmp_path / "nope", state.params, state.opt_state) is None


def test_checkpoint_shape_mismatch_fails_loudly(tmp_path):
    _, state = make_state()
    save_train_checkpoint(
        tmp_path / "ck", state.params, state.opt_state, 0, 0
    )
    from hypha_tpu.models import GPT2, GPT2Config

    other = GPT2(GPT2Config(vocab_size=32, n_positions=8, n_embd=8, n_layer=1, n_head=2))
    other_params = other.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    other_state = TrainState.create(other_params, build_optimizer(Adam()))
    with pytest.raises((ValueError, KeyError)):
        load_train_checkpoint(tmp_path / "ck", other_state.params, other_state.opt_state)


def test_ps_momentum_checkpoint_copy(tmp_path):
    """The PS copies its momentum file into the checkpoint dir atomically
    (ps_executor._checkpoint_momentum) and restores it on restart."""
    from safetensors.numpy import load_file, save_file

    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    m = {"a/w": np.arange(4, dtype=np.float32), "b": np.ones(2, np.float32)}
    momentum_file = tmp_path / "momentum.safetensors"
    save_file(m, str(momentum_file))
    ckpt = tmp_path / "ckpt"
    ParameterServerExecutor._checkpoint_momentum(momentum_file, ckpt)
    got = load_file(str(ckpt / "momentum.safetensors"))
    np.testing.assert_array_equal(got["a/w"], m["a/w"])
    assert not [p for p in ckpt.iterdir() if p.name.startswith(".momentum")]
    # absent momentum file is a no-op
    ParameterServerExecutor._checkpoint_momentum(tmp_path / "nope", ckpt)


def test_versioned_save_updates_pointer_and_prunes(tmp_path):
    _, state = make_state()
    d = tmp_path / "ck"
    save_train_checkpoint(d, state.params, state.opt_state, 1, 1)
    assert latest_manifest(d)["round"] == 1
    save_train_checkpoint(d, state.params, state.opt_state, 2, 2)
    save_train_checkpoint(d, state.params, state.opt_state, 3, 3)
    assert latest_manifest(d)["round"] == 3
    versions = [p.name for p in d.iterdir() if p.is_dir() and p.name.startswith("v")]
    assert len(versions) == 2  # pruned to the last two complete checkpoints
    # no stray staging/tmp entries
    assert not [p for p in d.iterdir() if p.name.startswith(".staging")]
    # a torn LATEST (pointing at a removed version) fails loudly
    (d / "LATEST").write_text("v99999999-9")
    with pytest.raises(ValueError, match="names missing"):
        load_train_checkpoint(d, state.params, state.opt_state)


@pytest.mark.slow
def test_job_resumes_from_checkpoint(tmp_path):
    """Two successive jobs sharing a checkpoint dir: the second starts from
    the first's weights (step counter keeps growing; resume logged)."""
    import asyncio
    import dataclasses

    from tests.test_e2e import diloco_job, start_cluster

    async def main():
        from hypha_tpu.scheduler.orchestrator import Orchestrator
        from hypha_tpu.worker.arbiter import LEASE_TIMEOUT_S

        hub, gw, data, workers, sched = await start_cluster(tmp_path)
        orch = Orchestrator(sched)
        job = diloco_job(rounds=1)
        job.checkpoint_dir = str(tmp_path / "ckpt")

        async def read_manifests(done) -> dict:
            # Workers write their checkpoint just AFTER the scheduler sees
            # completion (the save follows UpdateReceived in the executor
            # thread) — poll until the expected content appears.
            found = {}
            for _ in range(100):
                found = {}
                for sub in (tmp_path / "ckpt").glob("*"):
                    m = latest_manifest(sub)
                    if m is not None:
                        found[sub.name] = m
                if done(found):
                    return found
                await asyncio.sleep(0.1)
            return found

        def both(found):
            return {"w0", "w1"} <= set(found)

        try:
            await orch.run(job, auction_timeout=1.5)
            manifests_1 = await read_manifests(both)
            # Released leases are left to lapse before the resources are
            # free for the next auction.
            await asyncio.sleep(LEASE_TIMEOUT_S + 1)
            await orch.run(job, auction_timeout=1.5)
            manifests_2 = await read_manifests(
                lambda found: both(found)
                and all(
                    found[w]["step"] != manifests_1[w]["step"] for w in ("w0", "w1")
                )
            )
        finally:
            for w in workers:
                await w.stop()
            await data.stop()
            await sched.stop()
            await gw.stop()
        return manifests_1, manifests_2

    m1, m2 = asyncio.run(asyncio.wait_for(main(), 240))
    assert {"w0", "w1"} <= set(m1)
    for w in ("w0", "w1"):
        assert m2[w]["step"] > m1[w]["step"], (w, m1[w], m2[w])
    # PS momentum persisted
    assert (tmp_path / "ckpt" / "ps" / "momentum.safetensors").exists()
