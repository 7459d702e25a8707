"""Parameter-server executor tests: native kernels, golden Nesterov vs
torch SGD(nesterov=True), and the full aggregate round over the fabric.

Reference: crates/worker/src/executor/parameter_server.rs (golden test
:448-524 uses torch SGD nesterov exactly like ours).
"""

from __future__ import annotations

import asyncio
import functools
import io

import numpy as np
import pytest

from hypha_tpu import native
from hypha_tpu.aio import retry


def test_native_kernel_compiles():
    # The toolchain is baked into this image; the C++ path must be active.
    assert native.native_available()


def test_nesterov_golden_vs_torch():
    """Outer step must match torch.optim.SGD(momentum=mu, nesterov=True):
    the update applied to params equals our 'update' tensor."""
    import torch

    rng = np.random.default_rng(7)
    lr, mu = 0.7, 0.9
    theta0 = rng.standard_normal(64).astype(np.float32)
    grads = [rng.standard_normal(64).astype(np.float32) for _ in range(5)]

    p = torch.nn.Parameter(torch.from_numpy(theta0.copy()))
    opt = torch.optim.SGD([p], lr=lr, momentum=mu, nesterov=True)
    m = np.zeros(64, np.float32)
    for g in grads:
        before = p.detach().numpy().copy()
        opt.zero_grad()
        p.grad = torch.from_numpy(g.copy())
        opt.step()
        torch_update = before - p.detach().numpy()  # what SGD subtracted
        m, update = native.nesterov_update(m, g, lr, mu)
        np.testing.assert_allclose(update, torch_update, rtol=1e-5, atol=1e-6)


def test_fused_equals_separate():
    """Sum / Σw then Nesterov in one in-place pass == weighted sum with
    normalized weights, then the separate Nesterov kernel."""
    rng = np.random.default_rng(3)
    srcs = [rng.standard_normal(256).astype(np.float32) for _ in range(4)]
    samples = np.asarray([4, 2, 1, 1], np.float32)
    m0 = rng.standard_normal(256).astype(np.float32)
    mean = sum(w * s for w, s in zip(samples / samples.sum(), srcs))
    m_a, upd_a = native.nesterov_update(m0, mean, 0.7, 0.9)
    upd_b, m_b = sum(w * s for w, s in zip(samples, srcs)), m0.copy()
    native.fused_mean_nesterov(upd_b, samples.sum(), m_b, 0.7, 0.9)
    np.testing.assert_allclose(m_a, m_b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(upd_a, upd_b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Full aggregate round over the fabric
# ---------------------------------------------------------------------------


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


def test_ps_executor_round(tmp_path):
    from safetensors.numpy import load_file, save_file

    from hypha_tpu.messages import (
        PROTOCOL_PROGRESS,
        AggregateExecutorConfig,
        Executor,
        JobSpec,
        Nesterov,
        Progress,
        ProgressKind,
        ProgressResponse,
        ProgressResponseKind,
        Receive,
        Reference,
        Send,
    )
    from hypha_tpu.network import MemoryTransport, Node
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    async def main():
        hub = MemoryTransport()
        ps = Node(hub.shared(), peer_id="ps")
        w1 = Node(hub.shared(), peer_id="w1")
        w2 = Node(hub.shared(), peer_id="w2")
        sched = Node(hub.shared(), peer_id="sched")
        for n in (ps, w1, w2, sched):
            await n.start()
        for x in (ps, w1, w2, sched):
            for y in (ps, w1, w2, sched):
                if x is not y:
                    x.add_peer_addr(y.peer_id, y.listen_addrs[0])

        updated_rounds = []

        async def on_progress(peer, progress):
            assert peer == "ps"
            assert progress.kind == ProgressKind.UPDATED
            updated_rounds.append(progress.round)
            # run two outer rounds, then DONE
            if progress.round >= 1:
                return ProgressResponse(kind=ProgressResponseKind.DONE)
            return ProgressResponse(kind=ProgressResponseKind.OK)

        sched.on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)

        peers_ref = Reference.from_peers(["w1", "w2"], "updates")
        spec = JobSpec(
            job_id="agg-1",
            executor=Executor(
                kind="aggregate",
                name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(peers_ref),
                    results=Send(peers_ref),
                    optimizer=Nesterov(lr=0.7, momentum=0.9),
                    num_workers=2,
                ),
            ),
        )
        pse = ParameterServerExecutor(ps, tmp_path)
        execution = await pse.execute("agg-1", spec, "sched")

        # each worker builds a delta and pushes it; w1 saw 3x the samples
        d1 = {"w": np.ones(8, np.float32), "b": np.full(4, 2.0, np.float32)}
        d2 = {"w": np.zeros(8, np.float32), "b": np.zeros(4, np.float32)}
        f1, f2 = tmp_path / "d1.st", tmp_path / "d2.st"
        save_file(d1, str(f1)); save_file(d2, str(f2))

        async def worker_round(node, f, samples):
            header = {"resource": "updates", "name": "delta", "num_samples": samples}
            await retry(
                lambda: node.push("ps", header, f),
                attempts=3, base_delay=0.05,
            )
            push = await node.next_push(timeout=10)  # the broadcast update
            dest = tmp_path / f"update-{node.peer_id}.st"
            await push.save_to(dest)
            return push.resource, dest

        (h1, u1), (h2, u2) = await asyncio.gather(
            worker_round(w1, f1, 300), worker_round(w2, f2, 100)
        )
        assert h1["round"] == 0 and h2["round"] == 0

        # expected: weighted mean g = 0.75*d1 + 0.25*d2; m=g; upd=lr*(mu*m+g)
        upd = load_file(str(u1))
        g_w = 0.75 * d1["w"] + 0.25 * d2["w"]
        expect_w = 0.7 * (0.9 * g_w + g_w)
        np.testing.assert_allclose(upd["w"], expect_w, rtol=1e-5)

        # round 2 -> scheduler says DONE -> execution completes
        await asyncio.gather(
            worker_round(w1, f1, 300), worker_round(w2, f2, 100)
        )
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == "completed"
        assert updated_rounds == [0, 1]
        for n in (ps, w1, w2, sched):
            await n.stop()

    run(main())


def test_ps_rejects_disallowed_and_replaces_duplicates(tmp_path):
    from safetensors.numpy import save_file

    from hypha_tpu.messages import (
        PROTOCOL_PROGRESS,
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
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    async def main():
        hub = MemoryTransport()
        ps = Node(hub.shared(), peer_id="ps")
        w1 = Node(hub.shared(), peer_id="w1")
        w2 = Node(hub.shared(), peer_id="w2")
        eve = Node(hub.shared(), peer_id="eve")
        sched = Node(hub.shared(), peer_id="sched")
        for n in (ps, w1, w2, eve, sched):
            await n.start()
        for n in (ps, w1, w2, eve, sched):
            for m_ in (ps, w1, w2, eve, sched):
                if n is not m_:
                    n.add_peer_addr(m_.peer_id, m_.listen_addrs[0])

        async def on_progress(peer, progress):
            return ProgressResponse(kind=ProgressResponseKind.DONE)

        sched.on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)

        peers_ref = Reference.from_peers(["w1", "w2"], "updates")
        spec = JobSpec(
            job_id="agg-2",
            executor=Executor(
                kind="aggregate",
                name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(peers_ref),
                    results=Send(Reference.from_peers(["w1"], "results")),
                    optimizer=Nesterov(),
                    num_workers=2,
                ),
            ),
        )
        pse = ParameterServerExecutor(ps, tmp_path)
        execution = await pse.execute("agg-2", spec, "sched")

        ones = {"w": np.ones(4, np.float32)}
        twos = {"w": np.full(4, 2.0, np.float32)}
        f_ones, f_twos = tmp_path / "o.st", tmp_path / "t.st"
        save_file(ones, str(f_ones)); save_file(twos, str(f_twos))

        async def recv_update():
            push = await w1.next_push(timeout=10)
            dest = tmp_path / "u.st"
            await push.save_to(dest)
            return dest

        recv = asyncio.create_task(recv_update())
        # eve's push must be ignored
        await eve.push("ps", {"resource": "updates", "name": "evil"}, f_ones)
        # w1 double-sends: second replaces first
        await w1.push("ps", {"resource": "updates", "name": "d"}, f_ones)
        await w1.push("ps", {"resource": "updates", "name": "d"}, f_twos)
        await w2.push("ps", {"resource": "updates", "name": "d"}, f_twos)

        dest = await recv
        from safetensors.numpy import load_file

        upd = load_file(str(dest))
        # mean of (2,2) = 2 -> update = lr*(mu*m+g) with m=g=2
        expect = 0.7 * (0.9 * 2.0 + 2.0)
        np.testing.assert_allclose(upd["w"], np.full(4, expect, np.float32), rtol=1e-5)
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == "completed"
        for n in (ps, w1, w2, eve, sched):
            await n.stop()

    run(main())


# ---------------------------------------------------------------------------
# The outer step over resident momentum: one in-place pass a leaf
# ---------------------------------------------------------------------------

LEAVES = {"wte": (64, 32), "h_0/attn": (2**21 + 5,), "bias": (7,), "scale": (1,)}


def _delta(rng, keys=LEAVES):
    return {k: rng.standard_normal(LEAVES[k]).astype(np.float32) for k in keys}


def _folded(rng, keys=LEAVES):
    from hypha_tpu.stream.accum import RoundAccum

    accum = RoundAccum()
    for samples in (24.0, 8.0):
        accum.fold_tree(_delta(rng, keys), samples)
    return accum


def _file_based_outer_step(accum, momentum_file, lr, mu, work_dir, round_num):
    """The outer step as it was before the momentum became resident, kept
    here as the plain reference: a mean tree, the momentum read from the
    file the last round wrote, nesterov_update into two fresh trees, two
    files written."""
    from safetensors.numpy import load_file, save_file

    mean = accum.mean()
    momentum = dict(load_file(str(momentum_file))) if momentum_file.is_file() else {}
    update = {}
    for key, g in mean.items():
        m = momentum.get(key)
        if m is None:
            m = np.zeros(g.size, np.float32)
        new_m, upd = native.nesterov_update(m, g.ravel(), lr, mu)
        momentum[key] = new_m.reshape(g.shape)
        update[key] = upd.reshape(g.shape)
    out = work_dir / f"update-{round_num}.safetensors"
    save_file(update, str(out))
    save_file(momentum, str(momentum_file))
    return out


def _outer_line(caplog) -> dict:
    from perfbench import logs

    return logs.outer_steps("\n".join(r.getMessage() for r in caplog.records))[-1]


@pytest.mark.parametrize("save", [False, True], ids=["no_checkpoint_dir", "checkpoint_dir"])
def test_three_rounds_over_resident_momentum_write_the_file_based_paths_bytes(
    tmp_path, kernel_backend, save, caplog
):
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    caplog.set_level("INFO", logger="hypha.worker.ps")
    ref_dir, new_dir = tmp_path / "ref", tmp_path / "new"
    ref_dir.mkdir(); new_dir.mkdir()
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(new_dir / "momentum.safetensors", save=save, threads=3)
    for rnd in range(3):
        want = _file_based_outer_step(
            _folded(np.random.default_rng(rnd)), ref_dir / "momentum.safetensors",
            0.7, 0.9, ref_dir, rnd,
        )
        accum = _folded(np.random.default_rng(rnd))
        got = ps._outer_step({}, momentum, 0.7, 0.9, new_dir, rnd, accum)
        assert got.ensure_file().read_bytes() == want.read_bytes(), rnd
        assert accum.folds == 0  # taken: nobody reads a sum written over
        with pytest.raises(ValueError, match="no deltas folded"):
            accum.mean()
        line = _outer_line(caplog)
        assert line["native_kernels"] is (kernel_backend == "native")
        assert line["threads"] == (3 if kernel_backend == "native" else 1)
        assert line["momentum_resident"] == (1 if rnd else 0)
        assert line["momentum_saved"] == int(save)
        assert line["mean_s"] == 0 and line["load_s"] == 0
        assert (line["save_momentum_s"] > 0) == save
        # The momentum file has a reader only under a checkpoint_dir.
        assert momentum.file.is_file() == save
        if save:
            assert momentum.file.read_bytes() == (ref_dir / "momentum.safetensors").read_bytes()
        assert not (new_dir / "momentum.next.safetensors").exists()


def test_a_momentum_file_put_there_before_the_first_round_is_read_once(tmp_path, caplog):
    """Warm start and recovery: a file that was there before the first outer
    step seeds the resident tree; no later round reads it."""
    from safetensors.numpy import load_file, save_file

    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    caplog.set_level("INFO", logger="hypha.worker.ps")
    ref_dir, new_dir = tmp_path / "ref", tmp_path / "new"
    ref_dir.mkdir(); new_dir.mkdir()
    saved = _delta(np.random.default_rng(9))
    for d in (ref_dir, new_dir):
        save_file(saved, str(d / "momentum.safetensors"))
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(new_dir / "momentum.safetensors", save=True)
    for rnd in range(2):
        want = _file_based_outer_step(
            _folded(np.random.default_rng(rnd)), ref_dir / "momentum.safetensors",
            0.7, 0.9, ref_dir, rnd,
        )
        got = ps._outer_step(
            {}, momentum, 0.7, 0.9, new_dir, rnd, _folded(np.random.default_rng(rnd))
        )
        assert got.ensure_file().read_bytes() == want.read_bytes()
        line = _outer_line(caplog)
        assert (line["load_s"] > 0) == (rnd == 0)
        assert line["momentum_resident"] == rnd
    on_disk = load_file(str(momentum.file))
    for key, m in momentum.tree.items():
        np.testing.assert_array_equal(on_disk[key], m)


def test_a_size_mismatch_raises_before_any_kernel_runs(tmp_path, monkeypatch):
    from hypha_tpu.stream.accum import RoundAccum
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    good = RoundAccum()
    good.fold_tree({"a": np.ones(8, np.float32), "b": np.ones(6, np.float32)}, 2.0)
    ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, 0, good)
    before = {k: v.copy() for k, v in momentum.tree.items()}
    calls = []
    monkeypatch.setattr(
        native, "fused_mean_nesterov", lambda *a, **k: calls.append(a) or 1
    )
    short = RoundAccum()
    # "a" is sound and comes first; "b" is short: neither may be touched.
    short.fold_tree({"a": np.ones(8, np.float32), "b": np.ones(5, np.float32)}, 2.0)
    with pytest.raises(ValueError, match="size 5 != momentum 6"):
        ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, 1, short)
    assert not calls
    for key, m in before.items():
        np.testing.assert_array_equal(momentum.tree[key], m)


def test_a_fragment_round_touches_only_its_keys_momentum(tmp_path, caplog):
    """Stream and overlap close one fragment a round: the resident tree
    takes that fragment's keys and leaves the others' arrays as they are."""
    from safetensors.numpy import load_file

    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    caplog.set_level("INFO", logger="hypha.worker.ps")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    frags = [("wte", "bias"), ("h_0/attn", "scale")]
    rng = np.random.default_rng(4)
    for rnd, keys in enumerate(frags):  # each fragment's first round
        out = ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, rnd, _folded(rng, keys))
        assert set(load_file(str(out.ensure_file()))) == set(keys)
        assert _outer_line(caplog)["momentum_resident"] == 0
    assert set(momentum.tree) == set(LEAVES)
    held = dict(momentum.tree)
    others = {k: momentum.tree[k].copy() for k in frags[1]}
    mine = {k: momentum.tree[k].copy() for k in frags[0]}
    ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, 2, _folded(rng, frags[0]))
    assert _outer_line(caplog)["momentum_resident"] == 1
    for k in LEAVES:
        assert momentum.tree[k] is held[k]  # updated where it lies
    for k in frags[1]:
        np.testing.assert_array_equal(momentum.tree[k], others[k])
    for k in frags[0]:
        assert not np.array_equal(momentum.tree[k], mine[k])


def test_the_metrics_planes_norms_need_no_mean_tree(tmp_path):
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    mean = _folded(np.random.default_rng(2)).mean()
    stats: dict = {}
    out = ps._outer_step(
        {"w0": None, "w1": None}, momentum, 0.7, 0.9, tmp_path, 0,
        _folded(np.random.default_rng(2)), stats,
    )
    from safetensors.numpy import load_file

    update = load_file(str(out.ensure_file()))
    g = np.sqrt(sum(float(np.vdot(v, v)) for v in mean.values()))
    u = np.sqrt(sum(float(np.vdot(v, v)) for v in update.values()))
    assert stats["delta_norm"] == pytest.approx(g, rel=1e-5)
    assert stats["update_norm"] == pytest.approx(u, rel=1e-6)
    assert stats["accepted"] == 2.0


@pytest.mark.parametrize("cpus,want", [(0, None), (0.5, None), (2, 2), (10_000, None)])
def test_the_outer_steps_threads_stay_within_what_the_node_was_given(tmp_path, cpus, want):
    import os

    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    allowed = len(os.sched_getaffinity(0))
    ps = ParameterServerExecutor(node=None, work_root=tmp_path, cpus=cpus)
    assert ps.threads == (min(want, allowed) if want else allowed)


@pytest.mark.parametrize("checkpoint", [False, True], ids=["no_checkpoint_dir", "checkpoint_dir"])
def test_momentum_is_on_disk_before_commit_and_broadcast_exactly_under_a_checkpoint_dir(
    tmp_path, monkeypatch, checkpoint
):
    """A whole aggregate job, two rounds. With a checkpoint_dir the momentum
    file is there, equal to the resident tree, when the durable commit runs
    and when the broadcast starts; without one it is never written, and
    neither is the update's file: the work dir holds the delta alone."""
    from safetensors.numpy import load_file, save_file

    from hypha_tpu.ft.durable import DurablePS
    from hypha_tpu.messages import (
        PROTOCOL_PROGRESS,
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
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    seen: list[tuple] = []  # (event, round, files in work_dir, file == resident)
    state = {}

    def look(event, rnd):
        momentum = state["momentum"]
        work_dir = momentum.file.parent
        same = None
        if momentum.file.is_file():
            on_disk = load_file(str(momentum.file))
            same = set(on_disk) == set(momentum.tree) and all(
                np.array_equal(on_disk[k], momentum.tree[k]) for k in on_disk
            )
        seen.append((event, rnd, sorted(p.name for p in work_dir.iterdir()), same))

    outer = ParameterServerExecutor._outer_step

    def outer_spy(self, received, momentum, *a, **k):
        state["momentum"] = momentum
        return outer(self, received, momentum, *a, **k)

    commit = DurablePS.commit_round

    def commit_spy(self, round_num, *a, **k):
        look("commit", round_num)
        return commit(self, round_num, *a, **k)

    bcast = ParameterServerExecutor._broadcast

    async def bcast_spy(self, cfg, wire, round_num, *a, **k):
        look("broadcast", round_num)
        return await bcast(self, cfg, wire, round_num, *a, **k)

    monkeypatch.setattr(ParameterServerExecutor, "_outer_step", outer_spy)
    monkeypatch.setattr(DurablePS, "commit_round", commit_spy)
    monkeypatch.setattr(ParameterServerExecutor, "_broadcast", bcast_spy)

    async def main():
        hub = MemoryTransport()
        nodes = {n: Node(hub.shared(), peer_id=n) for n in ("ps", "w1", "sched")}
        for n in nodes.values():
            await n.start()
        for x in nodes.values():
            for y in nodes.values():
                if x is not y:
                    x.add_peer_addr(y.peer_id, y.listen_addrs[0])

        async def on_progress(peer, progress):
            done = progress.round >= 1
            return ProgressResponse(
                kind=ProgressResponseKind.DONE if done else ProgressResponseKind.OK
            )

        nodes["sched"].on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)
        ref = Reference.from_peers(["w1"], "updates")
        spec = JobSpec(
            job_id="agg-m",
            executor=Executor(
                kind="aggregate", name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(ref), results=Send(ref),
                    optimizer=Nesterov(lr=0.7, momentum=0.9), num_workers=1,
                    checkpoint_dir=str(tmp_path / "ckpt") if checkpoint else None,
                ),
            ),
        )
        pse = ParameterServerExecutor(nodes["ps"], tmp_path / "work")
        execution = await pse.execute("agg-m", spec, "sched")
        f = tmp_path / "d.st"
        save_file({"w": np.ones(8, np.float32), "b": np.full(4, 2.0, np.float32)}, str(f))
        for rnd in range(2):
            header = {"resource": "updates", "name": "delta", "num_samples": 4,
                      "round": rnd}
            await retry(lambda: nodes["w1"].push("ps", header, f),
                        attempts=3, base_delay=0.05)
            push = await nodes["w1"].next_push(timeout=10)
            await push.save_to(tmp_path / f"u{rnd}.st")
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == "completed"
        for n in nodes.values():
            await n.stop()

    run(main())
    events = [(e, r) for e, r, _, _ in seen]
    if checkpoint:
        assert events == [("commit", 0), ("broadcast", 0), ("commit", 1), ("broadcast", 1)]
        assert all(same is True for _, _, _, same in seen)
        assert all("momentum.safetensors" in files for _, _, files, _ in seen)
        # A durable job's commit hard-links the wire: there the update is a file.
        assert all(f"update-{rnd}.safetensors" in files for _, rnd, files, _ in seen)
        assert (tmp_path / "ckpt" / "momentum.safetensors").is_file()
    else:
        assert events == [("broadcast", 0), ("broadcast", 1)]
        for _, rnd, files, same in seen:
            assert same is None
            # The round's delta, and nothing else of that size: the update
            # is broadcast from the buffers it was computed in. (From round 1
            # on the spool round 0's delta was kept in is there, a
            # directory: round 1's push was saved over the spare.)
            assert [f for f in files if not f.startswith("delta-")] == (
                ["spare"] if rnd else []
            ), files


# ---------------------------------------------------------------------------
# The fold as one pass into resident buffers
# ---------------------------------------------------------------------------

from test_native import _bits  # noqa: E402  (every bit of an f32 array, NaNs as one value)


def _same_bits(got: dict, want: dict) -> None:
    assert set(got) == set(want)
    for key in want:
        assert got[key].shape == want[key].shape and got[key].dtype == np.float32, key
        assert np.array_equal(_bits(got[key]), _bits(want[key])), key


def _special_delta(rng, keys=LEAVES):
    d = _delta(rng, keys)
    flat = d["h_0/attn"] if "h_0/attn" in d else next(iter(d.values())).reshape(-1)
    vals = [-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-45, 3.4e38]
    flat[: len(vals)] = vals[: flat.size]
    return d


def _write(path, tree, codec="none"):
    from hypha_tpu import compress

    compress.write_delta(path, tree, codec)
    return path


def _numpy_sum(ops):
    """Today's arithmetic, written out: ``prev = s·Δ`` for a round's first
    delta and ``prev += s·Δ`` after, s = f32(sign·samples), or f32(sign) for
    a prefolded partial."""
    want = None
    with np.errstate(invalid="ignore", over="ignore"):
        for tree, samples, sign, prefolded in ops:
            scale = np.float32(sign) if prefolded else np.float32(sign * samples)
            if want is None:
                want = {k: scale * np.asarray(v, np.float32) for k, v in tree.items()}
            else:
                for k, v in tree.items():
                    want[k] += scale * np.asarray(v, np.float32)
    return want


@functools.lru_cache(maxsize=None)
def _fold_cases():
    """Made once: no fold writes to what it is given."""
    rng = np.random.default_rng(30)
    d = [_special_delta(rng) if n == 1 else _delta(rng) for n in range(5)]
    partial = _numpy_sum([(d[3], 5.0, 1.0, False), (d[4], 11.0, 1.0, False)])
    return {
        "one_delta": [(d[0], 24.0, 1.0, False)],
        "two_deltas": [(d[0], 24.0, 1.0, False), (d[1], 7.0, 1.0, False)],
        "four_deltas": [(d[n], s, 1.0, False) for n, s in enumerate((24.0, 7.0, 40.0, 3.0))],
        "unfold_of_a_replaced_duplicate": [
            (d[0], 24.0, 1.0, False), (d[2], 7.0, 1.0, False),
            (d[0], 24.0, -1.0, False), (d[3], 24.0, 1.0, False),
        ],
        "prefolded_partial_first": [(partial, 16.0, 1.0, True), (d[0], 24.0, 1.0, False)],
        "prefolded_partial_later_and_unfolded": [
            (d[0], 24.0, 1.0, False), (partial, 16.0, 1.0, True), (partial, 16.0, -1.0, True),
        ],
        "signed_zeros_infinities_and_nans": [(d[1], 3.0, 1.0, False), (d[1], 3.0, 1.0, False)],
    }


@pytest.mark.parametrize("entry", ["file", "tree"])
@pytest.mark.parametrize("threads", [1, 2])
@pytest.mark.parametrize("case", sorted(_fold_cases()))
def test_the_fold_is_bit_equal_to_the_numpy_expression(
    tmp_path, kernel_backend, case, threads, entry
):
    """Files take the one-pass path (read into the sum's buffer, or into
    the staging leaf, and scaled or added there); trees the decoded one.
    ``h_0/attn`` is over what one thread takes, the others under."""
    from hypha_tpu.stream.accum import RoundAccum, SumBuffers

    ops = _fold_cases()[case]
    accum = RoundAccum(SumBuffers(threads))
    for n, (tree, samples, sign, prefolded) in enumerate(ops):
        if entry == "file":
            did = accum.fold(_write(tmp_path / f"d{n}.st", tree), samples, sign, prefolded)
            assert did.direct == did.leaves == len(LEAVES)
            assert did.threads == (threads if kernel_backend == "native" else 1)
        else:
            accum.fold_tree(tree, samples, sign, prefolded)
    _same_bits(accum.partial(), _numpy_sum(ops))
    assert accum.total_samples == sum(s * g for _, s, g, _ in ops)
    assert accum.folds == sum(1 if g > 0 else -1 for _, _, g, _ in ops)


@pytest.mark.parametrize("entry", ["file", "tree"])
def test_a_kept_buffer_is_overwritten_by_a_rounds_first_fold_never_added_to(
    tmp_path, kernel_backend, entry
):
    from hypha_tpu.stream.accum import RoundAccum, SumBuffers

    pool = SumBuffers(2)
    rng = np.random.default_rng(5)
    first = RoundAccum(pool)
    first.fold_tree(_delta(rng), 8.0)
    taken, _ = first.take()
    for buf in taken.values():
        buf.fill(np.nan)  # what the outer step left there is stale
    first.release(taken)
    tree = _special_delta(rng)
    second = RoundAccum(pool)
    if entry == "file":
        did = second.fold(_write(tmp_path / "d.st", tree), 3.0)
        assert did.resident == did.direct == did.leaves == len(LEAVES)
    else:
        second.fold_tree(tree, 3.0)
    got = second.partial()
    _same_bits(got, _numpy_sum([(tree, 3.0, 1.0, False)]))
    for key, buf in taken.items():
        assert got[key] is buf  # the last round's pages


def _ps_and_momentum(tmp_path, threads=2):
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor, _OuterMomentum

    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    return ps, _OuterMomentum(tmp_path / "momentum.safetensors", save=False, threads=threads)


def test_round_twos_sum_lies_at_round_ones_addresses_and_leaves_its_update_alone(
    tmp_path, kernel_backend
):
    """The job's buffers outlive the round's accumulator: lease, outer step
    in place, the update read where it lies, retired, leased again."""
    from hypha_tpu.stream.accum import RoundAccum

    ps, momentum = _ps_and_momentum(tmp_path)
    rng = np.random.default_rng(11)
    addresses, updates = [], []
    for rnd in range(3):
        accum = RoundAccum(momentum.sums)
        did = accum.fold(_write(tmp_path / f"delta-{rnd}.st", _delta(rng)), 16.0)
        assert did.direct == did.leaves == len(LEAVES)
        assert did.resident == (len(LEAVES) if rnd else 0)
        addresses.append({k: v.ctypes.data for k, v in accum.partial().items()})
        out = ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, rnd, accum)
        # The update is the sum's own pages, and a file only when asked.
        assert {k: v.ctypes.data for k, v in out.tree.items()} == addresses[-1]
        assert not list(tmp_path.glob("update-*"))
        updates.append(out.ensure_file().read_bytes())
        # Its last reader has ended: the pages go back, the file goes.
        out.retire()
        assert not list(tmp_path.glob("update-*"))
    assert addresses[0] == addresses[1] == addresses[2]
    assert len(set(updates)) == 3


def test_a_fragment_round_leases_and_touches_only_its_keys(tmp_path):
    from hypha_tpu.stream.accum import RoundAccum

    ps, momentum = _ps_and_momentum(tmp_path)
    frags = [("wte", "bias"), ("h_0/attn", "scale")]
    rng = np.random.default_rng(4)
    held = {}
    for rnd, keys in enumerate(frags):
        accum = RoundAccum(momentum.sums)
        accum.fold(_write(tmp_path / f"d{rnd}.st", _delta(rng, keys)), 8.0)
        held.update(accum.partial())
        ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, rnd, accum).retire()
    others = {k: held[k].copy() for k in frags[1]}
    accum = RoundAccum(momentum.sums)
    did = accum.fold(_write(tmp_path / "d2.st", _delta(rng, frags[0])), 8.0)
    assert did.leaves == did.resident == len(frags[0])
    assert {k: v.ctypes.data for k, v in accum.partial().items()} == {
        k: held[k].ctypes.data for k in frags[0]
    }
    for k in frags[1]:
        np.testing.assert_array_equal(held[k], others[k])


def test_two_sums_open_over_the_same_keys_do_not_share_a_buffer(tmp_path):
    """Pipelined stream rounds: the second open sum gets buffers of its
    own, which the job then keeps too."""
    from hypha_tpu.stream.accum import RoundAccum, SumBuffers

    pool = SumBuffers()
    rng = np.random.default_rng(6)
    trees = [_delta(rng), _delta(rng)]
    open_sums = [RoundAccum(pool), RoundAccum(pool)]
    for n, (accum, tree) in enumerate(zip(open_sums, trees)):
        accum.fold(_write(tmp_path / f"d{n}.st", tree), 4.0)
    a, b = (s.partial() for s in open_sums)
    for key in LEAVES:
        assert not np.shares_memory(a[key], b[key])
    _same_bits(a, _numpy_sum([(trees[0], 4.0, 1.0, False)]))
    _same_bits(b, _numpy_sum([(trees[1], 4.0, 1.0, False)]))
    for accum in open_sums:
        accum.release(accum.take()[0])
    again = [RoundAccum(pool), RoundAccum(pool)]
    for accum in again:
        assert accum.fold(_write(tmp_path / "d.st", trees[0]), 4.0).resident == len(LEAVES)


def _faulty(tmp_path, fault, tree):
    """A delta file that must be refused, and what the refusal says."""
    import json
    import struct

    from safetensors.numpy import save_file

    path = tmp_path / f"{fault}.st"
    if fault == "mismatched_shape":
        save_file({**tree, "wte": np.zeros((32, 64), np.float32)}, str(path))
        return path, "mismatched shape"
    if fault == "mismatched_keys":
        save_file({k: v for k, v in tree.items() if k != "bias"}, str(path))
        return path, "mismatched keys"
    save_file(tree, str(path))
    blob = path.read_bytes()
    if fault == "truncated":
        path.write_bytes(blob[:-1000])
        return path, "do not fit"
    n = struct.unpack("<Q", blob[:8])[0]
    header = json.loads(blob[8:8 + n])
    # "F32" over two bytes an element: the header's dtype is not the data's.
    begin, end = header["scale"]["data_offsets"]
    header["scale"]["data_offsets"] = [begin, end - 2]
    head = json.dumps(header, separators=(",", ":")).encode().ljust(n)
    assert len(head) == n
    path.write_bytes(blob[:8] + head + blob[8 + n:])
    return path, "do not fit"


# A round's first delta is what the others are matched against, so only a
# file that is wrong in itself can be refused there.
@pytest.mark.parametrize("fault,folded_before", [
    ("truncated", 0), ("wrong_dtype", 0), ("truncated", 1), ("wrong_dtype", 1),
    ("mismatched_shape", 1), ("mismatched_keys", 1),
])
def test_a_delta_that_does_not_fit_is_refused_before_the_sum_is_written(
    tmp_path, fault, folded_before
):
    from hypha_tpu.stream.accum import RoundAccum, SumBuffers

    rng = np.random.default_rng(8)
    pool = SumBuffers(2)
    accum = RoundAccum(pool)
    ops = []
    if folded_before:
        ops.append((_delta(rng), 24.0, 1.0, False))
        accum.fold(_write(tmp_path / "good.st", ops[0][0]), 24.0)
        before = {k: v.copy() for k, v in accum.partial().items()}
    path, says = _faulty(tmp_path, fault, _delta(rng))
    with pytest.raises(ValueError, match=says):
        accum.fold(path, 8.0)
    assert accum.folds == folded_before and accum.total_samples == 24.0 * folded_before
    if folded_before:
        _same_bits(accum.partial(), before)
    else:
        with pytest.raises(ValueError, match="no deltas folded"):
            accum.partial()
    # And the round goes on as if the refused delta had never come.
    ops.append((_delta(rng), 7.0, 1.0, False))
    accum.fold(_write(tmp_path / "next.st", ops[-1][0]), 7.0)
    _same_bits(accum.partial(), _numpy_sum(ops))


@pytest.mark.parametrize("later", [False, True], ids=["first_fold", "later_fold"])
@pytest.mark.parametrize("codec", ["none", "bf16", "int8", "mixed"])
def test_the_path_is_chosen_from_the_files_own_header(tmp_path, kernel_backend, codec, later):
    """All-F32 SafeTensors goes straight into the sum; bf16 SafeTensors, an
    HQD1 frame and a file with one bf16 tensor among f32 ones are decoded
    as ever and land in the same resident buffers with today's sums."""
    import ml_dtypes
    from safetensors.numpy import save_file

    from hypha_tpu import compress
    from hypha_tpu.stream.accum import RoundAccum, SumBuffers

    pool = SumBuffers(2)
    rng = np.random.default_rng(12)
    warm = RoundAccum(pool)
    warm.fold_tree(_delta(rng), 1.0)
    kept, _ = warm.take()
    warm.release(kept)
    accum = RoundAccum(pool)
    ops = []
    if later:
        ops.append((_delta(rng), 24.0, 1.0, False))
        accum.fold_tree(ops[0][0], 24.0)
    tree = _delta(rng)
    path = tmp_path / "delta.bin"
    if codec == "mixed":
        save_file({**tree, "bias": tree["bias"].astype(ml_dtypes.bfloat16)}, str(path))
    else:
        compress.write_delta(path, tree, codec)
    decoded = compress.read_delta(path)
    did = accum.fold(path, 8.0)
    assert did.leaves == did.resident == len(LEAVES)
    assert did.direct == (len(LEAVES) if codec == "none" else 0)
    assert did.bytes == path.stat().st_size
    ops.append((decoded, 8.0, 1.0, False))
    got = accum.partial()
    _same_bits(got, _numpy_sum(ops))
    for key, buf in kept.items():
        assert got[key] is buf
