"""Compressed delta transport (hypha_tpu.compress): quantization error
bounds, native/numpy bit-exact parity (mirroring the CBOR codec's corpus
approach), HQD1 frame round-trips, error-feedback tracking, the quantized
parameter-server round over the fabric, and the parallel broadcast.
"""

from __future__ import annotations

import asyncio
import struct

import numpy as np
import pytest

from hypha_tpu import native
from hypha_tpu.aio import retry
from hypha_tpu.compress import (
    DEFAULT_CHUNK,
    ErrorFeedback,
    effective_codec,
    is_frame,
    read_delta,
    read_delta_into,
    read_frame,
    write_delta,
    write_frame,
)
from hypha_tpu.compress import quant
from hypha_tpu.compress.quant import QMAX, dequantize, payload_nbytes, quantize


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["int8", "int4"])
@pytest.mark.parametrize("chunk", [64, 4096])
def test_roundtrip_error_bounded_per_chunk(codec, chunk):
    """|x - Q⁻¹(Q(x))| ≤ scale/2 within every chunk (half-to-even round)."""
    rng = np.random.default_rng(11)
    a = (rng.standard_normal(10_000) * rng.uniform(0.01, 100, 10_000)).astype(
        np.float32
    )
    payload, scales = quantize(a, codec, chunk)
    back = dequantize(payload, scales, a.size, codec, chunk)
    nchunks = (a.size + chunk - 1) // chunk
    for c in range(nchunks):
        lo, hi = c * chunk, min((c + 1) * chunk, a.size)
        err = np.abs(a[lo:hi] - back[lo:hi]).max()
        # scale = maxabs/qmax; rounding error is at most half a step.
        assert err <= scales[c] * 0.5 * (1 + 1e-6), (codec, c, err, scales[c])


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_native_numpy_bit_exact_parity(codec):
    """The parity corpus: payload bytes AND scale bits must be identical
    between the C++ kernel and the numpy spec, like the CBOR pair."""
    assert native.native_available()
    rng = np.random.default_rng(5)
    corpus = [
        np.zeros(100, np.float32),
        np.ones(1, np.float32),
        rng.standard_normal(7).astype(np.float32),
        rng.standard_normal(4096).astype(np.float32),
        rng.standard_normal(4097).astype(np.float32),
        (rng.standard_normal(9999) * 1e-30).astype(np.float32),
        (rng.standard_normal(5000) * 1e30).astype(np.float32),
        np.full(300, -2.5, np.float32),
        np.concatenate(
            [np.zeros(4096, np.float32), rng.standard_normal(100).astype(np.float32)]
        ),
        # Non-finite values WITHOUT an accompanying Inf in the chunk: NaN
        # must propagate through the chunk max identically on both paths
        # (a native kernel that skips NaN in its max once shipped).
        np.array([1.0, 2.0, np.nan, 3.0] + [0.5] * 124, np.float32),
        np.array([np.inf, -1.0] + [4.0] * 126, np.float32),
        np.concatenate(
            [
                rng.standard_normal(64).astype(np.float32),
                np.array([np.nan], np.float32),
                rng.standard_normal(63).astype(np.float32),
            ]
        ),
    ]
    for i, a in enumerate(corpus):
        for chunk in (64, 4096):
            p_nat, s_nat = quantize(a, codec, chunk)  # native path
            p_np = np.zeros_like(p_nat)
            s_np = np.zeros_like(s_nat)
            quant._np_quantize(a, chunk, codec, p_np, s_np)
            assert np.array_equal(p_nat, p_np), (codec, i, chunk, "payload")
            assert np.array_equal(
                s_nat.view(np.uint32), s_np.view(np.uint32)
            ), (codec, i, chunk, "scales")
            d_nat = dequantize(p_nat, s_nat, a.size, codec, chunk)
            d_np = np.empty(a.size, np.float32)
            quant._np_dequantize(p_nat, s_nat, a.size, chunk, codec, d_np)
            assert np.array_equal(
                d_nat.view(np.uint32), d_np.view(np.uint32)
            ), (codec, i, chunk, "dequant")


@pytest.mark.parametrize(
    "bad", [np.nan, np.inf, -np.inf], ids=["nan", "inf", "-inf"]
)
def test_nonfinite_chunk_degrades_to_zero(bad):
    """A chunk whose max-abs is NaN or Inf — each alone, not just together
    — encodes as zeros with scale 0 on BOTH paths: it must not poison the
    aggregate, and no non-finite value may reach an int cast."""
    a = np.array([1.0, bad, -3.0] + [0.5] * 61 + [2.0] * 64, np.float32)
    for codec in ("int8", "int4"):
        payload, scales = quantize(a, codec, 64)
        assert scales[0] == 0.0
        assert scales[1] > 0.0  # the clean second chunk still quantizes
        back = dequantize(payload, scales, a.size, codec, 64)
        assert np.all(back[:64] == 0.0)
        assert np.all(np.isfinite(back))
        # numpy spec agrees byte-for-byte
        p_np = np.zeros_like(payload)
        s_np = np.zeros_like(scales)
        quant._np_quantize(a, 64, codec, p_np, s_np)
        assert np.array_equal(payload, p_np)
        assert np.array_equal(scales.view(np.uint32), s_np.view(np.uint32))


def test_int4_packs_two_per_byte():
    a = np.linspace(-1, 1, 101).astype(np.float32)
    payload, _ = quantize(a, "int4", 64)
    assert payload.size == payload_nbytes(101, "int4") == 51
    p8, _ = quantize(a, "int8", 64)
    assert p8.size == 101


def test_quantize_rejects_bad_args():
    a = np.ones(8, np.float32)
    with pytest.raises(ValueError):
        quantize(a, "f8", 64)
    with pytest.raises(ValueError):
        quantize(a, "int4", 63)  # odd chunk breaks nibble alignment
    with pytest.raises(ValueError):
        quantize(a, "int8", 0)
    with pytest.raises(ValueError):
        dequantize(np.zeros(3, np.uint8), np.ones(1, np.float32), 8, "int8", 64)


# ---------------------------------------------------------------------------
# HQD1 frames
# ---------------------------------------------------------------------------


def test_frame_roundtrip_self_describing(tmp_path):
    rng = np.random.default_rng(2)
    flat = {
        "blocks_0/attn/kernel": rng.standard_normal((32, 48)).astype(np.float32),
        "bias": rng.standard_normal(5).astype(np.float32),
        "scalar": np.float32(2.5),
    }
    path = tmp_path / "delta.safetensors"  # name lies; magic tells the truth
    decoded = write_frame(path, flat, "int8", chunk=64)
    assert is_frame(path)
    back = read_frame(path)
    assert set(back) == set(flat)
    for k, arr in back.items():
        assert arr.dtype == np.float32
        np.testing.assert_array_equal(
            arr.ravel(), np.asarray(decoded[k], np.float32).ravel()
        )
    # shapes survive (scalars as (1,), SafeTensors-style)
    assert back["blocks_0/attn/kernel"].shape == (32, 48)
    assert back["scalar"].shape == (1,)
    # int8 payload ~4x smaller than the f32 bytes
    f32_bytes = sum(np.atleast_1d(v).nbytes for v in flat.values())
    assert path.stat().st_size < f32_bytes / 3


def test_read_delta_dispatches_on_magic(tmp_path):
    from safetensors.numpy import save_file

    tree = {"w": np.arange(6, dtype=np.float32)}
    st = tmp_path / "plain.safetensors"
    save_file(tree, str(st))
    got = read_delta(st)
    np.testing.assert_array_equal(got["w"], tree["w"])

    q = tmp_path / "quant.safetensors"
    write_frame(q, tree, "int4", chunk=64)
    got_q = read_delta(q)
    assert got_q["w"].dtype == np.float32


def _update_tree():
    rng = np.random.default_rng(5)
    return {
        "blocks_0/attn/kernel": rng.standard_normal((257, 130)).astype(np.float32),
        "scalar": np.float32(-0.0),
        "empty": np.zeros((0, 4), np.float32),
        "bias": rng.standard_normal(7).astype(np.float32),
    }


class _Pool:
    """The lease a job's ``SumBuffers`` gives, with what it handed out."""

    def __init__(self):
        from hypha_tpu.stream.accum import SumBuffers

        self.buffers = SumBuffers()
        self.leased = {}

    def lease(self, key, shape):
        buf, kept = self.buffers.lease(key, shape)
        self.leased[key] = buf
        return buf, kept


@pytest.mark.parametrize("leaf", sorted(_update_tree()))
@pytest.mark.parametrize("reads", [1, 2])
def test_read_delta_into_gives_what_read_delta_gives_in_leased_buffers(tmp_path, leaf, reads):
    """An all-F32 SafeTensors file (a scalar comes back ``(1,)``, an empty
    tensor stays empty): keys, shapes, dtype and bytes as ``read_delta``'s,
    every leaf in the buffer that was leased for it; the second read of a
    job finds the first one's buffers."""
    path = tmp_path / "update.safetensors"
    pool = _Pool()
    for n in range(reads):
        tree = {k: np.asarray(v) + np.float32(n) for k, v in _update_tree().items()}
        write_delta(path, tree, "none")
        want = read_delta(path)
        got, stats = read_delta_into(path, pool.lease)
        assert list(got) == list(want)
        assert got[leaf] is pool.leased[leaf]
        assert got[leaf].shape == want[leaf].shape and got[leaf].dtype == np.float32
        assert got[leaf].tobytes() == want[leaf].tobytes()
        assert stats.leaves == stats.direct == len(want) == 4
        assert stats.bytes == path.stat().st_size
        assert stats.resident == (len(want) if n else 0)
        pool.buffers.give_back(got)
    assert got["scalar"].shape == (1,) and got["empty"].shape == (0, 4)


@pytest.mark.parametrize("codec", ["bf16", "int8", "int4"])
def test_read_delta_into_leaves_other_formats_to_read_delta(tmp_path, codec):
    """A bf16 SafeTensors file and an HQD1 frame come back exactly as
    ``read_delta`` gives them, and nothing is leased."""
    path = tmp_path / "update.safetensors"
    write_delta(path, _update_tree(), codec)
    want = read_delta(path)
    pool = _Pool()
    got, stats = read_delta_into(path, pool.lease)
    assert not pool.leased and stats.direct == 0 and stats.resident == 0
    assert stats.leaves == len(want) and stats.bytes == path.stat().st_size
    assert list(got) == list(want)
    for key in want:
        assert got[key].dtype == want[key].dtype and got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes()
    if codec == "bf16":
        assert got["bias"].dtype != np.float32


@pytest.mark.parametrize("cut", [1, 4 * 130, 4 * 257 * 130])
def test_read_delta_into_refuses_a_file_shorter_than_its_header_says(tmp_path, cut):
    path = tmp_path / "update.safetensors"
    write_delta(path, _update_tree(), "none")
    data = path.read_bytes()
    path.write_bytes(data[:-cut])
    with pytest.raises(ValueError, match="do not fit"):
        read_delta_into(path, _Pool().lease)


def test_frame_rejects_malformed(tmp_path):
    bad = tmp_path / "bad"
    bad.write_bytes(b"HQD1" + struct.pack("<I", 10_000) + b"short")
    with pytest.raises(ValueError):
        read_frame(bad)
    notframe = tmp_path / "nf"
    notframe.write_bytes(b"\x00" * 16)
    with pytest.raises(ValueError):
        read_frame(notframe)
    assert not is_frame(notframe)
    assert not is_frame(tmp_path / "does-not-exist")


def test_frame_rejects_out_of_bounds_tensor(tmp_path):
    from hypha_tpu import codec as cbor

    header = cbor.dumps(
        {
            "codec": "int8",
            "chunk": 64,
            "tensors": [
                {"name": "w", "shape": [8], "qoff": 0, "qlen": 8, "soff": 900, "slen": 4}
            ],
        }
    )
    evil = tmp_path / "evil"
    evil.write_bytes(b"HQD1" + struct.pack("<I", len(header)) + header + b"\x01" * 8)
    with pytest.raises(ValueError, match="outside payload"):
        read_frame(evil)


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


def test_error_feedback_sum_tracks_truth(tmp_path):
    """Σ sent_t stays within ONE round's quantization error of Σ x_t — the
    EF recurrence ships every bit of error eventually, so compression
    error does not compound across rounds."""
    rng = np.random.default_rng(9)
    ef = ErrorFeedback()
    total_true = np.zeros(2048, np.float32)
    total_sent = np.zeros(2048, np.float32)
    worst_scale = 0.0
    for _ in range(40):
        x = (rng.standard_normal(2048) * 0.01).astype(np.float32)
        comp = ef.compensate({"x": x})
        decoded = write_frame(tmp_path / "f", comp, "int4", chunk=256)
        ef.absorb(comp, decoded)
        total_true += x
        total_sent += decoded["x"].astype(np.float32)
        worst_scale = max(worst_scale, float(np.abs(comp["x"]).max()) / QMAX["int4"])
    drift = float(np.abs(total_true - total_sent).max())
    assert drift <= worst_scale * 0.5 * 1.01, (drift, worst_scale)


def test_error_feedback_shape_change_resets():
    ef = ErrorFeedback()
    comp = ef.compensate({"x": np.ones(4, np.float32)})
    ef.absorb(comp, {"x": np.zeros(4, np.float32)})
    assert ef.tensors == 1
    # The stored (4,) residual must not be applied to a (2,) tensor.
    out = ef.compensate({"x": np.ones(2, np.float32)})
    np.testing.assert_array_equal(out["x"], np.ones(2, np.float32))


def test_effective_codec_mapping():
    assert effective_codec("none") == "none"
    assert effective_codec("none", "bfloat16") == "bf16"
    assert effective_codec("int8", "bfloat16") == "int8"
    assert effective_codec("int4") == "int4"
    with pytest.raises(ValueError):
        effective_codec("int2")


def test_job_config_validates_delta_codec():
    from hypha_tpu.scheduler.job_config import DiLoCoJob

    with pytest.raises(ValueError, match="delta_codec"):
        DiLoCoJob(model={}, dataset="d", delta_codec="gzip")
    job = DiLoCoJob(model={}, dataset="d", delta_codec="int8")
    assert job.delta_codec == "int8"


# ---------------------------------------------------------------------------
# toy-model DiLoCo: int8 + error feedback matches uncompressed
# ---------------------------------------------------------------------------


def _diloco_sim(codec: str, rounds: int = 30, workers: int = 3):
    """Linear-regression DiLoCo in numpy over the REAL compress + Nesterov
    kernels: H local SGD steps per worker, mean of deltas, outer Nesterov,
    broadcast merge — with the wire (both directions) quantized +
    error-fed-back when codec demands it."""
    import tempfile
    from pathlib import Path

    rng = np.random.default_rng(0)
    dim, nsamp = 64, 128
    w_star = rng.standard_normal(dim).astype(np.float32)
    xs, ys = [], []
    for _ in range(workers):
        X = rng.standard_normal((nsamp, dim)).astype(np.float32)
        xs.append(X)
        ys.append(X @ w_star + 0.01 * rng.standard_normal(nsamp).astype(np.float32))

    theta = np.zeros(dim, np.float32)
    momentum = np.zeros(dim, np.float32)
    worker_efs = [ErrorFeedback() for _ in range(workers)]
    ps_ef = ErrorFeedback()
    lr_in, lr_out, mu, steps = 0.05, 0.7, 0.9, 8
    with tempfile.TemporaryDirectory() as td:
        wire = Path(td) / "wire"
        for _ in range(rounds):
            deltas = []
            for k in range(workers):
                w = theta.copy()
                for _ in range(steps):
                    grad = xs[k].T @ (xs[k] @ w - ys[k]) / nsamp
                    w -= lr_in * grad
                delta = {"w": w - theta}
                if codec in ("int8", "int4"):
                    comp = worker_efs[k].compensate(delta)
                    decoded = write_frame(wire, comp, codec, chunk=64)
                    worker_efs[k].absorb(comp, decoded)
                    delta = {"w": decoded["w"].astype(np.float32)}
                deltas.append(delta["w"].ravel())
            g = np.mean(deltas, axis=0).astype(np.float32)
            momentum, update = native.nesterov_update(momentum, g, lr_out, mu)
            if codec in ("int8", "int4"):
                comp = ps_ef.compensate({"w": update})
                decoded = write_frame(wire, comp, codec, chunk=64)
                ps_ef.absorb(comp, decoded)
                update = decoded["w"].astype(np.float32).ravel()
            theta = theta + update
    loss = float(
        np.mean([np.mean((X @ theta - y) ** 2) for X, y in zip(xs, ys)])
    )
    return theta, loss


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_toy_diloco_quantized_ef_matches_uncompressed(codec):
    theta_f32, loss_f32 = _diloco_sim("none")
    theta_q, loss_q = _diloco_sim(codec)
    # Training made real progress…
    assert loss_f32 < 1e-2
    # …and the quantized run lands at the same optimum within tolerance
    # (measured: int8 rel param diff ~6e-5, int4 ~1.2e-3).
    assert loss_q <= loss_f32 * 1.05 + 1e-5, (loss_q, loss_f32)
    rel = np.linalg.norm(theta_q - theta_f32) / max(np.linalg.norm(theta_f32), 1e-9)
    assert rel < 0.02, rel


# ---------------------------------------------------------------------------
# quantized PS round over the fabric + parallel broadcast
# ---------------------------------------------------------------------------


def test_ps_round_int8_end_to_end(tmp_path):
    """Workers ship HQD1 int8 deltas; the PS folds them incrementally and
    broadcasts an int8-quantized update; the decoded update matches the
    f32 weighted-mean Nesterov step within quantization tolerance."""
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
        sched = Node(hub.shared(), peer_id="sched")
        for n in (ps, w1, w2, sched):
            await n.start()
        for x in (ps, w1, w2, sched):
            for y in (ps, w1, w2, sched):
                if x is not y:
                    x.add_peer_addr(y.peer_id, y.listen_addrs[0])

        async def on_progress(peer, progress):
            return ProgressResponse(kind=ProgressResponseKind.DONE)

        sched.on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)

        peers_ref = Reference.from_peers(["w1", "w2"], "updates")
        spec = JobSpec(
            job_id="agg-q",
            executor=Executor(
                kind="aggregate",
                name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(peers_ref),
                    results=Send(peers_ref),
                    optimizer=Nesterov(lr=0.7, momentum=0.9),
                    num_workers=2,
                    delta_codec="int8",
                ),
            ),
        )
        pse = ParameterServerExecutor(ps, tmp_path)
        execution = await pse.execute("agg-q", spec, "sched")

        rng = np.random.default_rng(4)
        d1 = {"w": rng.standard_normal(512).astype(np.float32)}
        d2 = {"w": rng.standard_normal(512).astype(np.float32)}
        f1, f2 = tmp_path / "d1.st", tmp_path / "d2.st"
        dec1 = write_frame(f1, d1, "int8")
        dec2 = write_frame(f2, d2, "int8")

        async def worker_round(node, f, samples):
            header = {"resource": "updates", "name": "delta", "num_samples": samples}
            await retry(
                lambda: node.push("ps", header, f),
                attempts=3, base_delay=0.05,
            )
            push = await node.next_push(timeout=10)
            dest = tmp_path / f"update-{node.peer_id}.st"
            await push.save_to(dest)
            return dest

        u1, u2 = await asyncio.gather(
            worker_round(w1, f1, 300), worker_round(w2, f2, 100)
        )
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == "completed"
        for n in (ps, w1, w2, sched):
            await n.stop()
        return u1, u2, dec1, dec2

    u1, u2, dec1, dec2 = run(main())
    # The broadcast IS a quantized frame, and both workers got the same one.
    assert is_frame(u1) and is_frame(u2)
    upd1, upd2 = read_delta(u1), read_delta(u2)
    np.testing.assert_array_equal(upd1["w"], upd2["w"])
    # Ground truth from what the PS actually decoded (the workers' HQD1
    # payloads), weighted 300:100.
    g = 0.75 * dec1["w"].ravel() + 0.25 * dec2["w"].ravel()
    expect = 0.7 * (0.9 * g + g)
    scale = np.abs(expect).max() / 127
    np.testing.assert_allclose(upd1["w"].ravel(), expect, atol=scale * 0.51)


class _FakeBroadcastNode:
    def __init__(self, fail=(), delay=None):
        self.fail = set(fail)
        self.delay = dict(delay or {})
        self.pushed: list[str] = []
        self.started: list[tuple[str, float]] = []

    async def push(self, peer, header, path):
        from hypha_tpu.network.node import RequestError

        self.started.append((peer, asyncio.get_running_loop().time()))
        await asyncio.sleep(self.delay.get(peer, 0.0))
        if peer in self.fail:
            raise RequestError(f"{peer} unreachable")
        self.pushed.append(peer)


def _bcast_cfg(peers, strategy):
    from hypha_tpu.messages import (
        AggregateExecutorConfig,
        Nesterov,
        Receive,
        Reference,
        Send,
    )

    ref = Reference.from_peers(list(peers), "results", strategy)
    return AggregateExecutorConfig(
        updates=Receive(Reference.from_peers(list(peers), "updates")),
        results=Send(ref),
        optimizer=Nesterov(),
        num_workers=len(peers),
    )


def test_broadcast_all_runs_parallel_and_tolerates_failures(tmp_path):
    from hypha_tpu.messages import TransferStrategy
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    node = _FakeBroadcastNode(fail={"w1"}, delay={"w0": 0.05, "w2": 0.05})
    ps = ParameterServerExecutor(node, tmp_path)
    cfg = _bcast_cfg(["w0", "w1", "w2"], TransferStrategy.ALL)
    upd = tmp_path / "u.st"
    upd.write_bytes(b"x")

    async def scenario():
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await ps._broadcast(cfg, upd, 0)
        return loop.time() - t0

    elapsed = run(scenario(), timeout=10)
    assert sorted(node.pushed) == ["w0", "w2"]  # w1 failed, others landed
    # Concurrent: every peer's push launches together (within one loop
    # tick), not serially. Total wall-clock is no longer ~the slowest
    # push alone — the dead peer's single backed-off re-attempt
    # (aio.retry in push_one, ≤ 0.375 s jittered) now dominates — but it
    # stays bounded: a failed peer costs one retry, never the round.
    starts = {p: t for p, t in node.started[:3]}
    assert len(starts) == 3
    assert max(starts.values()) - min(starts.values()) < 0.04, starts
    assert elapsed < 0.9, elapsed


def test_broadcast_any_first_success_cancels_rest(tmp_path):
    from hypha_tpu.messages import TransferStrategy
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    node = _FakeBroadcastNode(delay={"slow1": 0.5, "slow2": 0.5, "fast": 0.0})
    ps = ParameterServerExecutor(node, tmp_path)
    cfg = _bcast_cfg(["slow1", "fast", "slow2"], TransferStrategy.ANY)
    upd = tmp_path / "u.st"
    upd.write_bytes(b"x")

    async def scenario():
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await ps._broadcast(cfg, upd, 0)
        return loop.time() - t0

    elapsed = run(scenario(), timeout=10)
    assert node.pushed == ["fast"]  # first success; the slow pushes never landed
    assert elapsed < 0.4, elapsed  # did not wait out the slow peers


def test_broadcast_any_falls_through_failures(tmp_path):
    from hypha_tpu.messages import TransferStrategy
    from hypha_tpu.worker.ps_executor import ParameterServerExecutor

    node = _FakeBroadcastNode(fail={"w0", "w1"})
    ps = ParameterServerExecutor(node, tmp_path)
    cfg = _bcast_cfg(["w0", "w1", "w2"], TransferStrategy.ANY)
    upd = tmp_path / "u.st"
    upd.write_bytes(b"x")
    run(ps._broadcast(cfg, upd, 0), timeout=10)
    assert node.pushed == ["w2"]


# ---------------------------------------------------------------------------
# codec satellite: byte-string encode fast path
# ---------------------------------------------------------------------------


def test_cbor_bytes_variants_encode_identically():
    from hypha_tpu import codec as cbor

    payload = bytes(range(256)) * 4
    direct = cbor.dumps(payload)
    assert cbor.dumps(bytearray(payload)) == direct
    assert cbor.dumps(memoryview(payload)) == direct
    assert cbor.loads(direct) == payload
    # The pure-Python encoder (native may be active) agrees.
    assert cbor._py_dumps(payload) == direct
    assert cbor._py_dumps(bytearray(payload)) == direct
    assert cbor._py_dumps(memoryview(payload)) == direct
