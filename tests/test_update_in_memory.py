"""The PS broadcasts the update from the buffers the outer step wrote it into.

``_outer_step`` returns the update where the fused pass left it, framed as
the SafeTensors file it used to be copied into; ``_broadcast`` pushes that
frame from memory, and ``update-N.safetensors`` is written only for a reader
that needs a file (a durable job, a wire codec, a broadcast tree). Here: the
frame is a file every reader takes; a plain job writes no file and the
others write today's; a push from memory over TCP lands the frame, a cut
attempt is sent again whole, and a cancelled one leaves nothing queued that
points into the buffers; and the buffers go back to the sums only once the
fan-out has ended.
"""

from __future__ import annotations

import asyncio
import json
import types

import numpy as np
import pytest
from safetensors.numpy import load as load_bytes
from safetensors.numpy import load_file, save_file

from hypha_tpu import aio, compress
from hypha_tpu.compress.frame import f32_layout, frame_f32, read_delta_into
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
    ShardMap,
)
from hypha_tpu.network import MemoryTransport, Node, RequestError
from hypha_tpu.network.fabric import TcpTransport
from hypha_tpu.stream.accum import RoundAccum, SumBuffers
from hypha_tpu.telemetry import trace
from hypha_tpu.worker import ps_executor
from hypha_tpu.worker.ps_executor import (
    ParameterServerExecutor,
    _OuterMomentum,
    _Update,
)
from test_native import _bits


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _tree(kind: str) -> dict[str, np.ndarray]:
    """Deltas whose keys are not in sorted order, by what is odd about them."""
    rng = np.random.default_rng(5)

    def f32(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {
        # One element; a byte count (12) that is no multiple of 8.
        "odd_sizes": {"z/last": f32(1), "a/first": f32(3), "m": f32(5, 7)},
        # No dimension at all, and a dimension of none.
        "scalar_and_empty": {"s": f32(), "e": f32(0, 4), "b": f32(2, 3)},
        # A leaf of more than one slice of a push, and not a whole number of them.
        "several_slices": {"wte": f32(1_500_003), "bias": f32(3)},
    }[kind]


def _update(tmp_path, tree, rnd=0, momentum=None) -> _Update:
    """The outer step's update for a round whose one delta is ``tree``."""
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    if momentum is None:
        momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    accum = RoundAccum(momentum.sums)
    accum.fold_tree(tree, 4.0)
    return ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, rnd, accum)


def _joined(update: _Update) -> bytes:
    async def collect():
        return [piece async for piece in update.views()]

    pieces = run(collect())
    assert all(isinstance(p, memoryview) for p in pieces)
    assert max(len(p) for p in pieces) <= ps_executor._PUSH_SLICE
    return b"".join(pieces)


def _same_bits(got: dict, want: dict) -> None:
    assert set(got) == set(want)
    for key in want:
        assert got[key].shape == want[key].shape and got[key].dtype == np.float32, key
        assert np.array_equal(_bits(got[key]), _bits(want[key])), key


# ---------------------------------------------------------------------------
# (1) the frame
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["odd_sizes", "scalar_and_empty", "several_slices"])
def test_the_framed_update_is_a_file_safetensors_and_the_workers_reader_take(
    tmp_path, kind
):
    update = _update(tmp_path, _tree(kind))
    want = {k: v.copy() for k, v in update.tree.items()}
    assert list(want) == list(_tree(kind)) != sorted(want)  # buffer order
    blob = _joined(update)
    assert len(blob) == update.nbytes
    _same_bits(load_bytes(blob), want)
    path = tmp_path / "incoming.safetensors"
    path.write_bytes(blob)
    _same_bits(dict(load_file(str(path))), want)
    layout = f32_layout(path)
    assert layout is not None and list(layout) == list(want)  # file order
    pool = SumBuffers()
    for kept in (0, len(want)):  # a worker's first round, then every later one
        flat, stats = read_delta_into(path, pool.lease)
        assert stats.direct == stats.leaves == len(want) and stats.resident == kept
        assert stats.bytes == len(blob)
        _same_bits(flat, want)
        pool.give_back(flat)


@pytest.mark.parametrize("kind", ["odd_sizes", "scalar_and_empty", "several_slices"])
def test_the_frames_views_are_the_leaves_own_memory_and_the_file_is_save_files(
    tmp_path, kind
):
    update = _update(tmp_path, _tree(kind))
    head, views = frame_f32(update.tree)
    n = int.from_bytes(head[:8], "little")
    assert len(head) == 8 + n and n % 8 == 0
    header = json.loads(head[8:])
    assert list(header) == list(update.tree)
    leaves = [leaf for leaf in update.tree.values() if leaf.nbytes]
    assert len(views) == len(leaves)
    for view, leaf in zip(views, leaves):
        assert np.shares_memory(np.frombuffer(view, np.uint8), leaf)
    # Nothing is written until somebody asks, and then it is save_file's file.
    assert update.file is None and not list(tmp_path.glob("update-*"))
    save_file(update.tree, str(tmp_path / "want.safetensors"))
    path = update.ensure_file()
    assert path == tmp_path / "update-0.safetensors" == update.file
    assert path.read_bytes() == (tmp_path / "want.safetensors").read_bytes()
    assert update.ensure_file() == path  # once
    update.retire()
    assert not path.exists()
    with pytest.raises(RuntimeError, match="retired"):
        update.ensure_file()


def test_a_leaf_that_would_have_to_be_copied_is_refused():
    with pytest.raises(ValueError, match="not a C-contiguous float32"):
        frame_f32({"w": np.ones((4, 4), np.float32)[:, ::2]})
    with pytest.raises(ValueError, match="not a C-contiguous float32"):
        frame_f32({"w": np.ones(4, np.float64)})


# ---------------------------------------------------------------------------
# (2) who gets a file
# ---------------------------------------------------------------------------

DELTA = {"w": np.linspace(-1.0, 1.0, 8, dtype=np.float32), "b": np.full(4, 2.0, np.float32)}
ROUNDS = 2


def _spans(tmp_path) -> list[dict]:
    return [json.loads(x) for path in sorted((tmp_path / "spans").glob("spans-*.jsonl"))
            for x in path.read_text().splitlines()]


def _whole_job(tmp_path, monkeypatch, **cfg_kwargs):
    """Two rounds of an aggregate job with one worker. Returns, a round:
    the update as the outer step left it (a copy), the ``update-*`` files
    in the PS's work directory when the broadcast starts (name -> bytes),
    what the broadcast handed ``Node.push``, and what the worker received;
    and the catch-up sums that were kept."""
    rounds = [dict() for _ in range(ROUNDS)]
    catchups = []
    outer = ParameterServerExecutor._outer_step

    def outer_spy(self, received, momentum, lr, mu, work_dir, round_num, *a, **k):
        update = outer(self, received, momentum, lr, mu, work_dir, round_num, *a, **k)
        rounds[round_num]["tree"] = {k: v.copy() for k, v in update.tree.items()}
        rounds[round_num]["work_dir"] = work_dir
        return update

    bcast = ParameterServerExecutor._broadcast

    async def bcast_spy(self, cfg, wire, round_num, *a, **k):
        work_dir = rounds[round_num]["work_dir"]
        rounds[round_num]["files"] = {
            p.name: p.read_bytes() for p in work_dir.rglob("update-*")
        }
        return await bcast(self, cfg, wire, round_num, *a, **k)

    accumulate = ps_executor.CatchupBuffer.accumulate_tree

    def accumulate_spy(self, update, fragment_id=None):
        if self not in catchups:
            catchups.append(self)
        return accumulate(self, update, fragment_id=fragment_id)

    monkeypatch.setattr(ParameterServerExecutor, "_outer_step", outer_spy)
    monkeypatch.setattr(ParameterServerExecutor, "_broadcast", bcast_spy)
    monkeypatch.setattr(ps_executor.CatchupBuffer, "accumulate_tree", accumulate_spy)

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
            done = progress.round >= ROUNDS - 1
            return ProgressResponse(
                kind=ProgressResponseKind.DONE if done else ProgressResponseKind.OK
            )

        nodes["sched"].on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)
        push = nodes["ps"].push

        async def push_spy(peer, header, source, **timing):
            rounds[header["round"]]["source"] = source
            return await push(peer, header, source, **timing)

        nodes["ps"].push = push_spy
        ref = Reference.from_peers(["w1"], "updates")
        spec = JobSpec(
            job_id="agg-u",
            executor=Executor(
                kind="aggregate", name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(ref), results=Send(ref),
                    optimizer=Nesterov(lr=0.7, momentum=0.9), num_workers=1,
                    **cfg_kwargs,
                ),
            ),
        )
        pse = ParameterServerExecutor(nodes["ps"], tmp_path / "work")
        execution = await pse.execute("agg-u", spec, "sched")
        f = tmp_path / "d.st"
        save_file(DELTA, str(f))
        for rnd in range(ROUNDS):
            header = {"resource": "updates", "name": "delta", "num_samples": 4,
                      "round": rnd}
            await aio.retry(lambda: nodes["w1"].push("ps", header, f),
                            attempts=3, base_delay=0.05)
            got = await nodes["w1"].next_push(timeout=10)
            rounds[rnd]["header"] = dict(got.resource)
            rounds[rnd]["received"] = await got.read_all()
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == "completed"
        for n in nodes.values():
            await n.stop()

    trace.enable(tmp_path / "spans", node="ps")
    try:
        run(main())
    finally:
        trace.disable()
    return rounds, catchups


def _as_file(tmp_path, tree, codec="none", **kw) -> bytes:
    path = tmp_path / f"want-{codec}.bin"
    if codec == "none":
        save_file(tree, str(path))
    else:
        compress.write_delta(path, tree, codec, **kw)
    return path.read_bytes()


def _tree_broadcast_case(tmp_path):
    """``_broadcast`` under a broadcast tree: the relay and its leaves get
    the file, which is written because a relay is handed a path."""
    from hypha_tpu.stream.reduce import BroadcastRelay

    groups = [["r", "a", "b"]]
    smap = ShardMap(round=0, shards=["ps0"], tags=["u.s0"], fragments=1, groups=groups)
    update = _update(tmp_path / "ps", DELTA, rnd=5)
    tree = {k: v.copy() for k, v in update.tree.items()}

    async def main():
        hub = MemoryTransport()
        nodes = {n: Node(hub.shared(), peer_id=n) for n in ("ps0", "r", "a", "b")}
        for n in nodes.values():
            await n.start()
        for x in nodes.values():
            for y in nodes.values():
                if x is not y:
                    x.add_peer_addr(y.peer_id, y.listen_addrs[0])
        relay = BroadcastRelay(
            nodes["r"],
            types.SimpleNamespace(
                ps_shards=smap,
                results=Receive(Reference.from_peers(["ps0", "r"], "results")),
            ),
            work_dir=tmp_path / "relay",
        )
        relay.start()
        pse = ParameterServerExecutor(nodes["ps0"], tmp_path / "ps")
        cfg = types.SimpleNamespace(
            results=Send(Reference.from_peers(["r", "a", "b"], "results")),
            broadcast_tree=smap,
        )
        await pse._broadcast(cfg, update, 5, span_round=5)
        got = {}
        for peer in ("a", "b", "r"):
            push = await nodes[peer].next_push(timeout=20)
            got[peer] = (dict(push.resource)["name"], await push.read_all())
        await relay.stop()
        for n in nodes.values():
            await n.stop()
        return got

    trace.enable(tmp_path / "spans", node="ps")
    try:
        got = run(main())
    finally:
        trace.disable()
    want = _as_file(tmp_path, tree)
    assert update.file == tmp_path / "ps" / "update-5.safetensors"
    assert update.file.read_bytes() == want
    assert got == {peer: ("update-5.safetensors", want) for peer in ("a", "b", "r")}
    (span,) = [s for s in _spans(tmp_path) if s["name"] == "broadcast"]
    assert span["attrs"]["source"] == "file" and span["attrs"]["tree"] is True
    assert span["attrs"]["bytes"] == len(want)
    update.retire()
    assert not list((tmp_path / "ps").glob("update-*"))


@pytest.mark.parametrize(
    "case", ["plain", "durable", "bf16", "int8", "broadcast_tree", "elastic_catchup"]
)
def test_a_plain_job_writes_no_update_file_and_every_other_gets_todays(
    tmp_path, monkeypatch, case
):
    if case == "broadcast_tree":
        (tmp_path / "ps").mkdir()
        return _tree_broadcast_case(tmp_path)
    codec = case if case in ("bf16", "int8") else "none"
    rounds, catchups = _whole_job(tmp_path, monkeypatch, **{
        "plain": {},
        "durable": {"checkpoint_dir": str(tmp_path / "ckpt")},
        "bf16": {"delta_codec": "bf16"},
        "int8": {"delta_codec": "int8"},
        "elastic_catchup": {"quorum_fraction": 1.0, "round_deadline_s": 30.0},
    }[case])
    spans = _spans(tmp_path)
    in_memory = case in ("plain", "elastic_catchup")
    ef = compress.ErrorFeedback()  # the job's residual, replayed round by round
    for rnd, r in enumerate(rounds):
        name = f"update-{rnd}.safetensors"
        f32_file = _as_file(tmp_path, r["tree"])
        (saved,) = [s for s in spans if s["name"] == "outer_step.save_update"
                    and s["attrs"]["round"] == rnd]
        (bcast,) = [s for s in spans if s["name"] == "broadcast"
                    and s["attrs"]["round"] == rnd]
        assert saved["attrs"]["in_memory"] is in_memory
        assert saved["attrs"]["leaves"] == len(DELTA) and saved["attrs"]["bytes"] > 0
        assert bcast["attrs"]["source"] == ("memory" if in_memory else "file")
        assert bcast["attrs"]["bytes"] == len(r["received"])
        if in_memory:
            # No file under the PS's work directory, the frame on the wire.
            assert r["files"] == {}
            assert hasattr(r["source"], "__aiter__")
            assert r["header"]["name"] == name
            _same_bits(load_bytes(r["received"]), r["tree"])
            path = tmp_path / f"incoming-{rnd}.safetensors"
            path.write_bytes(r["received"])
            assert list(f32_layout(path)) == list(r["tree"])
            continue
        # The f32 update as save_file writes it, and the wire made from it.
        assert r["files"][name] == f32_file
        if codec == "none":
            assert set(r["files"]) == {name}
            assert r["source"] == r["work_dir"] / name
            assert r["header"]["name"] == name
            assert r["received"] == f32_file
        else:
            wire = f"update-{rnd}.wire.safetensors"
            assert set(r["files"]) == {name, wire}
            assert r["source"] == r["work_dir"] / wire
            assert r["header"]["name"] == wire
            kw = {"ef": ef} if codec == "int8" else {}
            # (_as_file left the f32 file there: the job encodes from load_file's tree.)
            f32_tree = dict(load_file(str(tmp_path / "want-none.bin")))
            assert r["received"] == r["files"][wire] == _as_file(
                tmp_path, f32_tree, codec, **kw
            )
    if case == "elastic_catchup":
        # What a rejoiner would be sent is what the worker merged, from the
        # tree in memory as from the file: the running sum of the updates.
        (catchup,) = catchups
        cum, n, _ = catchup.state()
        assert n == ROUNDS
        want = {k: v.copy() for k, v in rounds[0]["tree"].items()}
        for r in rounds[1:]:
            for key in want:
                want[key] += r["tree"][key]
        _same_bits(cum, want)
    else:
        assert not catchups
    # Nothing parameter-sized is left behind by a job that has ended.
    assert not list((tmp_path / "work").rglob("update-*"))


# ---------------------------------------------------------------------------
# (3) the push from memory, over TCP
# ---------------------------------------------------------------------------


async def _tcp_pair():
    nodes = {n: Node(TcpTransport(), peer_id=n) for n in ("ps", "w")}
    for n in nodes.values():
        await n.start(["127.0.0.1:0"])
    nodes["ps"].add_peer_addr("w", nodes["w"].listen_addrs[0])
    return nodes


def test_a_loopback_push_from_memory_lands_the_framed_update(tmp_path):
    update = _update(tmp_path, _tree("several_slices"))
    blob = _joined(update)
    header = {"resource": "results", "name": update.name, "round": 0}

    async def main():
        nodes = await _tcp_pair()
        before = nodes["ps"].bytes_out
        sent = await nodes["ps"].push("w", header, update.views())
        push = await nodes["w"].next_push(timeout=10)
        await push.save_to(tmp_path / "incoming" / push.resource["name"])
        for n in nodes.values():
            await n.stop()
        return sent, nodes["ps"].bytes_out - before

    (tmp_path / "incoming").mkdir()
    sent, counted = run(main())
    assert sent == counted == len(blob) == update.nbytes
    assert (tmp_path / "incoming" / "update-0.safetensors").read_bytes() == blob
    assert update.file is None


def test_an_attempt_cut_mid_stream_is_followed_by_one_that_sends_it_all_again(tmp_path):
    update = _update(tmp_path, _tree("several_slices"))
    blob = _joined(update)
    header = {"resource": "results", "name": update.name, "round": 0}
    attempts = []

    def source():
        attempts.append(len(attempts))
        if len(attempts) > 1:
            return update.views()

        async def cut():
            sent = 0
            async for piece in update.views():
                if sent > len(blob) // 2:
                    raise ConnectionResetError("cut mid-stream")
                sent += len(piece)
                yield piece

        return cut()

    async def main():
        nodes = await _tcp_pair()

        async def receive():
            got = []
            for _ in range(2):
                push = await nodes["w"].next_push(timeout=10)
                got.append(await push.read_all())
            return got

        receiver = asyncio.create_task(receive())
        await aio.retry(
            lambda: nodes["ps"].push("w", header, source()),
            attempts=2, base_delay=0.01, retry_on=(RequestError, OSError),
            what="push from memory",
        )
        got = await receiver
        for n in nodes.values():
            await n.stop()
        return got

    first, second = run(main())
    assert attempts == [0, 1]
    assert len(blob) // 2 < len(first) < len(blob) and blob.startswith(first)
    assert second == blob


def test_a_cancelled_push_leaves_nothing_queued_that_points_into_the_buffers(tmp_path):
    """A peer that stops reading, an attempt that times out, and buffers that
    are written again: what the peer reads afterwards is a beginning of the
    update as it was, cut by a reset, and never the next round's bytes."""
    rng = np.random.default_rng(9)
    tree = {"wte": rng.standard_normal(8_000_000).astype(np.float32)}  # 32 MB
    update = _update(tmp_path, tree)
    blob = _joined(update)
    header = {"resource": "results", "name": update.name, "round": 0}

    async def main():
        nodes = await _tcp_pair()
        streams = []
        stream_to = nodes["ps"]._stream_to

        async def spy(peer, proto):
            streams.append(await stream_to(peer, proto))
            return streams[-1]

        nodes["ps"]._stream_to = spy
        with pytest.raises(asyncio.TimeoutError):
            # Nobody takes the push: the sockets' buffers fill, a write waits.
            await asyncio.wait_for(nodes["ps"].push("w", header, update.views()), 1.0)
        transport = streams[-1]._writer.transport
        assert transport.get_write_buffer_size() == 0 and transport.is_closing()
        for leaf in update.tree.values():
            leaf[...] = 7.0  # the next round's sum
        push = await nodes["w"].next_push(timeout=10)
        got = b""
        try:
            while chunk := await push.stream.read(1 << 20):
                got += chunk
        except OSError:
            pass
        push.finish()
        for n in nodes.values():
            await n.stop()
        return got

    got = run(main())
    assert 0 < len(got) < len(blob) and blob.startswith(got)


# ---------------------------------------------------------------------------
# (4) who owns the buffers
# ---------------------------------------------------------------------------


class _StalledNode:
    """A node whose push takes the frame's first piece and then waits."""

    def __init__(self) -> None:
        self.gate = asyncio.Event()
        self.stalled = asyncio.Event()
        self.received: dict[str, bytes] = {}

    async def push(self, peer, header, source) -> int:
        got = []
        async for piece in source:
            got.append(bytes(piece))
            if len(got) == 1:
                self.stalled.set()
                await self.gate.wait()
        self.received[peer] = b"".join(got)
        return len(self.received[peer])


def test_the_buffers_go_back_only_when_the_fan_out_has_ended(tmp_path):
    tree = _tree("several_slices")
    momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    update = _update(tmp_path, tree, momentum=momentum)
    mine = {k: v.ctypes.data for k, v in update.tree.items()}
    blob = _joined(update)
    ref = Reference.from_peers(["w0", "w1"], "results")
    cfg = AggregateExecutorConfig(
        updates=Receive(Reference.from_peers(["w0", "w1"], "updates")),
        results=Send(ref), optimizer=Nesterov(), num_workers=2,
    )
    delta = tmp_path / "next.st"
    compress.write_delta(delta, _tree("several_slices"), "none")

    async def main():
        node = _StalledNode()
        ps = ParameterServerExecutor(node, tmp_path)
        fan_out = asyncio.create_task(ps._broadcast(cfg, update, 0))
        await asyncio.wait_for(node.stalled.wait(), 10)
        # A delta of the next round arrives while the pushes are open: its
        # sum opens over buffers of its own, and the update's are untouched.
        early = RoundAccum(momentum.sums)
        did = await asyncio.to_thread(early.fold, delta, 4.0)
        assert did.direct == did.leaves == len(tree) and did.resident == 0
        theirs = {k: v.ctypes.data for k, v in early.partial().items()}
        assert not set(theirs.values()) & set(mine.values())
        node.gate.set()
        await fan_out
        update.retire()
        return node.received, early

    received, early = run(main())
    assert received == {"w0": blob, "w1": blob}
    # The round after: both sums' buffers are the job's now, and a first
    # fold finds pages that exist.
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    ps._outer_step({}, momentum, 0.7, 0.9, tmp_path, 1, early).retire()
    later = RoundAccum(momentum.sums)
    did = later.fold(delta, 4.0)
    assert did.direct == did.resident == did.leaves == len(tree)


# ---------------------------------------------------------------------------
# (5) the line the harness parses
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("file", [False, True], ids=["in_memory", "file_asked_for"])
def test_the_outer_line_parses_with_every_field_the_harness_reads(tmp_path, caplog, file):
    from perfbench import logs

    caplog.set_level("INFO", logger="hypha.worker.ps")
    ps = ParameterServerExecutor(node=None, work_root=tmp_path)
    momentum = _OuterMomentum(tmp_path / "momentum.safetensors", save=False)
    accum = RoundAccum(momentum.sums)
    accum.fold_tree(DELTA, 4.0)
    update = ps._outer_step({"w0": None}, momentum, 0.7, 0.9, tmp_path, 3, accum,
                            file=file)
    (line,) = logs.outer_steps("\n".join(r.getMessage() for r in caplog.records))
    assert line["round"] == 3 and line["deltas"] == 1 and line["tensors"] == len(DELTA)
    assert line["native_kernels"] in (True, False) and line["native_cbor"] in (True, False)
    for key in ("wall_s", "mean_s", "load_s", "nesterov_s", "save_update_s",
                "save_momentum_s"):
        assert isinstance(line[key], float) and line[key] >= 0, key
    assert line["bytes"] == sum(v.nbytes for v in DELTA.values())
    assert line["threads"] >= 1 and line["momentum_resident"] == 0
    assert line["momentum_saved"] == 0
    assert line["update_in_memory"] == int(not file)
    assert (update.file is not None) == file
    assert bool(list(tmp_path.glob("update-*"))) == file
