"""The PS saves a worker's delta over the file the last round left.

``PushStream.save_to(over=spare)`` writes the payload over the spare, so the
bytes land in pages that exist, and renames it onto the destination; a
plain blocking PS job without a journal keeps a closed round's delta files
in a spool under its work directory (``_spool_deltas``) and hands them to
the next round's pushes. Here: the file ``save_to`` leaves is the payload
and nothing of the spare, whatever the spare's length; a push that ends any
way but at EOF leaves no file; ``over=None`` is as it was; and through a
whole PS job the updates are bit for bit those of a PS that is never handed
a spare, a duplicate un-folds exactly, a durable or elastic job is never
handed one, and the spool holds no more than a round received and goes with
the job.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import os
import re
import socket
import struct

import numpy as np
import pytest
from safetensors.numpy import load as load_bytes
from safetensors.numpy import save as save_bytes

from hypha_tpu import aio
from hypha_tpu.messages import (
    PROTOCOL_PROGRESS,
    AggregateExecutorConfig,
    DataSlice,
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
from hypha_tpu.network import node as node_mod
from hypha_tpu.network.fabric import TcpTransport
from hypha_tpu.network.node import ACCEPT_LIMIT
from hypha_tpu.telemetry import trace
from hypha_tpu.worker.ps_executor import ParameterServerExecutor
from test_native import _bits


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _payload(n: int, salt: int = 0) -> bytes:
    return bytes((i * 7 + salt) % 251 for i in range(257)) * (n // 257) + b"t" * (n % 257)


async def _pair(transport: str):
    if transport == "tcp":
        a, b = Node(TcpTransport(), peer_id="a"), Node(TcpTransport(), peer_id="b")
        await a.start(["127.0.0.1:0"])
        await b.start(["127.0.0.1:0"])
    else:
        hub = MemoryTransport()
        a, b = Node(hub.shared(), peer_id="a"), Node(hub.shared(), peer_id="b")
        await a.start()
        await b.start()
    a.add_peer_addr("b", b.listen_addrs[0])
    return a, b


# ---------------------------------------------------------------------------
# (1) save_to(over=...)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["tcp", "memory"])
@pytest.mark.parametrize("spare_len", ["longer", "shorter", "equal", "empty"])
def test_over_a_spare_the_file_is_the_payload_and_the_spares_inode(
    tmp_path, transport, spare_len
):
    # More than two 4 MiB pieces, and not a whole number of them.
    data = _payload(9_000_001)
    spare = tmp_path / "spool" / "delta-0.safetensors"
    spare.parent.mkdir()
    spare.write_bytes(
        b"\xee" * {"longer": 4 * len(data) + 5, "shorter": len(data) // 4,
                   "equal": len(data), "empty": 0}[spare_len]
    )
    inode = spare.stat().st_ino
    dest = tmp_path / "delta-1.safetensors"

    async def main():
        a, b = await _pair(transport)
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=0), data))
        push = await b.next_push(timeout=10)
        n = await push.save_to(dest, over=spare)
        await sending
        assert b._push_sem._value == ACCEPT_LIMIT
        for node in (a, b):
            await node.stop()
        return n, push

    n, push = run(main())
    assert n == len(data) == dest.stat().st_size
    assert dest.read_bytes() == data
    assert not spare.exists() and list(spare.parent.iterdir()) == []
    assert dest.stat().st_ino == inode
    assert push.recycled is True
    assert push.read_s >= 0.0 and push.write_s > 0.0


@pytest.mark.parametrize("transport", ["tcp", "memory"])
def test_without_a_spare_the_save_is_as_it_was(tmp_path, transport):
    """``over=None``: a fresh file (one that was there is truncated, as
    ``open(path, "wb")`` always did), the payload's bytes, and a failed push
    leaves what it had written."""
    data = _payload(5_000_003, salt=3)
    dest = tmp_path / "delta.bin"
    dest.write_bytes(b"\xee" * (2 * len(data)))

    async def main():
        a, b = await _pair(transport)
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=0), data))
        push = await b.next_push(timeout=10)
        n = await push.save_to(dest)
        await sending
        # ... and one whose stream breaks: today's partial file stays.
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=1), data))
        broken = await b.next_push(timeout=10)
        broken.stream = _Breaks(broken.stream, after=1 << 20)
        with pytest.raises(ConnectionResetError):
            await broken.save_to(tmp_path / "partial.bin")
        assert b._push_sem._value == ACCEPT_LIMIT
        sending.cancel()
        for node in (a, b):
            await node.stop()
        return n, push

    n, push = run(main())
    assert n == len(data) and dest.read_bytes() == data
    assert push.recycled is False
    partial = (tmp_path / "partial.bin").read_bytes()
    assert 0 < len(partial) < len(data) and data.startswith(partial)


def test_the_digest_of_a_push_saved_over_a_spare_is_the_payloads(tmp_path):
    data = _payload(4_500_000, salt=9)
    spare = tmp_path / "spare.bin"
    spare.write_bytes(b"\xee" * (len(data) + 999))
    hasher = hashlib.sha256()

    async def main():
        a, b = await _pair("memory")
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=0), data))
        push = await b.next_push(timeout=10)
        await push.save_to(tmp_path / "d.bin", hasher=hasher, over=spare)
        await sending
        for node in (a, b):
            await node.stop()

    run(main())
    assert hasher.hexdigest() == hashlib.sha256(data).hexdigest()
    assert (tmp_path / "d.bin").read_bytes() == data


def test_a_spare_that_is_gone_gives_a_fresh_file_and_says_so(tmp_path):
    data = _payload(100_000)

    async def main():
        a, b = await _pair("memory")
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=0), data))
        push = await b.next_push(timeout=10)
        n = await push.save_to(tmp_path / "d.bin", over=tmp_path / "no-such-spare")
        await sending
        for node in (a, b):
            await node.stop()
        return n, push

    n, push = run(main())
    assert n == len(data) and (tmp_path / "d.bin").read_bytes() == data
    assert push.recycled is False


def test_a_plain_tcp_push_with_a_spare_goes_to_the_drain_thread_and_no_other_does(
    tmp_path, monkeypatch
):
    """The thread that ``recv_into``s a kept buffer takes a push that comes
    with a spare over plain TCP; a digest, a transport without a raw socket
    or no spare keep the loop's path."""
    drains = []
    real = node_mod._drain_socket_to_file
    monkeypatch.setattr(
        node_mod, "_drain_socket_to_file",
        lambda *a, **k: (drains.append(1), real(*a, **k))[1],
    )
    data = _payload(9_000_001)

    async def one(a, b, i, **kw):
        sending = asyncio.create_task(a.push("b", DataSlice(dataset="d", index=i), data))
        push = await b.next_push(timeout=10)
        before = b.bytes_in
        n = await push.save_to(tmp_path / f"d{i}.bin", **kw)
        await sending
        assert n == len(data) and (tmp_path / f"d{i}.bin").read_bytes() == data
        assert b.bytes_in - before == len(data) or b.bytes_in >= len(data)
        return push

    def spare(i):
        path = tmp_path / f"spare{i}.bin"
        path.write_bytes(b"\xee" * (len(data) + i))
        return path

    async def main():
        taken = []
        a, b = await _pair("tcp")
        for i, kw in enumerate((
            {"over": spare(0)},                              # the thread
            {},                                              # the loop
            {"over": spare(2), "hasher": hashlib.sha256()},  # the loop: a digest
        )):
            push = await one(a, b, i, **kw)
            taken.append((len(drains), push.recycled))
        m1, m2 = await _pair("memory")
        push = await one(m1, m2, 3, over=spare(3))           # the loop: no raw socket
        taken.append((len(drains), push.recycled))
        for node in (a, b, m1, m2):
            await node.stop()
        return taken

    assert run(main()) == [(1, True), (1, False), (1, True), (1, True)]


class _Breaks:
    """A push's stream whose sender is lost after ``after`` bytes."""

    def __init__(self, inner, after: int) -> None:
        self._inner, self._left = inner, after

    async def read(self, n: int = 65536) -> bytes:
        if self._left <= 0:
            raise ConnectionResetError("sender lost mid-push")
        data = await self._inner.read(min(n, self._left))
        self._left -= len(data)
        return data

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.mark.parametrize("transport", ["tcp", "memory"])
@pytest.mark.parametrize("how", ["reset", "cancelled", "timeout"])
def test_a_push_that_ends_early_over_a_spare_leaves_no_file_and_frees_the_slot(
    tmp_path, how, transport
):
    """Beside ``test_network``'s dead-sender case: with a spare the partial
    file would read as the push's head and the last round's tail. Over TCP
    the drain thread has the socket, over the memory transport the loop."""
    from hypha_tpu import messages

    spare = tmp_path / "spool" / "spare.bin"
    spare.parent.mkdir()
    spare.write_bytes(b"\xee" * 3_000_000)
    dest = tmp_path / "delta.bin"

    async def main():
        a, b = await _pair(transport)

        async def dribble():
            yield b"x" * 300_000
            await asyncio.sleep(3600)  # stalls until the receiver gives up

        if how == "reset" and transport == "tcp":
            # The sender's connection is reset under the push.
            stream = await a._stream_to("b", node_mod.PROTOCOL_PUSH)
            await stream.write_frame(messages.encode(DataSlice(dataset="d", index=0)))
            await stream.write(b"x" * 300_000)
            sending = asyncio.create_task(asyncio.sleep(0))
        else:
            sending = asyncio.create_task(
                a.push("b", DataSlice(dataset="d", index=0), dribble())
            )
        push = await b.next_push(timeout=10)
        if how == "reset":
            saving = asyncio.create_task(push.save_to(dest, over=spare))
            if transport == "tcp":
                await asyncio.sleep(0.3)
                # Linger 0: the close is a reset, not a clean end.
                stream._writer.get_extra_info("socket").setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0)
                )
                stream._writer.transport.abort()
            else:
                push.stream = _Breaks(push.stream, after=100_000)
            with pytest.raises(ConnectionError):
                await saving
        elif how == "cancelled":
            saving = asyncio.create_task(push.save_to(dest, over=spare))
            await asyncio.sleep(0.3)
            # Mid-push the destination's name is not there yet.
            assert spare.exists() and not dest.exists()
            saving.cancel()
            with pytest.raises(asyncio.CancelledError):
                await saving
        else:
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(push.save_to(dest, over=spare), 0.3)
        assert b._push_sem._value == ACCEPT_LIMIT
        assert not dest.exists() and not spare.exists()
        sending.cancel()
        for node in (a, b):
            await node.stop()

    run(main())
    assert not dest.exists() and not spare.exists()
    assert list(spare.parent.iterdir()) == []


def test_a_sender_that_goes_away_leaves_its_bytes_or_nothing_never_the_spares_tail(
    tmp_path,
):
    """The sender's node stops mid-push (``test_network``'s case). Whether
    the receiver sees a clean end or an error is the transport's affair:
    the destination is then exactly what arrived, or is not there."""
    spare = tmp_path / "spare.bin"
    spare.write_bytes(b"\xee" * 1_000_000)
    dest = tmp_path / "delta.bin"

    async def main():
        a, b = await _pair("tcp")

        async def dribble():
            yield b"x" * 4096
            await asyncio.sleep(3600)

        sending = asyncio.create_task(
            a.push("b", DataSlice(dataset="d", index=0), dribble())
        )
        push = await b.next_push(timeout=10)
        saving = asyncio.create_task(push.save_to(dest, over=spare))
        await asyncio.sleep(0.2)
        sending.cancel()
        await a.stop()
        try:
            await asyncio.wait_for(saving, 10)
        except (ConnectionError, OSError):
            pass
        assert b._push_sem._value == ACCEPT_LIMIT
        await b.stop()

    run(main())
    assert not spare.exists()
    assert not dest.exists() or dest.read_bytes() == b"x" * 4096


# ---------------------------------------------------------------------------
# (2) through a PS job
# ---------------------------------------------------------------------------

ROUNDS = 3
UPLOAD_LINE = re.compile(
    r"ps upload: round=(\d+) peer=(\S+) bytes=(\d+) pages=(recycled|fresh) "
    r"wall_s=([\d.]+) read_s=([\d.]+) write_s=([\d.]+)$"
)


def _delta(worker: int, rnd: int, salt: int = 0) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(1000 * worker + 10 * rnd + salt)
    # A round's deltas differ in length by round: a spare is longer or
    # shorter than the push saved over it (the names' lengths in the header).
    return {
        "wte": rng.standard_normal((64, 33)).astype(np.float32),
        "h.0.attn" + "x" * (3 * rnd): rng.standard_normal(257).astype(np.float32),
        "b": rng.standard_normal(()).astype(np.float32),
    }


# The program's own, whatever spies a test's earlier job left in their place.
_SPOOL = ParameterServerExecutor._spool_deltas
_SAVE = ParameterServerExecutor._save_delta
_OUTER = ParameterServerExecutor._outer_step


def _plain_sends(workers: int) -> list[list[tuple[int, dict]]]:
    return [[(w, _delta(w, rnd)) for w in range(workers)] for rnd in range(ROUNDS)]


def _job(tmp_path, monkeypatch, caplog, sends, *, spares=True, stop_after=None,
         transport="memory", **cfg_kwargs):
    """An aggregate job of ``len(sends)`` rounds over the memory transport
    (the loop's path) or loopback TCP (the drain thread);
    ``sends[rnd]`` are the pushes of a round, in order, as (worker, tree).
    ``spares=False`` is a PS that is never handed a spare; ``stop_after``
    cancels the job once that round's update has arrived. Returns what the
    first worker received a round, what ``_save_delta`` was handed as
    ``over`` a push, the spool's listing after each cleanup, the ``ps
    upload:`` lines, the spans, and the job's work directory."""
    workers = 1 + max(w for rnd in sends for w, _ in rnd)
    names = [f"w{i}" for i in range(workers)]
    seen = {"overs": [], "spool": [], "work_dir": None, "durable": []}

    def spool_spy(received, work_dir, kept):
        _SPOOL(received, work_dir, kept if spares else None)
        d = work_dir / "spare"
        seen["spool"].append(
            (len(received), sorted(p.name for p in d.iterdir()) if d.is_dir() else None)
        )

    async def save_spy(push, work_dir, round_num, *a, over=None, **k):
        seen["overs"].append((round_num, push.peer, over))
        return await _SAVE(push, work_dir, round_num, *a, over=over, **k)

    def outer_spy(self, received, momentum, lr, mu, work_dir, round_num, *a, **k):
        seen["work_dir"] = work_dir
        if cfg_kwargs.get("checkpoint_dir"):
            # What the journal names, as the files stand when the round closes.
            seen["durable"].append(
                {path.name: path.read_bytes() for path, _ in received.values()}
            )
        return _OUTER(self, received, momentum, lr, mu, work_dir, round_num, *a, **k)

    monkeypatch.setattr(ParameterServerExecutor, "_spool_deltas", staticmethod(spool_spy))
    monkeypatch.setattr(ParameterServerExecutor, "_save_delta", staticmethod(save_spy))
    monkeypatch.setattr(ParameterServerExecutor, "_outer_step", outer_spy)
    received: list[bytes] = []

    async def main():
        hub = MemoryTransport()
        nodes = {
            n: Node(TcpTransport() if transport == "tcp" else hub.shared(), peer_id=n)
            for n in ["ps", "sched", *names]
        }
        for n in nodes.values():
            await n.start(*([["127.0.0.1:0"]] if transport == "tcp" else []))
        for x in nodes.values():
            for y in nodes.values():
                if x is not y:
                    x.add_peer_addr(y.peer_id, y.listen_addrs[0])

        async def on_progress(peer, progress):
            done = progress.round >= len(sends) - 1
            return ProgressResponse(
                kind=ProgressResponseKind.DONE if done else ProgressResponseKind.OK
            )

        nodes["sched"].on(PROTOCOL_PROGRESS, Progress).respond_with(on_progress)
        ref = Reference.from_peers(names, "updates")
        spec = JobSpec(
            job_id="agg-r",
            executor=Executor(
                kind="aggregate", name="parameter-server",
                aggregate=AggregateExecutorConfig(
                    updates=Receive(ref), results=Send(ref),
                    optimizer=Nesterov(lr=0.7, momentum=0.9),
                    num_workers=workers, **cfg_kwargs,
                ),
            ),
        )
        pse = ParameterServerExecutor(nodes["ps"], tmp_path / "work")
        execution = await pse.execute("agg-r", spec, "sched")
        for rnd, pushes in enumerate(sends):
            for i, (w, tree) in enumerate(pushes):
                header = {"resource": "updates", "name": f"delta-{i}",
                          "num_samples": 4 + w, "round": rnd}
                blob = save_bytes(tree)
                await aio.retry(
                    lambda n=names[w], h=header, b=blob: nodes[n].push("ps", h, b),
                    attempts=3, base_delay=0.05,
                )
            for n in names:
                got = await nodes[n].next_push(timeout=10)
                blob = await got.read_all()
                if n == names[0]:
                    received.append(blob)
            if stop_after == rnd:
                # Into the next round: its first push is saved over a spare
                # while the other spare waits in the spool. Then the job goes.
                w, tree = sends[rnd + 1][0]
                header = {"resource": "updates", "name": "delta-0",
                          "num_samples": 4 + w, "round": rnd + 1}
                await nodes[names[w]].push("ps", header, save_bytes(tree))
                for _ in range(500):
                    if len(seen["overs"]) > sum(map(len, sends[: rnd + 1])):
                        break
                    await asyncio.sleep(0.01)
                assert seen["work_dir"].is_dir()
                seen["at_cancel"] = sorted(
                    str(p.relative_to(seen["work_dir"]))
                    for p in seen["work_dir"].rglob("*.safetensors")
                )
                await execution.cancel()
                break
        status = await asyncio.wait_for(execution.wait(), 10)
        assert status.state == ("completed" if stop_after is None else "cancelled")
        # The job's last act, after it has said how it ended.
        for _ in range(500):
            if not seen["work_dir"].exists():
                break
            await asyncio.sleep(0.01)
        for n in nodes.values():
            await n.stop()

    trace.enable(tmp_path / "spans", node="ps")
    caplog.set_level(logging.INFO, logger="hypha.worker.ps")
    try:
        run(main())
    finally:
        trace.disable()
    spans = [json.loads(x) for path in sorted((tmp_path / "spans").glob("spans-*.jsonl"))
             for x in path.read_text().splitlines()]
    lines = [m.groups() for m in map(UPLOAD_LINE.search, caplog.messages) if m]
    return {**seen, "received": received, "lines": lines, "work": tmp_path / "work",
            "uploads": [s for s in spans if s["name"] == "upload"]}


def _same_update(got: bytes, want: bytes) -> None:
    got, want = load_bytes(got), load_bytes(want)
    assert set(got) == set(want)
    for key in want:
        assert np.array_equal(_bits(got[key]), _bits(want[key])), key


@pytest.mark.parametrize("transport", ["memory", "tcp"])
@pytest.mark.parametrize("workers", [1, 2])
def test_round_0_saves_into_fresh_pages_and_every_later_round_over_a_spare(
    tmp_path, monkeypatch, caplog, workers, transport
):
    sends = _plain_sends(workers)
    job = _job(tmp_path / "a", monkeypatch, caplog, sends, transport=transport)
    want = [("fresh" if rnd == 0 else "recycled") for rnd in range(ROUNDS)
            for _ in range(workers)]
    # ... on the upload span, with the split and the bytes that arrived ...
    assert [s["attrs"]["pages"] for s in job["uploads"]] == want
    for span, (rnd, pushes) in zip(
        job["uploads"], [(r, p) for r, ps in enumerate(sends) for p in ps]
    ):
        attrs = span["attrs"]
        assert attrs["round"] == rnd
        assert attrs["bytes"] == len(save_bytes(pushes[1]))
        assert attrs["read_s"] >= 0.0 and attrs["write_s"] >= 0.0
        took = (span["end_ns"] - span["start_ns"]) / 1e9
        assert attrs["read_s"] + attrs["write_s"] <= took + 1e-3
    # ... and on the line, one a push.
    assert [(int(r), pages) for r, _, _, pages, *_ in job["lines"]] == [
        (rnd, want[rnd * workers + i]) for rnd in range(ROUNDS) for i in range(workers)
    ]
    assert [int(b) for _, _, b, *_ in job["lines"]] == [
        s["attrs"]["bytes"] for s in job["uploads"]
    ]
    # Every push after round 0 was handed a spare; the spool never held more
    # than the round had received, and was empty once they were handed out.
    assert [over is not None for _, _, over in job["overs"]] == [p == "recycled" for p in want]
    assert job["spool"] == [(workers, job["spool"][0][1])] + job["spool"][1:]
    assert all(n == workers and len(names) == workers for n, names in job["spool"])
    # Gone with the job.
    assert not job["work_dir"].exists() and list(job["work"].iterdir()) == []


@pytest.mark.parametrize("transport", ["memory", "tcp"])
@pytest.mark.parametrize("workers", [1, 2])
def test_every_rounds_update_is_bit_for_bit_that_of_a_ps_never_handed_a_spare(
    tmp_path, monkeypatch, caplog, workers, transport
):
    sends = _plain_sends(workers)
    with_spares = _job(tmp_path / "a", monkeypatch, caplog, sends, transport=transport)
    caplog.clear()
    without = _job(tmp_path / "b", monkeypatch, caplog, sends, spares=False,
                   transport=transport)
    assert all(over is None for _, _, over in without["overs"])
    assert {pages for *_, pages, _, _, _ in without["lines"]} == {"fresh"}
    assert all(names is None for _, names in without["spool"])
    assert any(over is not None for _, _, over in with_spares["overs"])
    assert len(with_spares["received"]) == len(without["received"]) == ROUNDS
    for got, want in zip(with_spares["received"], without["received"]):
        assert got == want
        _same_update(got, want)


def test_a_duplicate_in_a_round_with_a_spare_unfolds_exactly(
    tmp_path, monkeypatch, caplog
):
    """Round 1: worker 0 sends, sends again (another delta), then worker 1.
    The first send is saved over a spare, un-folded from that file and
    unlinked; the re-send takes the spare that is left, worker 1 a fresh
    file. The update is that of a round in which only the re-send came."""
    sends = _plain_sends(2)
    resend = _delta(0, 1, salt=7)
    twice = [list(r) for r in sends]
    twice[1] = [sends[1][0], (0, resend), sends[1][1]]
    once = [list(r) for r in sends]
    once[1] = [(0, resend), sends[1][1]]
    dup = _job(tmp_path / "a", monkeypatch, caplog, twice)
    assert [pages for *_, pages, _, _, _ in dup["lines"]] == [
        "fresh", "fresh", "recycled", "recycled", "fresh", "recycled", "recycled",
    ]
    assert any("duplicate delta from w0; replacing" in m for m in caplog.messages)
    caplog.clear()
    dup_fresh = _job(tmp_path / "b", monkeypatch, caplog, twice, spares=False)
    caplog.clear()
    plain = _job(tmp_path / "c", monkeypatch, caplog, once)
    for rnd in range(ROUNDS):
        assert dup["received"][rnd] == dup_fresh["received"][rnd]
        _same_update(dup["received"][rnd], plain["received"][rnd])
    # Two spares at most, though round 1 saved three files.
    assert all(len(names) <= n for n, names in dup["spool"])
    assert [n for n, _ in dup["spool"]] == [2, 2, 2]


@pytest.mark.parametrize(
    "case", ["durable", "elastic"]
)
def test_a_job_with_a_journal_or_a_quorum_is_never_handed_a_spare(
    tmp_path, monkeypatch, caplog, case
):
    sends = _plain_sends(1)
    job = _job(tmp_path, monkeypatch, caplog, sends, **{
        "durable": {"checkpoint_dir": str(tmp_path / "ckpt")},
        "elastic": {"quorum_fraction": 1.0, "round_deadline_s": 30.0},
    }[case])
    assert len(job["overs"]) == ROUNDS
    assert all(over is None for _, _, over in job["overs"])
    assert [pages for *_, pages, _, _, _ in job["lines"]] == ["fresh"] * ROUNDS
    assert [s["attrs"]["pages"] for s in job["uploads"]] == ["fresh"] * ROUNDS
    # No spool was ever made (a durable round's cleanup does not come by it).
    assert all(names is None for _, names in job["spool"])
    if case == "durable":
        # The files the journal names hold what was sent, each round.
        assert len(job["durable"]) == ROUNDS
        for rnd, files in enumerate(job["durable"]):
            (blob,) = files.values()
            assert blob == save_bytes(sends[rnd][0][1])
        kept = sorted((tmp_path / "ckpt").rglob("delta-*.safetensors"))
        sent = {save_bytes(tree) for rnd in sends for _, tree in rnd}
        assert all(path.read_bytes() in sent for path in kept)


def test_the_spool_goes_with_a_cancelled_job(tmp_path, monkeypatch, caplog):
    job = _job(tmp_path, monkeypatch, caplog, _plain_sends(2), stop_after=1)
    # Two rounds closed, each left its two deltas as spares; when the job
    # was cancelled one of round 1's was under round 2's first delta (or
    # about to be) and the other in the spool ...
    assert [(n, len(names)) for n, names in job["spool"]] == [(2, 2), (2, 2)]
    assert job["overs"][-1][0] == 2 and job["overs"][-1][2] is not None
    assert len(job["at_cancel"]) == 2
    assert sum(name.startswith("spare/") for name in job["at_cancel"]) >= 1
    # ... and nothing of the job is left.
    assert not job["work_dir"].exists()
    assert not list(job["work"].rglob("*.safetensors"))


def test_what_the_spool_does_not_take_is_unlinked(tmp_path):
    """A round that received fewer than the spool still holds (spares that
    were never handed out) adds none; one that received more fills it up."""
    work = tmp_path / "work"
    (work / "spare").mkdir(parents=True)
    left = []
    for i in range(2):
        path = work / "spare" / f"delta-0-{i}.safetensors"
        path.write_bytes(b"s")
        left.append(path)

    def received(rnd, n):
        out = {}
        for i in range(n):
            path = work / f"delta-{rnd}-{i}.safetensors"
            path.write_bytes(b"d")
            out[f"p{i}"] = (path, 1.0)
        return out

    spares = list(left)
    ParameterServerExecutor._spool_deltas(received(1, 1), work, spares)
    assert spares == left and not list(work.glob("delta-1-*"))
    ParameterServerExecutor._spool_deltas(received(2, 3), work, spares)
    assert len(spares) == 3 and all(p.parent == work / "spare" and p.is_file() for p in spares)
    assert len(list(work.glob("delta-2-*"))) == 0
    assert sorted(os.listdir(work / "spare")) == sorted(p.name for p in spares)
    # Without a list nothing is kept.
    ParameterServerExecutor._spool_deltas(received(3, 2), work, None)
    assert not list(work.glob("delta-3-*")) and len(os.listdir(work / "spare")) == 3
