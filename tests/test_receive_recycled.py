"""The worker's node saves a broadcast over the file the last one left.

``Connector._save`` gives every push that has landed a second name, a hard
link under ``<dest_dir>/spare/`` named for the stream (sender and resource
tag), before a consumer hears of the file. The consumer reads it and unlinks
it; the next push of the stream finds the spare with a link count of 1,
takes it by a rename and hands it to ``PushStream.save_to(over=...)``: the
payload lands in pages that exist, over plain TCP through the drain thread.
A spare whose other name is still there belongs to a reader and is never
written over. Here: each of those, the ways a push can end, what a refused
link costs (nothing), how many spares there are, that they go with the job,
and through a whole job that a worker merges bit for bit what it merges when
its node is never given a spare.
"""

from __future__ import annotations

import asyncio
import errno
import logging
import os
import re
import shutil
import socket
import struct
import sys
import tempfile
import textwrap
from pathlib import Path

import pytest

# The harness's tests' helpers, and one fixture of theirs (section 5).
sys.path.append(str(Path(__file__).resolve().parent / "perfbench"))

from hypha_tpu.messages import Receive, Reference
from hypha_tpu.network import node as node_mod
from hypha_tpu.network.node import ACCEPT_LIMIT
from hypha_tpu.worker import connectors
from hypha_tpu.worker.connectors import Connector
from hypha_tpu.worker.process_executor import ProcessExecutor
from test_bridge import _train_spec
from test_journey_metrics import rehearsed  # noqa: F401  (the fixture: one more traced tiny run)
from test_upload_recycled import _Breaks, _pair, _payload, run

RECV = Receive(Reference.from_peers(["a"], "results"))
RECEIVED_LINE = re.compile(
    r"push received: round=(\S+) peer=(\S+) bytes=(\d+) pages=(recycled|fresh) "
    r"path=(thread|loop) wall_s=([\d.]+) read_s=([\d.]+) write_s=([\d.]+)$"
)
# More than two 4 MiB pieces, and not a whole number of them.
SIZE = 9_000_001


def _header(rnd: int, resource: str = "results") -> dict:
    # A file is saved under its sender's and its own name, whatever its tag.
    return {"resource": resource, "name": f"{resource}-{rnd}.safetensors", "round": rnd}


async def _land(a, conn, dest_dir, rnd, data, resource="results"):
    """One push of the stream through a receive of its own, as
    ``await_round_update`` opens one a round."""
    recv = Receive(Reference.from_peers(["a"], resource))
    gen = conn.receive(recv, dest_dir)
    sending = asyncio.create_task(a.push("b", _header(rnd, resource), data))
    try:
        rf = await asyncio.wait_for(anext(gen), 20)
        await sending
    finally:
        await gen.aclose()
    return rf


def _lines(caplog) -> list[tuple[str, str]]:
    """(pages, path) of every ``push received:`` line so far."""
    found = [RECEIVED_LINE.search(m) for m in caplog.messages]
    return [(m.group(4), m.group(5)) for m in found if m]


def _spares(dest_dir: Path) -> list[Path]:
    d = dest_dir / "spare"
    return sorted(d.iterdir()) if d.is_dir() else []


@pytest.fixture
def lines(caplog):
    caplog.set_level(logging.INFO, logger="hypha.worker.connector")
    return lambda: _lines(caplog)


# ---------------------------------------------------------------------------
# (1) one stream, push after push
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["tcp", "memory"])
def test_a_push_that_landed_has_a_second_name_under_spare(tmp_path, lines, transport):
    data = _payload(SIZE)
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair(transport)
        rf = await _land(a, Connector(b), dest_dir, 0, data)
        for node in (a, b):
            await node.stop()
        return rf

    rf = run(main())
    assert rf.path.parent == dest_dir and rf.path.read_bytes() == data
    (spare,) = _spares(dest_dir)
    assert spare.stat().st_ino == rf.path.stat().st_ino
    assert rf.path.stat().st_nlink == 2
    # The first push of a stream has nothing to land in.
    assert lines() == [("fresh", "loop")]


@pytest.mark.parametrize("transport", ["tcp", "memory"])
@pytest.mark.parametrize("second", ["shorter", "longer", "equal"])
def test_once_the_consumer_has_unlinked_the_next_push_lands_in_the_same_pages(
    tmp_path, lines, transport, second
):
    first = _payload(SIZE)
    data = _payload(
        {"shorter": SIZE // 3, "longer": 2 * SIZE + 5, "equal": SIZE}[second], salt=5
    )
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair(transport)
        conn = Connector(b)
        rf0 = await _land(a, conn, dest_dir, 0, first)
        inode = rf0.path.stat().st_ino
        rf0.path.unlink()  # the consumer, after its last read
        (spare,) = _spares(dest_dir)
        assert spare.stat().st_nlink == 1
        rf1 = await _land(a, conn, dest_dir, 1, data)
        assert b._push_sem._value == ACCEPT_LIMIT
        for node in (a, b):
            await node.stop()
        return inode, rf0, rf1

    inode, rf0, rf1 = run(main())
    # The drain thread where the stream is a raw socket, the loop elsewhere.
    assert lines() == [
        ("fresh", "loop"), ("recycled", "thread" if transport == "tcp" else "loop")
    ]
    assert rf1.path != rf0.path and not rf0.path.exists()
    # The payload byte for byte and nothing of the spare, whatever its length.
    assert rf1.size == len(data) == rf1.path.stat().st_size
    assert rf1.path.read_bytes() == data
    assert rf1.path.stat().st_ino == inode
    # ... and it is the stream's one spare again.
    (spare,) = _spares(dest_dir)
    assert spare.stat().st_ino == inode and spare.stat().st_nlink == 2


@pytest.mark.parametrize("transport", ["tcp", "memory"])
def test_a_file_its_consumer_still_names_is_never_written_over(tmp_path, lines, transport):
    """A re-broadcast that arrives while the merge still reads, a slice that
    is kept: the link count is 2, the push goes into a fresh file, and a
    reader that holds the old one open reads the old bytes to the end."""
    old, new = _payload(SIZE, salt=1), _payload(SIZE + 77, salt=2)
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair(transport)
        conn = Connector(b)
        rf0 = await _land(a, conn, dest_dir, 0, old)
        fd = os.open(rf0.path, os.O_RDONLY)
        head = os.pread(fd, 1 << 20, 0)
        rf1 = await _land(a, conn, dest_dir, 1, new)
        rest = os.pread(fd, SIZE, 1 << 20)
        os.close(fd)
        for node in (a, b):
            await node.stop()
        return rf0, rf1, head + rest

    rf0, rf1, read = run(main())
    assert lines() == [("fresh", "loop"), ("fresh", "loop")]
    assert read == old and rf0.path.read_bytes() == old
    assert rf1.path.read_bytes() == new
    assert rf1.path.stat().st_ino != rf0.path.stat().st_ino
    # The old file is its consumer's alone now; the new one is the spare.
    assert rf0.path.stat().st_nlink == 1
    (spare,) = _spares(dest_dir)
    assert spare.stat().st_ino == rf1.path.stat().st_ino
    # Once the consumer lets go of both, the stream goes on recycling.
    rf0.path.unlink()
    rf1.path.unlink()
    assert spare.stat().st_nlink == 1


def test_a_stream_never_holds_more_than_one_spare(tmp_path, lines):
    """Over N rounds, with a consumer that unlinks at once, late, or keeps
    the file over a push: one name a stream under ``spare/``, and no name a
    save claimed is left behind. A second resource tag is a second stream."""
    dest_dir = tmp_path / "incoming"
    rounds = 6
    keeps = {2, 3}  # rounds whose file the consumer still names at the next push

    async def main():
        a, b = await _pair("tcp")
        conn = Connector(b)
        held = []
        for rnd in range(rounds):
            rf = await _land(a, conn, dest_dir, rnd, _payload(200_000 + 999 * rnd, salt=rnd))
            assert rf.path.read_bytes() == _payload(200_000 + 999 * rnd, salt=rnd)
            assert len(_spares(dest_dir)) == (1 if rnd == 0 else 2)
            for path in held:
                path.unlink()
            held = [rf.path] if rnd in keeps else []
            if not held:
                rf.path.unlink()
            other = await _land(a, conn, dest_dir, rnd, b"o" * 5000, resource="other")
            other.path.unlink()
            assert len(_spares(dest_dir)) == 2
        for node in (a, b):
            await node.stop()

    run(main())
    got = [pages for pages, _ in lines()][::2]
    # Round 3 finds round 2's file still named, round 4 round 3's.
    assert got == ["fresh", "recycled", "recycled", "fresh", "fresh", "recycled"]
    assert [pages for pages, _ in lines()][1::2] == ["fresh"] + ["recycled"] * (rounds - 1)
    assert all(p.suffix == ".bin" and ".over" not in p.name for p in _spares(dest_dir))
    assert [p for p in dest_dir.iterdir() if p.is_file()] == []


# ---------------------------------------------------------------------------
# (2) saves that meet, pushes that end early, links that are refused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["tcp", "memory"])
def test_of_two_saves_at_once_on_one_stream_one_takes_the_spare(tmp_path, lines, transport):
    """Two jobs or two open receives: the rename gives the spare to one save,
    the other goes fresh, and neither file holds a byte of the other's."""
    first, one, two = _payload(SIZE), _payload(SIZE - 11, salt=3), _payload(SIZE + 11, salt=4)
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair(transport)
        conn = Connector(b)
        rf0 = await _land(a, conn, dest_dir, 0, first)
        inode = rf0.path.stat().st_ino
        rf0.path.unlink()
        sending = [
            asyncio.create_task(a.push("b", _header(rnd), data))
            for rnd, data in ((1, one), (2, two))
        ]
        pushes = [await b.next_push(timeout=10) for _ in sending]
        dests = [dest_dir / f"{p.resource['name']}.bin" for p in pushes]
        sizes = await asyncio.gather(*(
            conn._save(p, d, "results", p.resource) for p, d in zip(pushes, dests)
        ))
        await asyncio.gather(*sending)
        assert b._push_sem._value == ACCEPT_LIMIT
        for node in (a, b):
            await node.stop()
        return inode, pushes, dests, sizes

    inode, pushes, dests, sizes = run(main())
    assert sorted(pages for pages, _ in lines()[1:]) == ["fresh", "recycled"]
    want = {1: one, 2: two}
    for push, dest, size in zip(pushes, dests, sizes):
        assert dest.read_bytes() == want[push.resource["round"]] and size == dest.stat().st_size
    assert [d.stat().st_ino == inode for d in dests].count(True) == 1
    (spare,) = _spares(dest_dir)
    assert spare.stat().st_ino in {d.stat().st_ino for d in dests}


@pytest.mark.parametrize("transport", ["tcp", "memory"])
@pytest.mark.parametrize("how", ["reset", "cancelled"])
def test_a_push_that_ends_early_over_a_spare_leaves_neither_file_nor_spare(
    tmp_path, lines, transport, how
):
    """The head would be this push's and the tail the last round's: no name
    is left on such a file, the accept slot is free again, and the stream's
    next push lands whole, in a fresh file."""
    from hypha_tpu import messages

    first, later = _payload(3_000_000), _payload(1_000_000, salt=8)
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair(transport)
        conn = Connector(b)
        rf0 = await _land(a, conn, dest_dir, 0, first)
        rf0.path.unlink()

        async def dribble():
            yield b"x" * 300_000
            await asyncio.sleep(3600)  # stalls until the receiver gives up

        gen = conn.receive(RECV, dest_dir)
        if how == "reset" and transport == "tcp":
            stream = await a._stream_to("b", node_mod.PROTOCOL_PUSH)
            await stream.write_frame(messages.encode(_header(1)))
            await stream.write(b"x" * 300_000)
            sending = asyncio.create_task(asyncio.sleep(0))
        else:
            sending = asyncio.create_task(a.push("b", _header(1), dribble()))
        if how == "reset" and transport == "memory":
            # No socket to reset: the stream itself loses its sender.
            push = await b.next_push(timeout=10)
            push.stream = _Breaks(push.stream, after=100_000)
            saving = asyncio.create_task(
                conn._save(push, dest_dir / "update-1.bin", "results", push.resource)
            )
        else:
            saving = asyncio.create_task(anext(gen))
        await asyncio.sleep(0.3)
        if how == "reset":
            if transport == "tcp":
                # Linger 0: the close is a reset, not a clean end.
                stream._writer.get_extra_info("socket").setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0)
                )
                stream._writer.transport.abort()
            with pytest.raises(ConnectionError):
                await saving
        else:
            # Mid-push the spare is under the save's own name and no other.
            (claimed,) = _spares(dest_dir)
            assert claimed.name.endswith(".over")
            saving.cancel()
            with pytest.raises(asyncio.CancelledError):
                await saving
        try:
            await gen.aclose()
        except RuntimeError:
            pass
        sending.cancel()
        assert b._push_sem._value == ACCEPT_LIMIT
        assert _spares(dest_dir) == []
        assert [p for p in dest_dir.iterdir() if p.is_file()] == []
        rf2 = await _land(a, conn, dest_dir, 2, later)
        for node in (a, b):
            await node.stop()
        return rf2

    rf2 = run(main())
    assert rf2.path.read_bytes() == later
    assert lines() == [("fresh", "loop"), ("fresh", "loop")]
    assert len(_spares(dest_dir)) == 1


@pytest.mark.parametrize("why", ["EPERM", "EXDEV", "EMLINK"])
def test_where_a_link_is_refused_every_push_is_saved_as_it_always_was(
    tmp_path, lines, monkeypatch, why
):
    def refused(src, dst, **kw):
        raise OSError(getattr(errno, why), os.strerror(getattr(errno, why)))

    monkeypatch.setattr(connectors.os, "link", refused)
    dest_dir = tmp_path / "incoming"

    async def main():
        a, b = await _pair("tcp")
        conn = Connector(b)
        for rnd in range(3):
            data = _payload(500_000 + rnd, salt=rnd)
            rf = await _land(a, conn, dest_dir, rnd, data)
            assert rf.path.read_bytes() == data and rf.path.stat().st_nlink == 1
            rf.path.unlink()
            assert _spares(dest_dir) == []
        for node in (a, b):
            await node.stop()

    run(main())
    assert lines() == [("fresh", "loop")] * 3


def test_a_spare_on_another_inode_than_its_name_says_is_not_trusted(tmp_path, lines):
    """Whatever lies under the spare's name with more names than one (here a
    link the test makes to a file of its own) is left alone."""
    dest_dir = tmp_path / "incoming"
    kept = tmp_path / "kept.bin"
    kept.write_bytes(b"k" * 70_000)

    async def main():
        a, b = await _pair("tcp")
        conn = Connector(b)
        rf0 = await _land(a, conn, dest_dir, 0, _payload(60_000))
        (spare,) = _spares(dest_dir)
        rf0.path.unlink()
        spare.unlink()
        os.link(kept, spare)
        rf1 = await _land(a, conn, dest_dir, 1, _payload(50_000, salt=1))
        for node in (a, b):
            await node.stop()
        return rf1

    rf1 = run(main())
    assert kept.read_bytes() == b"k" * 70_000 and kept.stat().st_nlink == 1
    assert rf1.path.read_bytes() == _payload(50_000, salt=1)
    assert lines() == [("fresh", "loop"), ("fresh", "loop")]


def test_a_serving_follower_cancelled_mid_read_keeps_the_files_name_until_the_read_ends(
    tmp_path, monkeypatch
):
    """The one consumer whose unlink could come before its last read: the
    weight subscriber decodes a wire in a thread, and used to unlink it on
    the way out of a cancellation while that thread still read. The name now
    goes when the read has ended, so the link count says 2 until then and
    the stream's next push cannot be written over what is being read."""
    import threading

    from hypha_tpu.messages import WeightFollow
    from hypha_tpu.serving import weight_stream
    from hypha_tpu.serving.weight_stream import WeightSubscriber

    reading, release = threading.Event(), threading.Event()
    seen = {}

    def slow_read(path):
        seen["path"] = Path(path)
        reading.set()
        assert release.wait(20)
        seen["nlink_at_the_reads_end"] = os.stat(path).st_nlink
        return {}

    monkeypatch.setattr(weight_stream, "read_delta", slow_read)

    async def main():
        a, b = await _pair("tcp")
        sub = WeightSubscriber(b, WeightFollow(results=RECV), pool=None, work_dir=tmp_path / "w")
        sub.start()
        await asyncio.sleep(0.05)
        await a.push("b", _header(1), _payload(100_000))
        assert await asyncio.to_thread(reading.wait, 20)
        stopping = asyncio.create_task(sub.stop())
        await asyncio.sleep(0.3)
        named = seen["path"].exists(), seen["path"].stat().st_nlink
        release.set()
        await stopping
        for _ in range(200):
            if not seen["path"].exists():
                break
            await asyncio.sleep(0.01)
        for node in (a, b):
            await node.stop()
        return named

    assert run(main()) == (True, 2)
    assert seen["nlink_at_the_reads_end"] == 2
    assert not seen["path"].exists()
    (spare,) = _spares(tmp_path / "w")
    assert spare.stat().st_nlink == 1


# ---------------------------------------------------------------------------
# (3) the spares go with the job
# ---------------------------------------------------------------------------

RECEIVER_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from hypha_tpu.executor.bridge_client import Session
    from hypha_tpu.messages import Receive, Reference

    with Session(os.environ["SOCKET_PATH"]) as s:
        with s.receive(Receive(Reference.from_peers(["ps"], "updates"))) as events:
            event = next(events)
    path = os.path.join(os.environ["WORK_DIR"], event["path"])
    assert os.stat(path).st_nlink == 2, "the node keeps a second name"
    os.unlink(path)
    open({mark!r}, "w").write(os.environ["WORK_DIR"])
    time.sleep({sleep})
    """
)


@pytest.fixture
def short_root():
    """A work root short enough for the bridge's socket (107 bytes of path)."""
    root = Path(tempfile.mkdtemp(prefix="rr-"))
    yield root
    shutil.rmtree(root, ignore_errors=True)


@pytest.mark.parametrize("ending", ["completed", "cancelled"])
def test_the_spares_go_with_the_jobs_work_directory(tmp_path, short_root, ending):
    from hypha_tpu.network import MemoryTransport, Node

    mark = tmp_path / "mark"
    script = tmp_path / "receiver.py"
    script.write_text(RECEIVER_SCRIPT.format(
        repo=str(Path(__file__).resolve().parent.parent), mark=str(mark),
        sleep=0 if ending == "completed" else 300,
    ))

    async def main():
        hub = MemoryTransport()
        worker, ps = Node(hub.shared(), peer_id="worker"), Node(hub.shared(), peer_id="ps")
        await worker.start()
        await ps.start()
        ps.add_peer_addr("worker", worker.listen_addrs[0])
        pe = ProcessExecutor(
            node=worker, cmd=sys.executable, args=[str(script)], work_root=short_root
        )
        execution = await pe.execute("rj", _train_spec("rj"), "sched")
        await ps.push("worker", {"resource": "updates", "name": "update-0"}, b"u" * 123_456)
        seen = None
        for _ in range(3000):
            if mark.exists() and mark.read_text():
                work_dir = Path(mark.read_text())
                seen = seen or [
                    (p.stat().st_size, p.stat().st_nlink)
                    for p in (work_dir / "incoming" / "spare").iterdir()
                ] if work_dir.is_dir() else seen
                if ending == "cancelled":
                    await execution.cancel()
                break
            await asyncio.sleep(0.01)
        status = await asyncio.wait_for(execution.wait(), 30)
        assert status.state == ending, status
        await worker.stop()
        await ps.stop()
        return seen

    seen = run(main())
    if ending == "cancelled":
        # While the job lived its node held the one update it had been sent.
        assert seen == [(123_456, 1)]
    assert list(short_root.rglob("*")) == []


# ---------------------------------------------------------------------------
# (4) through a whole job: one worker, one parameter server, three rounds
# ---------------------------------------------------------------------------

JOB_ROUNDS = 3


def _worker_job(tmp, spares: bool):
    """test_round_spans' job at three rounds, with every merge of the worker
    on record (a copy of what came out, made on the device as the round's
    anchor is) and every ``push received:`` line. ``spares=False`` is a node
    that is never given a spare: the parent commit's receive."""
    import jax
    import jax.numpy as jnp

    import test_round_spans as cluster
    from hypha_tpu.executor import training
    from hypha_tpu.worker import arbiter

    record = {"merged": []}
    merge_update = training.merge_update

    def spy_merge(params, update):
        out = merge_update(params, update)
        record["merged"].append(jax.tree.map(jnp.copy, out))
        return out

    handler = cluster._Lines()
    log = logging.getLogger("hypha.worker.connector")
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(arbiter, "LEASE_TIMEOUT_S", cluster.LEASE_S)
            mp.setattr(cluster, "ROUNDS", JOB_ROUNDS)
            mp.setattr(training, "merge_update", spy_merge)
            if not spares:
                mp.setattr(connectors, "claim_spare", lambda spare: None)
                mp.setattr(connectors, "_keep_spare", lambda dest, spare: None)
            result = cluster.run(cluster._job(tmp))
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
    assert result.rounds == JOB_ROUNDS
    record["lines"] = [m.groups() for m in map(RECEIVED_LINE.search, handler.lines) if m]
    record["left"] = [p for p in (tmp / "w0").rglob("*") if p.is_file()]
    return record


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    return {
        "spares": _worker_job(tmp_path_factory.mktemp("spares"), True),
        "none": _worker_job(tmp_path_factory.mktemp("none"), False),
    }


def test_round_0_lands_in_fresh_pages_and_every_later_round_in_the_last_ones(jobs):
    lines = jobs["spares"]["lines"]
    assert [(rnd, pages) for rnd, _, _, pages, *_ in lines] == [
        (str(rnd), "fresh" if rnd == 0 else "recycled") for rnd in range(JOB_ROUNDS)
    ]
    # The memory fabric has no socket to hand to a thread.
    assert {path for *_, path, _, _, _ in lines} == {"loop"}
    assert len({nbytes for _, _, nbytes, *_ in lines}) == 1
    assert [pages for _, _, _, pages, *_ in jobs["none"]["lines"]] == ["fresh"] * JOB_ROUNDS


@pytest.mark.parametrize("rnd", range(JOB_ROUNDS))
def test_every_round_merges_bit_for_bit_what_a_node_without_spares_merges(jobs, rnd):
    import jax

    from hypha_tpu.executor.serialization import flatten_tree

    assert len(jobs["spares"]["merged"]) == len(jobs["none"]["merged"]) == JOB_ROUNDS
    got = flatten_tree(jax.device_get(jobs["spares"]["merged"][rnd]))
    want = flatten_tree(jax.device_get(jobs["none"]["merged"][rnd]))
    assert list(got) == list(want)
    for key in want:
        assert got[key].dtype == want[key].dtype and got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes(), key
    if rnd:
        before = flatten_tree(jax.device_get(jobs["spares"]["merged"][rnd - 1]))
        assert any(before[key].tobytes() != got[key].tobytes() for key in got)


@pytest.mark.parametrize("which", ["spares", "none"])
def test_nothing_of_the_job_is_left_under_the_workers_root(jobs, which):
    assert jobs[which]["left"] == []


# ---------------------------------------------------------------------------
# (5) through the CLI's roles over loopback TCP: the harness's tiny rehearsal
# ---------------------------------------------------------------------------


def test_the_rehearsals_lines_and_spans_say_fresh_once_and_recycled_ever_after(rehearsed):  # noqa: F811
    """``tests/perfbench/test_journey_metrics.py`` holds one line that was
    true of every program until this one: every round's ``push received:``
    reads ``pages=fresh path=loop``. A PR may not edit a file the benchmark
    has, so ``tests/conftest.py`` expects that test to fail at that line
    (and at no other); every assertion of it is held here again, over one
    more run of the same rehearsal, with the line as it reads now."""
    _, spans, w0 = rehearsed
    rounds = {s["attrs"]["round"] for s in spans if s["name"] == "merge"}
    assert len(rounds) >= 2
    for name, node in (("receive", "w0"), ("send", "w0"), ("upload", "ps"), ("broadcast", "ps")):
        found = {s["attrs"]["round"] for s in spans if s["name"] == name and s["node"] == node}
        assert rounds <= found, name
    assert all("pushes" in s["attrs"] for s in spans if s["name"] == "broadcast")
    got = re.findall(r"push received: round=(\d+) peer=ps bytes=\d+ pages=(\w+) path=(\w+)", w0)
    assert len(got) >= len(rounds)
    assert got[0] == ("0", "fresh", "loop")
    assert {(pages, path) for _, pages, path in got[1:]} == {("recycled", "thread")}
    received = sorted(
        (s["attrs"]["round"], s["attrs"]["pages"], s["attrs"]["path"])
        for s in spans if s["name"] == "receive" and s["node"] == "w0" and "pages" in s["attrs"]
    )
    assert received[0] == (0, "fresh", "loop")
    assert {(pages, path) for _, pages, path in received[1:]} == {("recycled", "thread")}
    # The PS's end of the other journey, as since PR 41.
    uploads = sorted(
        (s["attrs"]["round"], s["attrs"]["pages"])
        for s in spans if s["name"] == "upload" and s["node"] == "ps" and "pages" in s["attrs"]
    )
    assert uploads[0] == (0, "fresh") and {pages for _, pages in uploads[1:]} == {"recycled"}
    marks = [s for s in spans if s["name"] == "clock_mark"]
    assert len(marks) >= 2 * len(rounds) and all(s["node"] == "w0" for s in marks)
