"""The worker's executor writes a round's delta over the file the last round's
left, and nobody reads a file that is being written over.

``do_update`` no longer unlinks ``delta-N.safetensors`` in ``cleanup``: it stays
under its name and round N+1's ``encode.write`` claims it (``claim_spare``: a
rename, then a link count of 1) and hands it to ``write_delta(over=...)``. The
node sends a file in the background, so ``Bridge._send`` takes a second name
for it (a hard link under ``work_dir/held/``) before it answers 202, says so
(``held``), sends from that name and drops it when the send has returned,
raised or been cancelled. The executor keeps a spare only if every send of
the file was held; a spare whose second name is still there is the sender's
and is never written over. Here: (1) the bridge's end, (2) the executor's end
through ``run_training`` behind a session that plays the node, (3) a whole job
of one worker and one parameter server, against one in which no spare is kept.
"""

from __future__ import annotations

import asyncio
import errno
import logging
import os
import re
from pathlib import Path

import numpy as np
import pytest

from hypha_tpu import aio, compress
from hypha_tpu.executor.bridge_client import Session
from hypha_tpu.ft.durable import RESYNC_KEY
from hypha_tpu.messages import Reference, Send
from hypha_tpu.network import MemoryTransport, Node
from hypha_tpu.worker import bridge as bridge_mod
from hypha_tpu.worker.bridge import Bridge
from hypha_tpu.worker.connectors import claim_spare
from test_data_pipeline import _FakeSession, _spec

SYNC_LINE = re.compile(r"sync done: round=(\d+) .* resident=\d+ pages=(recycled|fresh)$")


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _held(work: Path) -> list[Path]:
    return [p for p in (work / "held").rglob("*") if p.is_file()] if (work / "held").is_dir() else []


# ---------------------------------------------------------------------------
# (1) the node's end: a second name from the 202 until the send has ended
# ---------------------------------------------------------------------------


class _Sender:
    """Stands where the bridge's ``Connector`` stands: a send that ends when
    the test says, the way it says."""

    def __init__(self) -> None:
        self.began = asyncio.Event()
        self.end = asyncio.Event()
        self.fails = False
        self.got: list[Path] = []

    async def send(self, send, path, resource, meta=None):
        self.got.append(path)
        self.began.set()
        await self.end.wait()
        if self.fails:
            raise OSError(errno.ECONNRESET, "the peer went away")


@pytest.mark.parametrize("ending", ["returns", "raises", "is cancelled",
                                    "is cancelled before its first step", "is left to stop()"])
def test_a_send_is_held_by_a_second_name_from_the_202_until_it_has_ended(
    tmp_path, monkeypatch, ending
):
    async def main():
        node = Node(MemoryTransport().shared(), peer_id="w")
        await node.start()
        sender = _Sender()
        work = tmp_path / "work"
        bridge = Bridge(node, work, "j", "sched", connector=sender)
        sock = await bridge.start()
        delta = work / "delta-3.safetensors"
        delta.write_bytes(b"d" * 4321)
        tasks = []
        if ending == "is cancelled before its first step":
            spawn = aio.spawn

            def spawn_and_cancel(coro, **kw):
                task = spawn(coro, **kw)
                task.cancel()
                return task

            monkeypatch.setattr(bridge_mod.aio, "spawn", spawn_and_cancel)

        def client():
            with Session(str(sock)) as s:
                return s.send_resource(Send(Reference.from_peers(["ps"], "updates")), delta.name)

        assert await asyncio.to_thread(client) is True
        if ending != "is cancelled before its first step":
            await asyncio.wait_for(sender.began.wait(), 10)
            # In flight: one more name, the file's own, in a directory of the
            # send's; the sender reads by that one.
            (second,) = _held(work)
            assert second.name == delta.name and second.samefile(delta)
            assert delta.stat().st_nlink == 2 and sender.got == [second]
            # An executor that came for its spare now would leave it alone.
            assert claim_spare(delta) is None and not delta.exists()
            assert second.read_bytes() == b"d" * 4321 and second.stat().st_nlink == 1
            tasks = list(bridge._send_tasks)
        if ending == "returns":
            sender.end.set()
        elif ending == "raises":
            sender.fails = True
            sender.end.set()
        elif ending == "is cancelled":
            tasks[0].cancel()
        elif ending == "is left to stop()":
            sender.end.set()
            await bridge.stop()
        for _ in range(500):
            if not bridge._send_tasks:
                break
            await asyncio.sleep(0.01)
        assert not bridge._send_tasks
        assert _held(work) == [] and list((work / "held").iterdir()) == []
        if ending == "is cancelled before its first step":
            assert sender.got == [] and delta.stat().st_nlink == 1
        await bridge.stop()
        await node.stop()

    run(main())


@pytest.mark.parametrize("why", ["EPERM", "EXDEV", "EMLINK"])
def test_where_a_link_is_refused_the_send_reads_the_file_itself_and_the_202_says_so(
    tmp_path, monkeypatch, why
):
    def refuse(src, dst, **kw):
        raise OSError(getattr(errno, why), os.strerror(getattr(errno, why)))

    async def main():
        node = Node(MemoryTransport().shared(), peer_id="w")
        await node.start()
        sender = _Sender()
        sender.end.set()
        work = tmp_path / "work"
        bridge = Bridge(node, work, "j", "sched", connector=sender)
        sock = await bridge.start()
        delta = work / "delta-0.safetensors"
        delta.write_bytes(b"d" * 99)
        monkeypatch.setattr(bridge_mod.os, "link", refuse)

        def client():
            with Session(str(sock)) as s:
                return s.send_resource(Send(Reference.from_peers(["ps"], "updates")), delta.name)

        assert await asyncio.to_thread(client) is False
        await asyncio.wait_for(sender.began.wait(), 10)
        assert sender.got == [delta] and delta.stat().st_nlink == 1
        assert _held(work) == [] and list((work / "held").iterdir()) == []
        await bridge.stop()
        await node.stop()

    run(main())


@pytest.mark.parametrize("sends", [1, 3])
def test_a_held_send_carries_the_files_own_name_and_bytes_to_the_peer(tmp_path, sends):
    """Through the real ``Connector``: the push header's ``name`` is the
    delta's, whatever directory the second name lies in; several sends of
    one file (a re-send after a PS restart) are held each by a name of its
    own."""

    async def main():
        hub = MemoryTransport()
        a, b = Node(hub.shared(), peer_id="a"), Node(hub.shared(), peer_id="b")
        await a.start()
        await b.start()
        a.add_peer_addr("b", b.listen_addrs[0])
        work = tmp_path / "wa"
        bridge = Bridge(a, work, "j", "sched")
        sock = await bridge.start()
        delta = work / "delta-7.safetensors"
        delta.write_bytes(bytes(range(256)) * 40)

        def client():
            with Session(str(sock)) as s:
                return [s.send_resource(Send(Reference.from_peers(["b"], "updates")),
                                        delta.name, meta={"round": 7}) for _ in range(sends)]

        assert await asyncio.to_thread(client) == [True] * sends
        for _ in range(sends):
            push = await b.next_push(timeout=10)
            assert push.resource["name"] == delta.name and push.resource["round"] == 7
            assert await push.read_all() == delta.read_bytes()
        await bridge.stop()
        assert _held(work) == [] and delta.stat().st_nlink == 1
        await a.stop()
        await b.stop()

    run(main())


# ---------------------------------------------------------------------------
# (2) the executor's end: run_training behind a session that plays the node
# ---------------------------------------------------------------------------


class _NodeSession(_FakeSession):
    """``_FakeSession`` whose ``send_resource`` does what the bridge does: a
    second name for the file, kept as long as the send
    of that round's delta is open (``open_for``: how many syncs more), and the
    answer a 202 gives. On record:
    every delta as it was when it was sent, the names it lay under, what was
    in the work directory at that moment."""

    def __init__(self, work_dir, rounds, *, held=lambda rnd: True, open_for=lambda rnd: 0,
                 resend_in=(), resend_held=True):
        super().__init__(work_dir, rounds=rounds)
        self.held, self.open_for = held, open_for
        self.resend_in, self.resend_held = set(resend_in), resend_held
        self.sent: dict[int, dict] = {}
        self.names: dict[int, Path] = {}
        self.resent: list[int] = []
        self.whole_at_release: dict[int, bool] = {}

    def send_status(self, progress):
        from hypha_tpu.messages import ProgressKind

        if progress.kind == ProgressKind.UPDATE:
            # A sync begins: the sends that have ended by now drop their names,
            # each having read to the end what it was handed.
            for rnd, name in list(self.names.items()):
                if self.rounds_done - rnd > self.open_for(rnd):
                    self.whole_at_release[rnd] = name.read_bytes() == self.sent[rnd]["bytes"]
                    name.unlink()
                    del self.names[rnd]
        return super().send_status(progress)

    def send_resource(self, send, path, resource="updates", meta=None):
        rnd = int(meta["round"])
        file = self.work_dir / path
        if rnd in self.sent:  # a re-send after a resync: no second update
            self.resent.append(rnd)
            return self.resend_held
        self.sent[rnd] = {
            "bytes": file.read_bytes(), "inode": file.stat().st_ino,
            "deltas": sorted(p.name for p in self.work_dir.glob("delta-*")),
        }
        if rnd in self.resend_in:
            self.events.put({"path": "incoming/nothing", "meta": {RESYNC_KEY: True}, "size": 0})
        super().send_resource(send, path, resource, meta)
        if not self.held(rnd):
            return False
        self.names[rnd] = self.work_dir / f"second-name-{rnd}"
        os.link(file, self.names[rnd])
        return True


@pytest.fixture
def sync_lines():
    lines: list[str] = []

    class _Lines(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    log = logging.getLogger("hypha.executor.training")
    handler, level = _Lines(logging.INFO), log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    yield lambda: [(int(m.group(1)), m.group(2)) for m in map(SYNC_LINE.search, lines) if m]
    log.removeHandler(handler)
    log.setLevel(level)


ROUNDS = 4


def _train(tmp_path, name, **session_kw):
    from hypha_tpu.executor.training import run_training

    work = tmp_path / name
    work.mkdir()
    session = _NodeSession(work, ROUNDS, **session_kw)
    result = run_training(session, work, _spec(work), max_batches=64)
    assert result.rounds == ROUNDS and sorted(session.sent) == list(range(ROUNDS))
    return session, work


def test_every_round_after_the_first_writes_over_the_file_the_last_one_left(tmp_path, sync_lines):
    session, work = _train(tmp_path, "held")
    assert sync_lines() == [(0, "fresh")] + [(r, "recycled") for r in range(1, ROUNDS)]
    # One inode under a new name every round, and never two delta files.
    assert len({s["inode"] for s in session.sent.values()}) == 1
    assert [s["deltas"] for s in session.sent.values()] == [
        [f"delta-{r}.safetensors"] for r in range(ROUNDS)
    ]
    # The last one stays, the next round's spare, until the job's directory goes.
    assert sorted(p.name for p in work.glob("delta-*")) == [f"delta-{ROUNDS - 1}.safetensors"]


def test_what_is_sent_is_bit_for_bit_what_an_executor_that_keeps_no_spare_sends(
    tmp_path, sync_lines
):
    held, _ = _train(tmp_path, "held")
    lines = sync_lines()
    never, work = _train(tmp_path, "never", held=lambda rnd: False)
    assert sync_lines()[len(lines):] == [(r, "fresh") for r in range(ROUNDS)]
    for rnd in range(ROUNDS):
        assert held.sent[rnd]["bytes"] == never.sent[rnd]["bytes"], rnd
    assert any(held.sent[r]["bytes"] != held.sent[r - 1]["bytes"] for r in range(1, ROUNDS))
    # A send that was not held means no spare: the file goes in ``cleanup`` as it did.
    assert [s["deltas"] for s in never.sent.values()] == [
        [f"delta-{r}.safetensors"] for r in range(ROUNDS)
    ]
    assert list(work.glob("delta-*")) == []


def test_a_spare_whose_second_name_a_sender_still_holds_is_never_written_over(
    tmp_path, sync_lines
):
    """Round 0's send stays open through rounds 1 and 2 (a push that is being
    retried): round 1 goes fresh, and what the sender reads to the end is
    round 0's delta, whole. Round 1's send has ended by round 2, which writes
    over round 1's file."""
    session, work = _train(tmp_path, "open", open_for=lambda rnd: 2 if rnd == 0 else 0)
    assert sync_lines() == [(0, "fresh"), (1, "fresh"), (2, "recycled"), (3, "recycled")]
    inodes = [session.sent[r]["inode"] for r in range(ROUNDS)]
    assert inodes[1] != inodes[0] and inodes[2] == inodes[1] == inodes[3]
    # Read by the sender's own name after rounds 1 and 2 had written.
    assert session.whole_at_release == {0: True, 1: True, 2: True}


def test_a_resend_that_was_not_held_means_no_spare(tmp_path, sync_lines):
    session, work = _train(tmp_path, "resend", resend_in={1}, resend_held=False)
    assert session.resent == [1]
    # Round 1's file was sent twice, once unheld: round 2 does not write over it.
    assert sync_lines() == [(0, "fresh"), (1, "recycled"), (2, "fresh"), (3, "recycled")]
    assert session.sent[2]["deltas"] == ["delta-2.safetensors"]


def test_a_resend_that_was_held_keeps_the_spare(tmp_path, sync_lines):
    session, _ = _train(tmp_path, "resend", resend_in={1, 2})
    assert session.resent == [1, 2]
    assert sync_lines() == [(0, "fresh")] + [(r, "recycled") for r in range(1, ROUNDS)]


def test_a_session_that_says_nothing_of_holding_keeps_every_round_as_it_was(tmp_path, sync_lines):
    """The sessions the other tests drive ``run_training`` with answer None."""
    from hypha_tpu.executor.training import run_training

    work = tmp_path / "plain"
    work.mkdir()
    result = run_training(_FakeSession(work, rounds=3), work, _spec(work), max_batches=64)
    assert result.rounds == 3
    assert sync_lines() == [(r, "fresh") for r in range(3)]
    assert list(work.glob("delta-*")) == []


# ---------------------------------------------------------------------------
# (3) through a whole job: one worker, one parameter server, three rounds
# ---------------------------------------------------------------------------

JOB_ROUNDS = 3


def _job(tmp, spares: bool):
    """test_round_spans' job at three rounds, with every delta the parameter
    server folds on record (decoded from the file it is handed, before the
    fold) and every ``sync done:`` line. ``spares=False`` is a bridge client
    that never hears ``held``: the executor unlinks every delta in
    ``cleanup``, as the parent commit did."""
    import test_round_spans as cluster
    from hypha_tpu.stream.accum import RoundAccum
    from hypha_tpu.worker import arbiter

    record = {"folded": []}
    fold = RoundAccum.fold
    send_resource = Session.send_resource

    def spy_fold(self, path, *args, **kw):
        record["folded"].append({k: np.array(v) for k, v in compress.read_delta(path).items()})
        return fold(self, path, *args, **kw)

    def never_held(self, *args, **kw):
        send_resource(self, *args, **kw)
        return False

    handler = cluster._Lines()
    log = logging.getLogger("hypha.executor.training")
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(arbiter, "LEASE_TIMEOUT_S", cluster.LEASE_S)
            mp.setattr(cluster, "ROUNDS", JOB_ROUNDS)
            mp.setattr(RoundAccum, "fold", spy_fold)
            if not spares:
                mp.setattr(Session, "send_resource", never_held)
            result = cluster.run(cluster._job(tmp))
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
    assert result.rounds == JOB_ROUNDS
    record["lines"] = [(int(m.group(1)), m.group(2))
                       for m in map(SYNC_LINE.search, handler.lines) if m]
    record["left"] = [p for name in ("w0", "ps") for p in (tmp / name).rglob("*") if p.is_file()]
    return record


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    return {
        "spares": _job(tmp_path_factory.mktemp("spares"), True),
        "none": _job(tmp_path_factory.mktemp("none"), False),
    }


def test_round_0_writes_fresh_pages_and_every_later_round_the_last_ones(jobs):
    assert jobs["spares"]["lines"] == [
        (rnd, "fresh" if rnd == 0 else "recycled") for rnd in range(JOB_ROUNDS)
    ]
    assert jobs["none"]["lines"] == [(rnd, "fresh") for rnd in range(JOB_ROUNDS)]


@pytest.mark.parametrize("rnd", range(JOB_ROUNDS))
def test_the_ps_folds_bit_for_bit_what_it_folds_when_no_spare_is_ever_kept(jobs, rnd):
    assert len(jobs["spares"]["folded"]) == len(jobs["none"]["folded"]) == JOB_ROUNDS
    got, want = jobs["spares"]["folded"][rnd], jobs["none"]["folded"][rnd]
    assert list(got) == list(want) and len(want) > 3
    for key in want:
        assert got[key].dtype == want[key].dtype == np.float32
        assert got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes(), key
    if rnd:
        before = jobs["spares"]["folded"][rnd - 1]
        assert any(before[key].tobytes() != got[key].tobytes() for key in got)


@pytest.mark.parametrize("which", ["spares", "none"])
def test_nothing_of_the_job_is_left_under_either_roles_root(jobs, which):
    assert jobs[which]["left"] == []
