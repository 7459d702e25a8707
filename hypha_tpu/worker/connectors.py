"""Pluggable data-plane connectors: how job artifacts move.

Reference: crates/worker/src/connector/mod.rs — ``FetchConnector`` /
``SendConnector`` / ``ReceiveConnector`` traits (:65-87) with built-ins:

  * ``HttpHfFetcher``      — http(s) URI streaming + HuggingFace Hub
    downloads (:224-302); here also ``file://`` for local/offline runs;
  * ``PeerStreamPushConnector`` — send/receive tensor files over fabric
    push-streams, receivers filtered by allowed peers (:305-433);
  * ``PeerStreamPullConnector`` — ask the scheduler for a slice assignment
    (api::Data) then pull the slice from the data node (:436-507).

Received file names are SHA-256-hashed before hitting the filesystem,
matching the parameter server's path-injection defense
(crates/worker/src/executor/parameter_server.rs:133-135).
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import logging
import os
import shutil
import time
import urllib.parse
import urllib.request
from pathlib import Path
from typing import AsyncIterator

from .. import aio
from ..messages import (
    PROTOCOL_API,
    DataRequest,
    DataResponse,
    DataSlice,
    Fetch,
    Receive,
    Reference,
    Send,
    ShardMap,
    TransferStrategy,
)
from ..network.node import Node, PushStream, RequestError
from ..telemetry import trace
from ..telemetry.ft_metrics import DATA_METRICS

__all__ = ["Connector", "ReceivedFile", "claim_spare", "fetch_uri", "shard_route"]

log = logging.getLogger("hypha.worker.connector")


def _safe_name(name: str) -> str:
    """Collapse any peer-supplied name to a flat digest-based filename."""
    return hashlib.sha256(name.encode()).hexdigest()[:32]


# A name of its own for every spare a write claims (``claim_spare``).
_CLAIMS = itertools.count()


def claim_spare(spare: Path) -> Path | None:
    """The file the last write of its kind left, for this one to write over,
    if the caller is the only one that still names it; else None. The one
    definition of that claim: the node's (``Connector._save``, a stream's
    last push) and the executor's (``run_training``'s ``encode.write``, the
    last round's delta).

    The node's spare is a second name (``_keep_spare``) of a file whose
    first name went to a consumer; the executor's is its delta under the
    name it was sent by, which a sender that has not ended holds by a
    second name of the node's own (``Bridge._send``). The rename takes it
    for this write alone: of two that run at once one finds no spare and
    goes fresh. A link count of 1 then says the other has unlinked its
    name, which every consumer does after its last read and the bridge when
    its send has returned or raised, so the inode and its pages are this
    write's. With 2 a reader may still hold it (a re-broadcast arriving
    during the merge's read, a slice that is kept, a push that is being
    retried): the name is dropped, the file stays the reader's, and the
    fresh file this write makes is the next spare."""
    claimed = spare.with_name(f"{spare.stem}.{os.getpid()}-{next(_CLAIMS)}.over")
    try:
        os.rename(spare, claimed)
        if os.stat(claimed).st_nlink == 1:
            return claimed
        os.unlink(claimed)
    except OSError:
        pass  # no spare: the stream's first push, or another save has it
    return None


def _keep_spare(dest: Path, spare: Path) -> None:
    """Give the file that landed at ``dest`` a second name, the stream's one
    spare, before a consumer hears of it: the pages stay when the consumer
    unlinks ``dest``, for the stream's next push to land in. Where a link
    cannot be had (EPERM, EXDEV, EMLINK, a save that ran at once was first)
    there is none and the next push goes into a fresh file, as it always
    did."""
    try:
        spare.parent.mkdir(exist_ok=True)
        spare.unlink(missing_ok=True)  # an older one: its pages go with its last name
        os.link(dest, spare)
    except OSError:
        pass


# Outbound tensor pushes retry with jittered backoff (aio.retry) for up to
# this many seconds: a parameter-server restart or a transient partition
# costs a few re-attempts, not a lost delta and a wedged round. The PS's
# journal dedups any copy whose first attempt actually landed.
PUSH_RETRY_DEADLINE_ENV = "HYPHA_PUSH_RETRY_DEADLINE"
PUSH_RETRY_DEADLINE_DEFAULT = 120.0


def _push_deadline() -> float:
    try:
        return float(
            os.environ.get(PUSH_RETRY_DEADLINE_ENV, "")
            or PUSH_RETRY_DEADLINE_DEFAULT
        )
    except ValueError:
        return PUSH_RETRY_DEADLINE_DEFAULT


def payload_size(source: "Path | int") -> int:
    """What a push of ``source`` sends: a file's size (0 where it is gone),
    or the byte count itself for a push from memory."""
    if isinstance(source, int):
        return source
    try:
        return source.stat().st_size
    except OSError:
        return 0


def push_timeout(source: "Path | int", base: float = 60.0) -> float:
    """Per-attempt wall-clock bound for a parameter-sized push (``source``:
    the file pushed, or the byte count of a push from memory): a push
    black-holed by a partition that drops packets without RST must fail
    fast enough to retry (the deadline is only consulted BETWEEN
    attempts), but a legitimately slow multi-GB transfer must never be
    cancelled mid-flight — so the bound grows with the payload at a
    conservative floor rate (10 MB/s) over ``base``.
    ``$HYPHA_PUSH_ATTEMPT_TIMEOUT`` overrides outright."""
    env = os.environ.get("HYPHA_PUSH_ATTEMPT_TIMEOUT")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return base + payload_size(source) / (10 * 1024 * 1024)


def shard_route(
    shard_map: ShardMap, part: int, reduce_via: str | None = None
) -> tuple[Send, int, str]:
    """The Send reference for one placement part's delta push.

    Sharded parameter service (hypha_tpu.stream placement): part ``p`` is
    owned by shard ``shard_of(p, N)`` and must land on that shard's peer
    under that shard's updates tag — every peer derives the same owner
    from the same deterministic partition, so no manifest is exchanged.

    Returns ``(send, owner_shard, tag)``. With tree-reduce, the group's
    reducer peer is tried FIRST with ANY failover: a dead reducer degrades
    this worker to direct-to-shard pushes instead of wedging the round
    (the shard accepts both forms — a pre-folded partial and the raw
    delta — and reconciles any at-least-once overlap by cover sets; see
    ParameterServerExecutor._direct_covered/_retire_covered).
    """
    from ..stream.partition import shard_of

    if not shard_map.shards:
        raise ValueError("shard_route needs a populated ShardMap")
    owner = shard_of(part, len(shard_map.shards))
    owner_peer = shard_map.shards[owner]
    tag = (
        shard_map.tags[owner]
        if shard_map.tags
        else "updates"
    )
    peers = [owner_peer]
    strategy = TransferStrategy.ALL
    if reduce_via and reduce_via != owner_peer:
        peers = [reduce_via, owner_peer]
        strategy = TransferStrategy.ANY
    return Send(Reference.from_peers(peers, tag, strategy)), owner, tag


class ReceivedFile:
    def __init__(
        self,
        path: Path,
        size: int,
        from_peer: str,
        resource: str,
        meta: dict | None = None,
    ) -> None:
        self.path = path
        self.size = size
        self.from_peer = from_peer
        self.resource = resource
        # Full push header (round, epoch, catchup, num_samples, ...): the
        # executor-side control data that rides each tensor stream.
        self.meta = meta or {}


def fetch_uri(uri: str, dest_dir: Path) -> Path:
    """Blocking URI download (run via to_thread): http(s) streamed to disk,
    file:// hard-linked/copied. Scheme-validated (bridge.rs:350-377)."""
    parsed = urllib.parse.urlparse(uri)
    if parsed.scheme not in ("http", "https", "file"):
        raise ValueError(f"unsupported URI scheme {parsed.scheme!r}")
    dest_dir.mkdir(parents=True, exist_ok=True)
    name = Path(parsed.path).name or "download"
    dest = dest_dir / name
    if parsed.scheme == "file":
        src = Path(urllib.request.url2pathname(parsed.path))
        shutil.copyfile(src, dest)  # streams; checkpoints don't fit in RAM
        return dest
    with urllib.request.urlopen(uri) as resp, open(dest, "wb") as f:  # noqa: S310
        while True:
            chunk = resp.read(1 << 20)
            if not chunk:
                break
            f.write(chunk)
    return dest


class Connector:
    """Routes Reference variants to transports (connector/mod.rs router).

    ``slice_cache`` (worker.slice_cache.SliceCache, optional) backs
    scheduler-mediated slice fetches for PIPELINED jobs (the Fetch
    reference carries ``prefetch``): assignments whose ``(dataset, epoch,
    index)`` the cache already holds are served from disk — a rejoined or
    restarted worker re-pulls nothing it already had.
    """

    def __init__(
        self, node: Node, scheduler_peer: str = "", slice_cache=None
    ) -> None:
        self.node = node
        self.scheduler_peer = scheduler_peer
        self.slice_cache = slice_cache

    # -------------------------------------------------------------- fetch

    async def fetch(self, fetch: Fetch, dest_dir: Path) -> list[Path]:
        ref = fetch.ref
        variant = ref.variant()
        if variant == "uri":
            path = await asyncio.to_thread(fetch_uri, ref.uri, dest_dir)
            return [path]
        if variant == "huggingface":
            return await asyncio.to_thread(self._fetch_hf, ref, dest_dir)
        if variant == "scheduler":
            return [await self._fetch_slice(ref, dest_dir)]
        if variant == "peers":
            raise ValueError("peers variant is receive-only for fetch")
        raise ValueError(f"unknown fetch variant {variant}")

    def _fetch_hf(self, ref: Reference, dest_dir: Path) -> list[Path]:
        """HuggingFace Hub download via hf_hub (reference uses hf-hub crate)."""
        from huggingface_hub import hf_hub_download  # lazy: not in hot path

        dest_dir.mkdir(parents=True, exist_ok=True)
        out = []
        for filename in ref.filenames or []:
            cached = hf_hub_download(
                repo_id=ref.repo,
                filename=filename,
                revision=ref.revision or "main",
                token=ref.token,
            )
            dest = dest_dir / Path(filename).name
            shutil.copyfile(cached, dest)
            out.append(dest)
        return out

    async def _fetch_slice(self, ref: Reference, dest_dir: Path) -> Path:
        """Scheduler-mediated slice fetch: ask for an assignment, pull it
        (connector/mod.rs:436-507 PeerStreamPullConnector).

        Pipelined jobs (``ref.prefetch`` set) forward the prefetch window
        to the scheduler so it defers slice retirement, key the dest name
        by the response's epoch (a prefetching consumer may still be
        reading this index's previous-epoch file), and check/fill the
        on-disk slice cache around the network pull."""
        scheduler = ref.scheduler_peer or self.scheduler_peer
        if not scheduler:
            raise ValueError("no scheduler peer for slice fetch")
        prefetch = getattr(ref, "prefetch", None)
        resp = await self.node.request(
            scheduler,
            PROTOCOL_API,
            DataRequest(
                dataset=ref.dataset or "",
                peer_id=self.node.peer_id,
                prefetch=prefetch,
            ),
        )
        if not isinstance(resp, DataResponse):
            raise RequestError(f"unexpected data response {resp!r}")
        epoch = getattr(resp, "epoch", None)
        dest_dir.mkdir(parents=True, exist_ok=True)
        stem = _safe_name(ref.dataset or "slice")
        dest = (
            dest_dir / f"{stem}-e{epoch}-{resp.index:06d}"
            if epoch is not None
            else dest_dir / f"{stem}-{resp.index:06d}"
        )
        cache = (
            self.slice_cache
            if prefetch is not None and epoch is not None
            else None
        )
        if cache is not None and await asyncio.to_thread(
            cache.get, ref.dataset or "", epoch, resp.index, dest
        ):
            return dest
        stream = await self.node.pull(
            resp.data_provider, DataSlice(dataset=ref.dataset or "", index=resp.index)
        )
        loop = asyncio.get_running_loop()
        pulled = 0
        try:
            f = await asyncio.to_thread(open, dest, "wb")
            try:
                while True:
                    chunk = await stream.read(1 << 20)
                    if not chunk:
                        break
                    pulled += len(chunk)
                    await loop.run_in_executor(None, f.write, chunk)
            finally:
                await asyncio.to_thread(f.close)
        finally:
            await stream.close()
        DATA_METRICS.bytes_pulled.add(pulled)
        if cache is not None:
            await asyncio.to_thread(
                cache.put, ref.dataset or "", epoch, resp.index, dest
            )
        return dest

    # --------------------------------------------------------------- send

    async def send(
        self, send: Send, path: Path, resource: str, meta: dict | None = None
    ) -> None:
        """Push a local file to the reference's peers. ALL: every peer must
        get it; ANY: first success wins (connector/mod.rs:305-433).
        ``meta`` keys ride the stream header (the parameter server reads
        ``num_samples`` for its weighted mean); the reserved keys win.

        Failed pushes retry with jittered backoff up to
        ``$HYPHA_PUSH_RETRY_DEADLINE`` seconds (default 120): the worker
        *parks and re-pushes* across a receiver outage — a restarting
        parameter server — instead of failing the round on first contact.
        """
        ref = send.ref
        peers = ref.peers or []
        strategy = ref.strategy or TransferStrategy.ALL
        header = {**(meta or {}), "resource": resource, "name": path.name}
        deadline = _push_deadline()
        attempts: dict[str, int] = {}  # peer -> pushes tried (traced sends)
        # Per-attempt bound: a push black-holed by a silent partition (no
        # RST, TCP retransmitting forever) must be cancelled and retried —
        # the deadline alone cannot interrupt an attempt in flight.
        attempt_timeout = push_timeout(path)
        if strategy == TransferStrategy.ANY:

            async def any_once() -> None:
                last: Exception | None = None
                for peer in peers:
                    try:
                        await self._push_once(peer, header, path, attempts)
                        return
                    except (RequestError, OSError) as e:
                        # OSError too: a peer that accepts the dial but
                        # resets mid-push must not stop the failover —
                        # the next peer gets its try within THIS attempt.
                        last = e
                raise RequestError(f"no peer accepted {resource}: {last}")

            try:
                await aio.retry(
                    any_once,
                    base_delay=0.25, max_delay=5.0, deadline=deadline,
                    attempt_timeout=attempt_timeout * max(len(peers), 1),
                    retry_on=(RequestError, OSError),
                    what=f"push {resource} (any)", logger=log,
                )
            except asyncio.TimeoutError as e:
                raise RequestError(
                    f"push {resource} (any) timed out after {deadline}s"
                ) from e
            return
        failures = []
        # ONE retry budget shared across the whole peer list — the peers
        # are pushed sequentially, so a per-peer deadline would multiply
        # the promised bound by the number of dead peers. Every peer still
        # gets at least one attempt (retry only consults the deadline
        # before SLEEPING, never before the first try).
        stop_at = asyncio.get_running_loop().time() + deadline
        for peer in peers:
            try:
                await aio.retry(
                    lambda p=peer: self._push_once(p, header, path, attempts),
                    base_delay=0.25, max_delay=5.0,
                    attempt_timeout=attempt_timeout,
                    deadline=max(
                        stop_at - asyncio.get_running_loop().time(), 0.0
                    ),
                    retry_on=(RequestError, OSError),
                    what=f"push {resource} to {peer}", logger=log,
                )
            except (RequestError, OSError, asyncio.TimeoutError) as e:
                failures.append((peer, e))
        if failures:
            raise RequestError(f"send failures: {failures}")

    async def _push_once(
        self, peer: str, header: dict, path: Path, attempts: dict[str, int]
    ) -> None:
        """One attempt to push ``path`` to ``peer``. Traced, it is a ``send``
        span of this node: the sender's end of the journey whose other end
        is the receiver's ``upload`` (the PS) or ``receive`` (a worker),
        under the trace the header names, whether the attempt ran to its
        end, failed or was cancelled at its time limit."""
        t = trace.active()
        if t is None:
            await self.node.push(peer, header, path)
            return
        attempts[peer] = attempts.get(peer, 0) + 1
        attrs = {"peer": peer, "bytes": payload_size(path), "attempt": attempts[peer]}
        if header.get("round") is not None:
            attrs["round"] = header["round"]
        with t.span(
            "send", parent=header.get(trace.TRACEPARENT_KEY), attrs=attrs,
            node=self.node.peer_id, usage=True,
        ):
            await self.node.push(peer, header, path)

    # ------------------------------------------------------------- receive

    async def receive(
        self, receive: Receive, dest_dir: Path
    ) -> AsyncIterator[ReceivedFile]:
        """Yield files as they land from allowed peers; unknown senders are
        drained and dropped (connector/mod.rs:305-433 receiver filter).

        Routed: when the Receive reference carries a resource tag, only
        pushes with that tag are consumed — other consumers on the same node
        (another job's bridge, a parameter-server loop) keep theirs.
        """
        allowed = set(receive.ref.peers or [])
        tag = receive.ref.resource

        def wants(push: PushStream) -> bool:
            if tag is None:
                return True  # untagged receive: legacy catch-all
            r = push.resource
            return isinstance(r, dict) and r.get("resource") == tag

        dest_dir.mkdir(parents=True, exist_ok=True)
        consumer = self.node.consume_pushes(wants)
        try:
            async for push in consumer:
                try:
                    if allowed and push.peer not in allowed:
                        log.warning("dropping push from disallowed peer %s", push.peer)
                        await push.read_all()  # drain to release the accept slot
                        continue
                    resource, name = _push_names(push)
                    dest = dest_dir / f"{_safe_name(push.peer + '-' + name)}.bin"
                    meta = push.resource if isinstance(push.resource, dict) else {}
                    size = await self._save(push, dest, resource, meta)
                except asyncio.CancelledError:
                    # Consumer went away mid-transfer: release the accept slot
                    # so the sender's connection isn't pinned forever.
                    push.finish()
                    raise
                yield ReceivedFile(dest, size, push.peer, resource, meta)
        finally:
            consumer.close()

    async def _save(
        self, push: PushStream, dest: Path, resource: str, meta: dict
    ) -> int:
        """Save an accepted push. Traced, it is a ``receive`` span of this
        node, header arrival to payload on disk, under the trace the header
        names: the receiver's end of the journey whose other end is the
        sender's span (the PS's ``broadcast``), with what ``save_to`` says
        of itself under the names the PS's ``upload`` span gives them. A
        push that ends any other way (cancellation, a sender that went
        away) leaves the span with ``ok`` false. Tracing on or off, the
        ``push received:`` line says the same of a push that landed.

        The payload is written over the file the last push of this stream
        (sender and resource tag) left, once its consumer has unlinked it
        (``claim_spare``): ``pages=recycled``, and over plain TCP
        ``path=thread``. The state is the file system's, under
        ``dest``'s ``spare/``, and goes with the directory."""
        spare = dest.parent / "spare" / f"{_safe_name(push.peer + '-' + resource)}.bin"
        t = trace.active()
        span = None
        if t is not None:
            attrs = {"peer": push.peer, "resource": resource}
            if meta.get("round") is not None:
                attrs["round"] = meta["round"]
            span = t.begin(
                "receive", parent=meta.get(trace.TRACEPARENT_KEY), attrs=attrs,
                node=self.node.peer_id, usage=True,
            )
        t0 = time.monotonic()
        try:
            # Not through a thread: from the rename on the spare is
            # ``save_to``'s to write, name and unlink, with no await between.
            size = await push.save_to(dest, over=claim_spare(spare))
            await asyncio.to_thread(_keep_spare, dest, spare)
        except BaseException:
            trace.finish(span, ok=False)
            raise
        wall_s = time.monotonic() - t0
        pages = "recycled" if push.recycled else "fresh"
        path = "thread" if push.threaded else "loop"
        if span is not None:
            span.attributes.update(
                bytes=size, pages=pages, path=path,
                read_s=round(push.read_s, 6), write_s=round(push.write_s, 6),
            )
        trace.finish(span)
        # The receiving end's line, as the PS's ``ps upload:``, tracing on or off.
        log.info(
            "push received: round=%s peer=%s bytes=%d pages=%s path=%s "
            "wall_s=%.3f read_s=%.3f write_s=%.3f",
            meta.get("round"), push.peer, size, pages, path, wall_s,
            push.read_s, push.write_s,
        )
        return size


def _push_names(push: PushStream) -> tuple[str, str]:
    res = push.resource
    if isinstance(res, dict):
        return str(res.get("resource", "")), str(res.get("name", "push"))
    if isinstance(res, DataSlice):
        return res.dataset, f"{res.dataset}-{res.index}"
    return "", "push"
