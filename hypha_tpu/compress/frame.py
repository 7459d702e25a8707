"""HQD1: the self-describing compressed-delta wire container.

Layout (little-endian):

    bytes 0..3   magic ``HQD1``
    bytes 4..7   u32 header length H
    bytes 8..8+H CBOR header map:
        {"codec": "int8"|"int4", "chunk": int,
         "tensors": [{"name": str, "shape": [int, ...],
                      "qoff": int, "qlen": int,
                      "soff": int, "slen": int}, ...]}
    payload      concatenated per-tensor quantized bytes + f32 scale
                 arrays; every offset is relative to the payload start.

The header rides the repo's own CBOR codec (hypha_tpu.codec — native
extension when available), so the format needs no new dependency and a
receiver needs no out-of-band schema: codec, chunking and the tensor
table all travel in-band. SafeTensors files fail the magic check, which
is how :func:`read_delta` lets quantized and plain deltas interoperate on
the same stream.

Writers emit via a temp name + ``os.replace`` so a crashed writer never
publishes a torn frame.
"""

from __future__ import annotations

import json
import os
import struct
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

import numpy as np

from .. import codec as cbor
from .quant import DEFAULT_CHUNK, dequantize, quantize

__all__ = [
    "MAGIC",
    "is_frame",
    "write_frame",
    "read_frame",
    "read_delta",
    "read_delta_into",
    "frame_f32",
    "write_exact",
    "ReadStats",
    "write_delta",
    "frame_tag",
]

MAGIC = b"HQD1"

# Header sanity bound for untrusted input: a tensor table bigger than this
# is a malformed/hostile frame, not a real delta.
_MAX_HEADER = 64 * 1024 * 1024


def is_frame(path: Path | str) -> bool:
    """True when ``path`` starts with the HQD1 magic."""
    try:
        with open(path, "rb") as fp:
            return fp.read(4) == MAGIC
    except OSError:
        return False


def write_frame(
    path: Path | str,
    flat: dict[str, np.ndarray],
    codec: str,
    chunk: int = DEFAULT_CHUNK,
    tag: dict[str, Any] | None = None,
) -> dict[str, np.ndarray]:
    """Quantize ``flat`` and write one HQD1 frame atomically.

    Returns the DEQUANTIZED tree — exactly what a receiver will decode —
    so the caller can compute its error-feedback residual without
    re-reading the file. ``tag`` (e.g. a streaming sync's
    ``FragmentTag.header()`` with round/fragment_id) rides the CBOR
    header, making the frame self-identifying even off the push stream
    that carried it; decoders that predate the field ignore it.
    """
    path = Path(path)
    table: list[dict[str, Any]] = []
    chunks: list[bytes] = []
    decoded: dict[str, np.ndarray] = {}
    off = 0
    for name, arr in flat.items():
        a = np.ascontiguousarray(np.atleast_1d(np.asarray(arr, np.float32)))
        payload, scales = quantize(a.ravel(), codec, chunk)
        decoded[name] = dequantize(payload, scales, a.size, codec, chunk).reshape(
            a.shape
        )
        qb, sb = payload.tobytes(), scales.tobytes()
        table.append(
            {
                "name": name,
                "shape": list(a.shape),
                "qoff": off,
                "qlen": len(qb),
                "soff": off + len(qb),
                "slen": len(sb),
            }
        )
        chunks.append(qb)
        chunks.append(sb)
        off += len(qb) + len(sb)
    head: dict[str, Any] = {"codec": codec, "chunk": chunk, "tensors": table}
    if tag:
        head["tag"] = dict(tag)
    header = cbor.dumps(head)
    tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
    with open(tmp, "wb") as fp:
        fp.write(MAGIC)
        fp.write(struct.pack("<I", len(header)))
        fp.write(header)
        for blob in chunks:
            fp.write(blob)
    os.replace(tmp, path)
    return decoded


def read_frame(path: Path | str) -> dict[str, np.ndarray]:
    """Decode one HQD1 frame → {name: f32 ndarray}."""
    with open(path, "rb") as fp:
        data = fp.read()
    if data[:4] != MAGIC:
        raise ValueError(f"{path}: not an HQD1 frame")
    if len(data) < 8:
        raise ValueError(f"{path}: truncated frame header")
    (hlen,) = struct.unpack("<I", data[4:8])
    if hlen > _MAX_HEADER or 8 + hlen > len(data):
        raise ValueError(f"{path}: header length {hlen} exceeds frame")
    header = cbor.loads(data[8 : 8 + hlen])
    if not isinstance(header, dict):
        raise ValueError(f"{path}: malformed frame header")
    codec = header.get("codec")
    chunk = header.get("chunk")
    table = header.get("tensors")
    if not isinstance(chunk, int) or not isinstance(table, list):
        raise ValueError(f"{path}: malformed frame header")
    payload = memoryview(data)[8 + hlen :]
    out: dict[str, np.ndarray] = {}
    for entry in table:
        name = entry["name"]
        shape = tuple(int(d) for d in entry["shape"])
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        qoff, qlen = int(entry["qoff"]), int(entry["qlen"])
        soff, slen = int(entry["soff"]), int(entry["slen"])
        if qoff < 0 or soff < 0 or qoff + qlen > len(payload) or soff + slen > len(payload):
            raise ValueError(f"{path}: tensor {name!r} spans outside payload")
        q = np.frombuffer(payload[qoff : qoff + qlen], np.uint8)
        scales = np.frombuffer(payload[soff : soff + slen], np.float32)
        out[name] = dequantize(q, scales, n, codec, chunk).reshape(shape)
    return out


def write_delta(
    path: Path | str,
    flat: dict[str, np.ndarray],
    codec: str,
    chunk: int = DEFAULT_CHUNK,
    ef=None,
    tag: dict[str, Any] | None = None,
    over: Path | str | None = None,
) -> dict[str, np.ndarray]:
    """The one send-side entry point: encode ``flat`` per ``codec``.

    int8/int4 write an HQD1 frame — compensated through ``ef``
    (:class:`~hypha_tpu.compress.feedback.ErrorFeedback`) when given, so
    the quantization error rides the next send. bf16 casts f32 tensors
    (others pass through) into SafeTensors; "none" writes f32 SafeTensors.
    ``tag`` stamps HQD1 frames with the sender's stream identity
    (round/fragment); SafeTensors codecs rely on the push header alone.
    Returns the tree AS A RECEIVER WILL DECODE IT (for residuals, catch-up
    accounting, or tests).

    **Which trees are written without a copy.** "none" over a tree whose
    every leaf is ``float32``, which is what ``device_get`` gives of an f32
    model: the file is :func:`frame_f32`'s head and then the leaves' own
    memory, written as it lies (:func:`write_exact`), with no ``tobytes``
    of anything. A leaf that is not C-contiguous is copied first, that leaf
    alone, as every leaf always was normalised: on one thread, at about
    0.55 GB/s (a TPU keeps some matrices column-major and ``device_get``
    keeps the device's order: 0.81 GB of the 2.11 GB of a Nemotron-H cut).
    The worker's ``extract_delta`` asks the device for row-major results, so
    a leaf that arrives so is the exception, and ``encode.write``'s
    ``copied_bytes`` names it. The file loads with
    ``safetensors`` to the keys, shapes and bytes ``save_file`` would have
    written, its leaves in the tree's order. Every other tree (bf16,
    int8/int4, "none" with a leaf of another dtype) is encoded and saved
    through a copy of all of it, as it always was.

    ``over``: a file on ``path``'s file system that the caller is done with
    and alone still names (the delta of its last round;
    ``worker/connectors.py`` ``claim_spare``). The copy-free write goes
    over it from offset 0, without truncation, so it lands in pages that
    exist and not in fresh ones; it is cut to the exact length at the end
    and renamed onto ``path`` when whole, so ``path`` never names a file
    whose head is this delta's and whose tail the last one's. A write that
    fails leaves neither ``over`` nor (of this call's making) ``path``.
    Where the tree takes another path, ``over`` is unlinked and the file
    written is fresh.
    """
    from safetensors.numpy import save_file

    over = None if over is None else Path(over)
    if codec in ("int8", "int4"):
        if over is not None:
            over.unlink(missing_ok=True)
        if ef is not None:
            flat = ef.compensate(flat)
        decoded = write_frame(path, flat, codec, chunk, tag=tag)
        if ef is not None:
            ef.absorb(flat, decoded)
        return decoded
    # No copy of a leaf that is C-contiguous already.
    norm = {
        k: np.ascontiguousarray(np.atleast_1d(np.asarray(v)))
        for k, v in flat.items()
    }
    if codec == "none" and all(v.dtype == np.float32 for v in norm.values()):
        _write_f32(Path(path), norm, over)
        return norm
    if over is not None:
        over.unlink(missing_ok=True)
    if codec == "bf16":
        # ml_dtypes ships with jax; lazy so stripped PS hosts without the
        # bf16 codec configured never import it.
        import ml_dtypes

        norm = {
            k: v.astype(ml_dtypes.bfloat16) if v.dtype == np.float32 else v
            for k, v in norm.items()
        }
    elif codec != "none":
        raise ValueError(f"unknown wire codec {codec!r}")
    save_file(norm, str(path))
    return norm


def _write_f32(path: Path, tree: dict[str, np.ndarray], over: Path | None) -> None:
    """:func:`frame_f32` of ``tree`` as a file at ``path``: into a fresh
    file there, or over ``over`` and renamed onto ``path`` when whole."""
    head, views = frame_f32(tree)
    target = path if over is None else over
    # Without O_TRUNC over a spare: its pages are what this is for keeping.
    flags = os.O_WRONLY | (os.O_CREAT | os.O_TRUNC if over is None else 0)
    try:
        fd = os.open(target, flags, 0o644)
        try:
            write_exact(fd, memoryview(head))
            for view in views:
                write_exact(fd, view)
            if over is not None:
                os.ftruncate(fd, len(head) + sum(len(v) for v in views))
        finally:
            os.close(fd)
        if over is not None:
            os.replace(over, path)
    except BaseException:
        # Half a delta, or this delta's head before the last one's tail: no
        # reader may find that under any name.
        target.unlink(missing_ok=True)
        raise


def frame_tag(path: Path | str) -> dict[str, Any] | None:
    """The stream tag an HQD1 frame carries (None: untagged / not a frame).

    Reads only magic + header, never the payload — cheap enough for a
    receiver to cross-check a push header's (round, fragment_id) against
    what the sender baked into the frame itself.
    """
    try:
        with open(path, "rb") as fp:
            head = fp.read(8)
            if head[:4] != MAGIC or len(head) < 8:
                return None
            (hlen,) = struct.unpack("<I", head[4:8])
            if hlen > _MAX_HEADER:
                return None
            header = cbor.loads(fp.read(hlen))
    except (OSError, ValueError):
        return None
    if not isinstance(header, dict):
        return None
    tag = header.get("tag")
    return dict(tag) if isinstance(tag, dict) else None


def read_delta(path: Path | str) -> dict[str, np.ndarray]:
    """Read a delta/update file in ANY per-job wire format.

    HQD1 frames dequantize to f32; everything else is SafeTensors (f32 or
    bf16 — callers widen per tensor as they always did). This is the one
    receive-side entry point, so a job's codec choice never needs to reach
    the decoder out-of-band.
    """
    if is_frame(path):
        return read_frame(path)
    from safetensors.numpy import load_file

    return dict(load_file(str(path)))


@dataclass
class ReadStats:
    """What one read of a delta file did. ``direct``: leaves whose bytes
    went from the file into memory the caller keeps in one pass.
    ``resident``: leaves whose buffer was there before this read (kept from
    an earlier round) and not allocated by it."""

    bytes: int = 0
    leaves: int = 0
    direct: int = 0
    resident: int = 0

    def add(self, other: "ReadStats") -> None:
        """Count one more file of the same round."""
        self.bytes += other.bytes
        self.leaves += other.leaves
        self.direct += other.direct
        self.resident += other.resident


def f32_layout(path: Path) -> dict[str, tuple[tuple, int, int]] | None:
    """``{key: (shape, file offset, bytes)}`` in file order when ``path``
    is a SafeTensors file whose tensors are all ``F32``, by the file's own
    header; None for anything else (an HQD1 frame, bf16, a header this
    cannot read), which :func:`read_delta` decodes or refuses. An all-F32
    header that does not fit its file raises ``ValueError``."""
    size = os.path.getsize(path)
    with open(path, "rb") as fp:
        head = fp.read(8)
        n = int.from_bytes(head, "little")
        if len(head) < 8 or n > size - 8:  # an HQD1 frame's magic reads as a huge n
            return None
        try:
            header = json.loads(fp.read(n))
            header.pop("__metadata__", None)
            entries = sorted(
                (tuple(info["data_offsets"]), key, info["dtype"], tuple(info["shape"]))
                for key, info in header.items()
            )
        except (ValueError, AttributeError, KeyError, TypeError):
            return None
    if any(dtype != "F32" for _, _, dtype, _ in entries):
        return None
    layout = {}
    for (begin, end), key, _, shape in entries:
        nbytes = 4 * int(np.prod(shape, dtype=np.int64))
        if begin < 0 or end - begin != nbytes or 8 + n + end > size:
            raise ValueError(
                f"delta {key!r}: {nbytes} bytes of {shape} at {begin}:{end} "
                f"do not fit {path.name} ({size} bytes)"
            )
        layout[key] = (shape, 8 + n + begin, nbytes)
    return layout


def frame_f32(tree: dict[str, np.ndarray]) -> tuple[bytes, list[memoryview]]:
    """The SafeTensors framing of an all-f32 tree with none of its bytes
    moved: :func:`f32_layout`'s mirror on the sending side.

    Returns the file's head (the 8-byte length and the JSON header: the
    leaves in the tree's own order, ``F32``, ``data_offsets``; padded with
    spaces to a multiple of 8 as ``save_file`` pads) and one byte view a
    leaf, of the leaf's own memory, in that order. Head and views written
    one after the other are a file ``safetensors`` loads and
    :func:`f32_layout` takes. A leaf has to be C-contiguous ``float32``:
    anything else would have to be copied, which is what this is for not
    doing."""
    header: dict[str, dict] = {}
    views: list[memoryview] = []
    end = 0
    for key, leaf in tree.items():
        if leaf.dtype != np.float32 or not leaf.flags.c_contiguous:
            raise ValueError(f"update {key!r}: not a C-contiguous float32 array")
        begin, end = end, end + leaf.nbytes
        header[key] = {
            "dtype": "F32", "shape": list(leaf.shape), "data_offsets": [begin, end],
        }
        if leaf.nbytes:  # a view of nothing cannot be cast
            views.append(memoryview(leaf.reshape(-1)).cast("B"))
    body = json.dumps(header, separators=(",", ":")).encode()
    body += b" " * (-len(body) % 8)
    return len(body).to_bytes(8, "little") + body, views


def read_exact(fd: int, offset: int, dst: np.ndarray) -> None:
    """Fill ``dst`` with the file's bytes from ``offset``: reads into memory
    that exists (no mapping of the file, no ``bytes`` in between), each
    with the interpreter lock released."""
    view = memoryview(dst.reshape(-1)).cast("B")
    done = 0
    while done < len(view):
        got = os.preadv(fd, [view[done:]], offset + done)
        if got <= 0:
            raise ValueError(f"delta file ends {len(view) - done} bytes early")
        done += got


def write_exact(fd: int, view: memoryview) -> None:
    """:func:`read_exact`'s mirror: the whole of ``view`` to the file at its
    position, from the memory it lies in (nothing joined, no ``bytes`` in
    between), each write with the interpreter lock released."""
    done = 0
    while done < len(view):  # one write takes 2 GiB less a page at most
        done += os.write(fd, view[done:])


def read_delta_into(
    path: Path | str,
    lease: Callable[[str, tuple], tuple[np.ndarray, bool]],
) -> tuple[dict[str, np.ndarray], ReadStats]:
    """:func:`read_delta` into memory the caller keeps from one file to the
    next: the same keys, shapes, dtype and bytes.

    A plain SafeTensors file whose tensors are all F32 (what ``delta_codec``
    none sends; known from the file's own header) goes a leaf at a time
    from the file into the f32 buffer ``lease(key, shape)`` returns, with
    whether that buffer was kept from an earlier call
    (:meth:`~hypha_tpu.stream.accum.SumBuffers.lease`): no mapping of the
    file and, once the buffers exist, nothing parameter-sized allocated.
    The tree returned is those buffers, so it is only good until the caller
    has them written again. Anything else comes back as :func:`read_delta`
    gives it, fresh, with ``direct == 0``.
    """
    path = Path(path)
    stats = ReadStats(bytes=path.stat().st_size)
    layout = f32_layout(path)
    if layout is None:
        flat = read_delta(path)
    else:
        flat = {}
        with open(path, "rb", buffering=0) as fp:
            for key, (shape, offset, nbytes) in layout.items():
                flat[key], kept = lease(key, shape)
                stats.resident += kept
                if nbytes:
                    read_exact(fp.fileno(), offset, flat[key])
        stats.direct = len(flat)
    stats.leaves = len(flat)
    return flat, stats
