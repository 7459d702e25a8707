"""``compress.write_delta`` writes an all-f32 tree from the leaves' own memory,
into a fresh file or over one the caller hands it.

For codec ``none`` over a tree of ``float32`` leaves the file is
``frame_f32``'s head and then the leaves as they lie: no ``tobytes``, no
``save_file``; a leaf that is not C-contiguous is copied first, it alone. With ``over`` the bytes go over that file, which is cut to the
exact length and renamed onto the delta's name when whole. Here: the file is
what ``save_file`` would have written to every reader (``load_file``,
``f32_layout``, ``read_delta_into``), whatever the spare's length and whatever
the leaves' shapes; every other tree and codec goes the way it always went and
an ``over`` that was handed in is unlinked; a write that fails half way leaves
no file under either name.
"""

from __future__ import annotations

import errno
import os

import ml_dtypes
import numpy as np
import pytest
import safetensors.numpy
from safetensors.numpy import load_file, save_file

from hypha_tpu import compress
from hypha_tpu.compress import frame
from hypha_tpu.compress.frame import f32_layout, read_delta_into
from hypha_tpu.stream.accum import SumBuffers


def _tree(kind: str) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(11)

    def leaf(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    # Not in sorted order, as a model's leaves are not: save_file sorts, the
    # copy-free file keeps the tree's order, and every reader goes by key.
    tree = {"wte/embedding": leaf(16, 8), "blocks_0/attn/kernel": leaf(8, 3, 8),
            "ln_f/scale": leaf(8), "blocks_0/attn/bias": leaf(24)}
    if kind == "0-d":
        tree["step_scale"] = np.array(0.25, np.float32)  # as device_get gives a scalar
    elif kind == "empty leaf":
        tree["blocks_0/none"] = np.zeros((0, 8), np.float32)
        tree["zz_after_the_empty_one"] = leaf(5)
    elif kind == "one leaf":
        tree = {"only": leaf(1000, 33)}
    elif kind == "no leaf":
        tree = {}
    elif kind == "a view of a larger buffer":
        whole = leaf(4, 64)
        tree["row"] = whole[2]  # contiguous, does not own its memory, offset
    elif kind == "a column-major leaf":
        # As device_get hands back a narrow matrix on the TPU: copied, it alone.
        tree["router"] = np.asfortranarray(leaf(32, 4))
        assert not tree["router"].flags.c_contiguous
    elif kind == "a strided leaf":
        tree["ln_f/scale"] = np.arange(16, dtype=np.float32)[::2]
    return tree


def _as_save_file_wrote(tmp_path, tree) -> dict[str, np.ndarray]:
    """What the parent's ``write_delta(..., "none")`` left on disk."""
    old = tmp_path / "as-it-was.safetensors"
    save_file({k: np.ascontiguousarray(np.atleast_1d(np.asarray(v))) for k, v in tree.items()},
              str(old))
    return load_file(str(old))


def _spare(tmp_path, how: str, size: int):
    if how == "fresh":
        return None
    spare = tmp_path / "delta-0.1234-0.over"
    spare.write_bytes(b"\xee" * {"longer": 3 * size + 7, "shorter": max(size // 3, 1),
                                 "equal": size, "empty": 0}[how])
    return spare


TREES = ["plain", "0-d", "empty leaf", "one leaf", "no leaf", "a view of a larger buffer",
         "a column-major leaf", "a strided leaf"]
SPARES = ["fresh", "longer", "shorter", "equal", "empty"]


@pytest.mark.parametrize("how", SPARES)
@pytest.mark.parametrize("kind", TREES)
def test_the_file_is_save_files_to_every_reader(tmp_path, monkeypatch, kind, how):
    tree = _tree(kind)
    want = _as_save_file_wrote(tmp_path, tree)
    spare = _spare(tmp_path, how, (tmp_path / "as-it-was.safetensors").stat().st_size)
    inode = spare.stat().st_ino if spare else None
    path = tmp_path / "delta-1.safetensors"
    monkeypatch.setattr(safetensors.numpy, "save_file", None)  # not on this path
    sent = compress.write_delta(path, tree, "none", over=spare)

    # safetensors loads it to the same keys, shapes and bytes ...
    got = load_file(str(path))
    assert sorted(got) == sorted(want)
    for key in want:
        assert got[key].dtype == np.float32 and got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes(), key
    # ... f32_layout takes it, the leaves in the tree's own order, to the last byte ...
    layout = f32_layout(path)
    assert layout is not None and list(layout) == list(tree)
    head = 8 + int.from_bytes(path.read_bytes()[:8], "little")
    size = path.stat().st_size
    assert head % 8 == 0 and size == head + sum(nbytes for _, _, nbytes in layout.values())
    # ... and the PS's and the worker's reader land it in their own buffers.
    read, stats = read_delta_into(path, SumBuffers().lease)
    assert stats.direct == stats.leaves == len(tree) and stats.bytes == size
    assert all(read[key].tobytes() == want[key].tobytes() for key in want)
    # What comes back is what a receiver decodes, and the caller's own memory.
    assert list(sent) == list(tree)
    for key, leaf in tree.items():
        assert sent[key].shape == want[key].shape
        assert np.shares_memory(sent[key], leaf) == bool(leaf.size and leaf.flags.c_contiguous)
    # Over a spare: the spare's inode under the delta's name, the other name gone.
    if spare is not None:
        assert path.stat().st_ino == inode and not spare.exists()
    assert sorted(p.name for p in tmp_path.iterdir()) == ["as-it-was.safetensors", path.name]


@pytest.mark.parametrize("piece", [1, 1000, 1 << 20])
def test_a_write_the_kernel_cuts_short_is_carried_on(tmp_path, monkeypatch, piece):
    tree = _tree("plain")
    want = _as_save_file_wrote(tmp_path, tree)
    write = os.write
    calls = []

    def short(fd, view):
        calls.append(len(view))
        return write(fd, view[:piece])

    monkeypatch.setattr(frame.os, "write", short)
    compress.write_delta(tmp_path / "d.safetensors", tree, "none")
    monkeypatch.undo()
    got = load_file(str(tmp_path / "d.safetensors"))
    assert all(got[key].tobytes() == want[key].tobytes() for key in want)
    assert len(calls) >= len(tree) + 1


def _old_way(tmp_path, tree, codec, ef=None):
    """The same call with no spare handed in, into a directory of its own."""
    (tmp_path / "old").mkdir(exist_ok=True)
    path = tmp_path / "old" / "delta.bin"
    return path, compress.write_delta(path, tree, codec, ef=ef)


def _not_as_it_lies(kind: str):
    tree = _tree("plain")
    if kind == "an int32 leaf":
        tree["steps"] = np.arange(5, dtype=np.int32)
        return tree, "none"
    if kind == "a float16 leaf":
        tree["ln_f/scale"] = tree["ln_f/scale"].astype(np.float16)
        return tree, "none"
    if kind == "a float64 leaf":
        tree["ln_f/scale"] = tree["ln_f/scale"].astype(np.float64)
        return tree, "none"
    if kind == "a bfloat16 leaf":
        tree["ln_f/scale"] = tree["ln_f/scale"].astype(ml_dtypes.bfloat16)
        return tree, "none"
    return tree, kind  # a codec


@pytest.mark.parametrize("handed", ["no spare", "a spare"])
@pytest.mark.parametrize("kind", [
    "an int32 leaf", "a float16 leaf", "a float64 leaf", "a bfloat16 leaf",
    "bf16", "int8", "int4",
])
def test_every_other_tree_is_written_as_it_always_was_and_the_spare_unlinked(
    tmp_path, kind, handed
):
    tree, codec = _not_as_it_lies(kind)
    quant = codec in compress.QUANT_CODECS
    # Two rounds through one residual each, the second with a spare: error
    # feedback has to come out the same whether or not a spare was handed in.
    ef_old, ef_new = (compress.ErrorFeedback(), compress.ErrorFeedback()) if quant else (None, None)
    old_path, old_sent = _old_way(tmp_path, tree, codec, ef_old)
    spare = _spare(tmp_path, "longer" if handed == "a spare" else "fresh", 5000)
    # Held open, the spare's inode cannot be given to the file that is written.
    held = os.open(spare, os.O_RDONLY) if spare else None
    path = tmp_path / "delta-1.bin"
    sent = compress.write_delta(path, tree, codec, ef=ef_new, over=spare)
    assert path.read_bytes() == old_path.read_bytes()
    if spare is not None:
        assert not spare.exists() and os.fstat(held).st_nlink == 0
        assert os.fstat(held).st_ino != path.stat().st_ino
        os.close(held)
    assert compress.is_frame(path) == quant
    assert list(sent) == list(old_sent)
    for key in old_sent:
        assert sent[key].dtype == old_sent[key].dtype
        assert sent[key].tobytes() == old_sent[key].tobytes(), key
    if codec == "none":
        # What the parent wrote for such a tree: save_file of the normalised leaves.
        want = _as_save_file_wrote(tmp_path, tree)
        got = load_file(str(path))
        assert all(got[k].dtype == want[k].dtype and got[k].tobytes() == want[k].tobytes()
                   for k in want)
    if quant:
        new, old = ef_new.state(), ef_old.state()
        assert new and sorted(new) == sorted(old)
        assert all(new[k].tobytes() == old[k].tobytes() for k in old)
        # ... and rides the next send, again the same.
        _, old_next = _old_way(tmp_path, tree, codec, ef_old)
        nxt = compress.write_delta(path, tree, codec, ef=ef_new,
                                   over=_spare(tmp_path, "shorter", 5000) if spare else None)
        assert all(nxt[k].tobytes() == old_next[k].tobytes() for k in old_next)


def test_an_unknown_codec_is_refused_and_takes_the_spare_with_it(tmp_path):
    spare = _spare(tmp_path, "equal", 100)
    with pytest.raises(ValueError, match="unknown wire codec"):
        compress.write_delta(tmp_path / "d", _tree("plain"), "zstd", over=spare)
    assert list(tmp_path.iterdir()) == []


@pytest.mark.parametrize("how,fails_at", [
    (how, at) for how in ("fresh", "longer", "shorter")
    for at in ("the head", "the second leaf", "the cut", "the rename")
    if how != "fresh" or at in ("the head", "the second leaf")  # neither cut nor renamed
])
def test_a_write_that_fails_half_way_leaves_no_file_under_either_name(
    tmp_path, monkeypatch, how, fails_at
):
    tree = _tree("plain")
    spare = _spare(tmp_path, how, 2000)
    path = tmp_path / "delta-1.safetensors"
    full = OSError(errno.ENOSPC, "No space left on device")
    if fails_at in ("the head", "the second leaf"):
        write, n = frame.write_exact, iter(range(100))

        def failing(fd, view):
            if next(n) == {"the head": 0, "the second leaf": 2}[fails_at]:
                os.write(fd, view[: len(view) // 2])
                raise full
            write(fd, view)

        monkeypatch.setattr(frame, "write_exact", failing)
    elif fails_at == "the cut":
        monkeypatch.setattr(frame.os, "ftruncate", lambda fd, n: (_ for _ in ()).throw(full))
    else:
        monkeypatch.setattr(frame.os, "replace", lambda a, b: (_ for _ in ()).throw(full))
    with pytest.raises(OSError, match="No space left"):
        compress.write_delta(path, tree, "none", over=spare)
    monkeypatch.undo()
    assert list(tmp_path.iterdir()) == []
    # The next write goes fresh and is whole.
    compress.write_delta(path, tree, "none")
    assert f32_layout(path) is not None and len(load_file(str(path))) == len(tree)


def test_a_spare_that_is_gone_is_an_error_and_not_a_silent_fresh_file(tmp_path):
    """The caller claimed it by a rename a moment before: a spare that cannot
    be opened is a fault of the caller's, and the span would say ``recycled``
    of a file that is not."""
    with pytest.raises(FileNotFoundError):
        compress.write_delta(tmp_path / "d", _tree("plain"), "none", over=tmp_path / "gone")
    assert list(tmp_path.iterdir()) == []
