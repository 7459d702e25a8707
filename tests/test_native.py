"""Native (C++) runtime layer tests: SafeTensors mmap reader parity with the
Python safetensors library, the PS's in-place kernels against their plain
references bit for bit, malformed-input rejection, and the seam the
benchmark's harness leans on (its probe, and the symbols the loader binds)."""

from __future__ import annotations

import inspect
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from safetensors.numpy import save_file

from hypha_tpu import native

REPO = Path(__file__).resolve().parent.parent


pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="no native toolchain"
)


def _write_st(path, tensors):
    save_file(tensors, str(path))
    return path


def test_safetensors_view_parity(tmp_path):
    tensors = {
        "a/w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b/count": np.asarray([7], np.int64),
        "c": np.random.default_rng(0).standard_normal((2, 2, 2)).astype(np.float32),
    }
    p = _write_st(tmp_path / "t.safetensors", tensors)
    with native.SafeTensorsView(p) as view:
        assert sorted(view.keys()) == sorted(tensors)
        for name, want in tensors.items():
            got = view.tensor(name)
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
        with pytest.raises(KeyError):
            view.tensor("missing")


def test_safetensors_view_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.safetensors"
    bad.write_bytes(b"\xff" * 64)
    with pytest.raises(ValueError):
        native.SafeTensorsView(bad)
    # header length overrunning the file
    import struct

    trunc = tmp_path / "trunc.safetensors"
    trunc.write_bytes(struct.pack("<Q", 1 << 40) + b"{}")
    with pytest.raises(ValueError):
        native.SafeTensorsView(trunc)


@pytest.mark.parametrize("threads", [1, 2, 7])
@pytest.mark.parametrize("size", [1, 4096, 999_983, 2**22 + 3])
def test_fused_in_place_pass_is_bit_equal_to_mean_then_nesterov(kernel_backend, size, threads):
    """The PS's one pass (division and Nesterov fused, written over its
    inputs) against RoundAccum.mean() + nesterov_update, two rounds, on the
    same backend: every bit, whatever the thread count."""
    from hypha_tpu.stream.accum import RoundAccum

    rng = np.random.default_rng(size * 8 + threads)
    lr, mu = 0.7, 0.9
    m_ref = np.zeros(size, np.float32)
    m = np.zeros(size, np.float32)
    for _ in range(2):
        accum = RoundAccum()
        for samples in (24.0, 8.0, 40.0):
            accum.fold_tree({"w": rng.standard_normal(size).astype(np.float32)}, samples)
        m_ref, u_ref = native.nesterov_update(m_ref, accum.mean()["w"], lr, mu)
        tree, denom = accum.take()
        acc, m_before = tree["w"], m
        used = native.fused_mean_nesterov(acc, denom, m, lr, mu, threads)
        assert m is m_before and acc is tree["w"]  # in place: no new arrays
        assert acc.tobytes() == u_ref.tobytes()
        assert m.tobytes() == m_ref.tobytes()
        if kernel_backend == "native":
            assert used == max(1, min(threads, size >> 19))
        else:
            assert used == 1


@pytest.mark.parametrize("fault", ["short_momentum", "float64", "read_only", "strided"])
def test_fused_in_place_pass_refuses_what_it_cannot_write_over(kernel_backend, fault):
    acc = np.ones(64, np.float32)
    m = np.zeros(64, np.float32)
    if fault == "short_momentum":
        m = np.zeros(63, np.float32)
    elif fault == "float64":
        acc = acc.astype(np.float64)
    elif fault == "read_only":
        m.flags.writeable = False
    else:
        acc = np.ones(128, np.float32)[::2]
    before = (acc.copy(), m.copy())
    with pytest.raises(ValueError):
        native.fused_mean_nesterov(acc, 4.0, m, 0.7, 0.9, 2)
    np.testing.assert_array_equal(acc, before[0])
    np.testing.assert_array_equal(m, before[1])


def test_fused_in_place_pass_keeps_the_leaf_shape(kernel_backend):
    acc = np.full((3, 5), 8.0, np.float32)
    m = np.zeros((3, 5), np.float32)
    native.fused_mean_nesterov(acc, 4.0, m, 0.5, 0.5, 1)
    assert acc.shape == m.shape == (3, 5)
    np.testing.assert_array_equal(m, np.full((3, 5), 2.0, np.float32))
    np.testing.assert_array_equal(acc, np.full((3, 5), 1.5, np.float32))


def _bits(a: np.ndarray) -> np.ndarray:
    """Every bit of an f32 array, NaNs as one value: which NaN an
    operation hands on is the processor's choice, not the kernel's."""
    out = np.ascontiguousarray(a, np.float32).reshape(-1).view(np.uint32).copy()
    out[np.isnan(a.reshape(-1))] = 0x7FC00000
    return out


_SPECIAL = np.asarray(
    [-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-45, -1e-45, 3.4e38, -3.4e38], np.float32
)


@pytest.mark.parametrize("threads", [1, 2])
@pytest.mark.parametrize("size", [1, 4096, 2**19 - 1, 2**20 + 7, 2**22 + 3])
def test_fold_scaled_is_bit_equal_to_the_numpy_expression(kernel_backend, size, threads):
    """The fold's kernel against ``prev = s * x`` and ``prev += s * x``
    (numpy: the product rounded, then the sum), an overwrite, two adds and
    an un-fold in a row with zeros' signs, infinities and NaNs among the
    values, over a leaf under and over what one thread takes."""
    rng = np.random.default_rng(size + threads)
    xs = [rng.standard_normal(size).astype(np.float32) for _ in range(4)]
    for x in xs:
        k = min(size, _SPECIAL.size)
        x[rng.choice(size, k, replace=False)] = _SPECIAL[:k]
    scales = [np.float32(24.0), np.float32(7.0), np.float32(1.0), np.float32(-24.0)]
    acc = np.full(size, np.nan, np.float32)  # stale: the overwrite ignores it
    want = None
    for n, (x, scale) in enumerate(zip(xs, scales)):
        x_before = x.copy()
        with np.errstate(invalid="ignore", over="ignore"):
            if want is None:
                want = scale * x
            else:
                want += scale * x
        used = native.fold_scaled(acc, x, scale, overwrite=n == 0, threads=threads)
        assert np.array_equal(_bits(acc), _bits(want)), n
        assert x.tobytes() == x_before.tobytes()
        assert used == (max(1, min(threads, size >> 19)) if kernel_backend == "native" else 1)


def test_fold_scaled_scales_bytes_where_they_landed(kernel_backend):
    """Overwriting, the source may be the sum's own buffer."""
    rng = np.random.default_rng(3)
    acc = rng.standard_normal((3, 2**19 + 1)).astype(np.float32)
    acc[0, :4] = [-0.0, 0.0, np.inf, np.nan]
    want = np.float32(12.5) * acc
    native.fold_scaled(acc, acc, 12.5, overwrite=True, threads=2)
    assert acc.shape == want.shape and np.array_equal(_bits(acc), _bits(want))


def test_fold_scaled_rounds_the_product_before_the_sum(kernel_backend):
    """Two roundings: a fused multiply-add would keep the product's low
    bits and give 2**-24 here, not 0. g++ contracts by default, so the
    kernel's adding loop turns it off for itself; this holds it."""
    x = np.full(64, 1 + 2.0**-12, np.float32)
    acc = np.full(64, -(1 + 2.0**-11), np.float32)
    native.fold_scaled(acc, x, np.float32(1 + 2.0**-12), overwrite=False)
    assert acc.tobytes() == np.zeros(64, np.float32).tobytes()


@pytest.mark.parametrize("fault", ["short_x", "float64_acc", "float64_x", "read_only", "strided"])
def test_fold_scaled_refuses_what_it_cannot_write_over(kernel_backend, fault):
    acc, x = np.ones(64, np.float32), np.ones(64, np.float32)
    if fault == "short_x":
        x = np.ones(63, np.float32)
    elif fault == "float64_acc":
        acc = acc.astype(np.float64)
    elif fault == "float64_x":
        x = x.astype(np.float64)
    elif fault == "read_only":
        acc.flags.writeable = False
    else:
        x = np.ones(128, np.float32)[::2]
    before = acc.copy()
    with pytest.raises(ValueError):
        native.fold_scaled(acc, x, 2.0, overwrite=False, threads=2)
    np.testing.assert_array_equal(acc, before)


def test_the_benchmarks_probe_builds_and_calls_both_libraries():
    """``perfbench/cluster.py`` runs this text in a child before any role
    starts and refuses the run unless it ends in ``probed`` with both
    libraries built: the program's side of that contract, held here."""
    from perfbench.cluster import PROBE

    r = subprocess.run(
        [sys.executable, "-c", PROBE], cwd=str(REPO), capture_output=True,
        text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == "probed"
    assert json.loads(lines[0]) == {"ps_kernels": True, "cbor_codec": True}


def _exported_by_the_sources() -> set[str]:
    """Every non-static function defined between ``extern "C" {`` and its
    ``}`` in the translation units of ``libhypha_native.so`` (a definition
    starts in column 0 there, with its name on that line)."""
    names: set[str] = set()
    for src in native._SRCS:
        for block in re.findall(r'extern "C" \{\n(.*?)\n\}  // extern "C"', src.read_text(), re.S):
            names.update(re.findall(r"^(?!.*\bstatic\b)\w.*?(\w+)\(", block, re.M))
    return names


def _bound_by_the_loader() -> set[str]:
    return set(re.findall(r"\blib\.(\w+)\.(?:argtypes|restype)\b", inspect.getsource(native._load)))


def test_the_loader_binds_what_the_sources_export_and_nothing_else():
    """``_load`` binds every symbol in one ``try`` that does not catch
    ``AttributeError``: a function taken out of the C++ and left there turns
    ``import``'s first call into a crash of every role, and one exported and
    unbound is a kernel nobody can call. Both sets, and the built library."""
    exported, bound = _exported_by_the_sources(), _bound_by_the_loader()
    assert {"fused_mean_nesterov_inplace_f32", "fold_scaled_f32", "st_open"} <= exported
    assert exported == bound
    lib = native._load()
    for name in sorted(bound):
        assert hasattr(lib, name), name
