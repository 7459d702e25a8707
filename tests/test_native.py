"""Native (C++) runtime layer tests: SafeTensors mmap reader/writer parity
with the Python safetensors library, the full native outer step vs the
Python path, sendfile data plane, and malformed-input rejection."""

from __future__ import annotations

import os
import socket
import threading

import numpy as np
import pytest
from safetensors.numpy import load_file, save_file

from hypha_tpu import native


pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="no native toolchain"
)


def _write_st(path, tensors):
    save_file(tensors, str(path))
    return path


def _mean_then_nesterov(srcs, w, momentum, lr, mu):
    """The plain two-kernel reference: weighted mean, then Nesterov."""
    return native.nesterov_update(momentum, native.weighted_sum(srcs, w), lr, mu)


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


def test_native_outer_step_matches_python_kernels(tmp_path):
    rng = np.random.default_rng(5)
    shapes = {"x/w": (8, 4), "y/b": (16,)}
    n_workers = 3
    paths = []
    deltas = []
    for k in range(n_workers):
        t = {n: rng.standard_normal(s).astype(np.float32) for n, s in shapes.items()}
        deltas.append(t)
        paths.append(_write_st(tmp_path / f"d{k}.safetensors", t))
    w = np.asarray([3.0, 1.0, 2.0], np.float32)
    w = w / w.sum()
    lr, mu = 0.7, 0.9

    m_out = tmp_path / "m.safetensors"
    u_out = tmp_path / "u.safetensors"
    total = native.ps_outer_step(paths, w, None, m_out, u_out, lr, mu)
    assert total == sum(int(np.prod(s)) for s in shapes.values())

    update = load_file(str(u_out))
    momentum = load_file(str(m_out))
    for name in shapes:
        srcs = [d[name] for d in deltas]
        m_ref, u_ref = _mean_then_nesterov(
            srcs, w, np.zeros(srcs[0].size, np.float32), lr, mu
        )
        np.testing.assert_allclose(update[name].ravel(), u_ref, rtol=1e-5)
        np.testing.assert_allclose(momentum[name].ravel(), m_ref, rtol=1e-5)

    # Second round consumes the momentum file
    total2 = native.ps_outer_step(paths, w, m_out, m_out, u_out, lr, mu)
    assert total2 == total
    momentum2 = load_file(str(m_out))
    for name in shapes:
        srcs = [d[name] for d in deltas]
        m1, _ = _mean_then_nesterov(
            srcs, w, np.zeros(srcs[0].size, np.float32), lr, mu
        )
        m2_ref, _ = _mean_then_nesterov(srcs, w, m1, lr, mu)
        np.testing.assert_allclose(momentum2[name].ravel(), m2_ref, rtol=1e-5)


def test_native_outer_step_rejects_mismatch(tmp_path):
    a = _write_st(tmp_path / "a.safetensors", {"x": np.zeros((4,), np.float32)})
    b = _write_st(tmp_path / "b.safetensors", {"x": np.zeros((5,), np.float32)})
    with pytest.raises(ValueError, match="mismatch"):
        native.ps_outer_step(
            [a, b], np.asarray([0.5, 0.5], np.float32),
            None, tmp_path / "m", tmp_path / "u", 0.7, 0.9,
        )
    c = _write_st(tmp_path / "c.safetensors", {"x": np.zeros((4,), np.int64)})
    with pytest.raises(ValueError, match="unsupported delta dtype"):
        native.ps_outer_step(
            [c], np.asarray([1.0], np.float32),
            None, tmp_path / "m", tmp_path / "u", 0.7, 0.9,
        )


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


def test_send_file_fd_socketpair(tmp_path):
    payload = os.urandom(1 << 20) + b"tail"
    src = tmp_path / "blob.bin"
    src.write_bytes(payload)
    a, b = socket.socketpair()
    received = bytearray()

    def reader():
        while True:
            chunk = b.recv(1 << 16)
            if not chunk:
                return
            received.extend(chunk)

    t = threading.Thread(target=reader)
    t.start()
    try:
        sent = native.send_file_fd(a.fileno(), src)
        assert sent == len(payload)
    finally:
        a.close()
        t.join(10)
        b.close()
    assert bytes(received) == payload


def test_send_file_fd_missing_file(tmp_path):
    a, b = socket.socketpair()
    try:
        with pytest.raises(OSError):
            native.send_file_fd(a.fileno(), tmp_path / "nope")
    finally:
        a.close()
        b.close()
