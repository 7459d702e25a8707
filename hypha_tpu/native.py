"""ctypes bindings for the native (C++) runtime layer, with numpy fallback.

The reference's native layer is its Rust crates; the numerical hot spot is
the parameter server's outer step (SURVEY.md §2.9: candle-core averaging +
Nesterov over mmapped SafeTensors). The C++ equivalents live in
``native/``:

  * ``hypha_ps.cpp``          — flat f32 kernels: the PS's fold and outer
    step (a delta scaled into the round's sum, mean + Nesterov, both in
    place and threaded) and the plain Nesterov form the tests hold the
    outer step to;
  * ``hypha_safetensors.cpp`` — mmap'd SafeTensors reader (own JSON header
    parser) behind :class:`SafeTensorsView`;
  * ``hypha_quant.cpp``       — chunkwise int8/int4 quantization for the
    compressed delta transport (hypha_tpu.compress), bit-exact against
    the numpy fallback there.

Everything is compiled on first use with the system g++ into one shared
library and cached. Environments without a toolchain transparently fall
back to numpy/Python paths — results are identical.
"""

from __future__ import annotations

import ctypes
import logging
import subprocess
from pathlib import Path

import numpy as np

__all__ = [
    "nesterov_update",
    "fused_mean_nesterov",
    "fold_scaled",
    "native_available",
    "SafeTensorsView",
    "quant_chunks",
    "dequant_chunks",
]

log = logging.getLogger("hypha.native")

_REPO = Path(__file__).resolve().parent.parent
_SRCS = [
    _REPO / "native" / "hypha_ps.cpp",
    _REPO / "native" / "hypha_safetensors.cpp",
    _REPO / "native" / "hypha_quant.cpp",
]
_SO = _REPO / "native" / "build" / "libhypha_native.so"

_lib: ctypes.CDLL | None = None
_tried = False

_F32P = ctypes.POINTER(ctypes.c_float)


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        newest_src = max(src.stat().st_mtime for src in _SRCS)
        if not _SO.exists() or _SO.stat().st_mtime < newest_src:
            _SO.parent.mkdir(parents=True, exist_ok=True)
            subprocess.run(
                [
                    "g++", "-O3", "-march=native", "-std=c++17", "-pthread",
                    "-shared", "-fPIC", *map(str, _SRCS), "-o", str(_SO),
                ],
                check=True,
                capture_output=True,
                timeout=300,
            )
        lib = ctypes.CDLL(str(_SO))
        lib.nesterov_update_f32.argtypes = [
            _F32P, _F32P, _F32P, ctypes.c_int64, ctypes.c_float, ctypes.c_float,
        ]
        lib.fused_mean_nesterov_inplace_f32.argtypes = [
            _F32P, ctypes.c_float, _F32P, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_int64,
        ]
        lib.fused_mean_nesterov_inplace_f32.restype = ctypes.c_int64
        lib.fold_scaled_f32.argtypes = [
            _F32P, _F32P, ctypes.c_float, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int64,
        ]
        lib.fold_scaled_f32.restype = ctypes.c_int64
        lib.st_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.st_open.restype = ctypes.c_void_p
        lib.st_close.argtypes = [ctypes.c_void_p]
        lib.st_count.argtypes = [ctypes.c_void_p]
        lib.st_count.restype = ctypes.c_int64
        lib.st_name.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.st_name.restype = ctypes.c_char_p
        lib.st_tensor.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ]
        lib.st_tensor.restype = ctypes.c_void_p
        _U8P = ctypes.POINTER(ctypes.c_uint8)
        lib.quant_chunks_f32.argtypes = [
            _F32P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int, _U8P, _F32P,
        ]
        lib.quant_chunks_f32.restype = ctypes.c_int64
        lib.dequant_chunks_f32.argtypes = [
            _U8P, _F32P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int, _F32P,
        ]
        lib.dequant_chunks_f32.restype = ctypes.c_int64
        _lib = lib
    except (subprocess.SubprocessError, OSError, FileNotFoundError) as e:
        log.info("native kernels unavailable (%s); using numpy", e)
        _lib = None
    return _lib


def native_available() -> bool:
    return _load() is not None


def _as_f32(a: np.ndarray) -> np.ndarray:
    out = np.ascontiguousarray(a, dtype=np.float32)
    return out


def _ptr(a: np.ndarray) -> "ctypes._Pointer":
    return a.ctypes.data_as(_F32P)


def nesterov_update(
    momentum: np.ndarray, grad: np.ndarray, lr: float, mu: float
) -> tuple[np.ndarray, np.ndarray]:
    """m <- mu*m + g; update <- lr*(mu*m + g). Returns (momentum, update).

    The tests' reference for :func:`fused_mean_nesterov`: the in-place pass
    is held to ``RoundAccum.mean()`` followed by this, on the same backend,
    every bit (g++ contracts ``mu*m + g`` to a fused multiply-add, so numpy
    cannot stand in for the native form). No caller in the program.
    """
    m = _as_f32(momentum).ravel().copy()
    g = _as_f32(grad).ravel()
    lib = _load()
    if lib is None:
        m = mu * m + g
        return m, (lr * (mu * m + g)).astype(np.float32)
    upd = np.empty_like(g)
    lib.nesterov_update_f32(_ptr(m), _ptr(g), _ptr(upd), g.size, lr, mu)
    return m, upd


# The numpy fallback's block: small enough that its one scratch buffer is
# not a parameter-sized temporary.
_FALLBACK_BLOCK = 1 << 20


def _in_place_f32(a: np.ndarray, what: str) -> np.ndarray:
    if a.dtype != np.float32 or not a.flags.c_contiguous or not a.flags.writeable:
        raise ValueError(f"{what} must be a writable C-contiguous float32 array")
    return a.reshape(-1)


def fused_mean_nesterov(
    acc: np.ndarray,
    denom: float,
    momentum: np.ndarray,
    lr: float,
    mu: float,
    threads: int = 1,
) -> int:
    """The PS's outer step over one leaf, one pass, IN PLACE:
    ``g = acc / denom; m <- mu*m + g; acc <- lr*(mu*m + g)``.

    ``acc`` is the round's partial sum Σ samples·Δθ and comes back as the
    update; ``momentum`` is updated where it lies. Nothing leaf-sized is
    allocated. Bit-equal to ``RoundAccum.mean()`` followed by
    :func:`nesterov_update` (same expressions, same order), whatever
    ``threads`` is: the kernel splits a leaf's elements, and a leaf under
    about a million elements runs on the caller alone. Returns the number
    of threads that ran (1 on the numpy fallback).
    """
    a = _in_place_f32(acc, "acc")
    m = _in_place_f32(momentum, "momentum")
    if a.size != m.size:
        # The flat kernel trusts n: a short tensor must fail here, not
        # read out of bounds.
        raise ValueError(f"acc size {a.size} != momentum size {m.size}")
    denom = np.float32(denom)
    lib = _load()
    if lib is not None:
        return int(
            lib.fused_mean_nesterov_inplace_f32(
                _ptr(a), denom, _ptr(m), a.size, lr, mu, threads
            )
        )
    scratch = np.empty(min(a.size, _FALLBACK_BLOCK), np.float32)
    for lo in range(0, a.size, _FALLBACK_BLOCK):
        g, mm = a[lo:lo + _FALLBACK_BLOCK], m[lo:lo + _FALLBACK_BLOCK]
        tmp = scratch[:g.size]
        np.divide(g, denom, out=g)
        np.multiply(mm, mu, out=mm)
        np.add(mm, g, out=mm)
        np.multiply(mm, mu, out=tmp)
        np.add(tmp, g, out=g)
        np.multiply(g, lr, out=g)
    return 1


def fold_scaled(
    acc: np.ndarray,
    x: np.ndarray,
    scale: float,
    overwrite: bool,
    threads: int = 1,
) -> int:
    """One delta leaf folded into the round's partial sum where the sum
    lies: ``acc <- scale * x`` when ``overwrite`` (a round's first fold),
    else ``acc <- acc + scale * x``, the product rounded to f32 before the
    sum. Bit-equal to numpy's ``prev += np.float32(scale) * x`` whatever
    ``threads`` is. When overwriting, ``x`` may be ``acc`` itself (bytes
    read straight into the sum's buffer are scaled where they landed).
    Nothing leaf-sized is allocated. Returns the number of threads that
    ran (1 on the numpy fallback).
    """
    a = _in_place_f32(acc, "acc")
    if x.dtype != np.float32 or not x.flags.c_contiguous:
        raise ValueError("x must be a C-contiguous float32 array")
    v = x.reshape(-1)
    if a.size != v.size:
        raise ValueError(f"acc size {a.size} != x size {v.size}")
    scale = np.float32(scale)
    lib = _load()
    if lib is not None:
        return int(
            lib.fold_scaled_f32(
                _ptr(a), _ptr(v), scale, a.size, int(overwrite), threads
            )
        )
    with np.errstate(all="ignore"):  # as silent about inf and NaN as the kernel
        if overwrite:
            np.multiply(v, scale, out=a)
            return 1
        scratch = np.empty(min(a.size, _FALLBACK_BLOCK), np.float32)
        for lo in range(0, a.size, _FALLBACK_BLOCK):
            dst = a[lo:lo + _FALLBACK_BLOCK]
            tmp = scratch[:dst.size]
            np.multiply(v[lo:lo + _FALLBACK_BLOCK], scale, out=tmp)
            np.add(dst, tmp, out=dst)
    return 1


# ---------------------------------------------------------------------------
# Native SafeTensors
# ---------------------------------------------------------------------------

_DTYPES = {
    "F32": np.float32,
    "F64": np.float64,
    "F16": np.float16,
    "I64": np.int64,
    "I32": np.int32,
    "I16": np.int16,
    "I8": np.int8,
    "U8": np.uint8,
    "BOOL": np.bool_,
}


class SafeTensorsView:
    """Zero-copy mmap'd SafeTensors reader over the native parser.

    Tensors come back as numpy views into the mapping (read-only); the
    mapping lives until close(). Raises OSError when the native library is
    unavailable — callers fall back to safetensors.numpy.
    """

    def __init__(self, path: str | Path) -> None:
        lib = _load()
        if lib is None:
            raise OSError("native library unavailable")
        err = ctypes.create_string_buffer(256)
        self._lib = lib
        self._handle = lib.st_open(str(path).encode(), err, len(err))
        if not self._handle:
            raise ValueError(f"st_open({path}): {err.value.decode()}")

    def _live_handle(self):
        # After close() the C layer would dereference NULL -> SIGSEGV;
        # surface a Python error instead.
        if not self._handle:
            raise ValueError("SafeTensorsView is closed")
        return self._handle

    def keys(self) -> list[str]:
        handle = self._live_handle()
        n = self._lib.st_count(handle)
        return [self._lib.st_name(handle, i).decode() for i in range(n)]

    def tensor(self, name: str) -> np.ndarray:
        handle = self._live_handle()
        nbytes = ctypes.c_int64()
        dtype_buf = ctypes.create_string_buffer(16)
        shape = (ctypes.c_int64 * 16)()
        ndim = ctypes.c_int()
        ptr = self._lib.st_tensor(
            handle, name.encode(), ctypes.byref(nbytes),
            dtype_buf, len(dtype_buf), shape, 16, ctypes.byref(ndim),
        )
        if not ptr:
            raise KeyError(name)
        dtype_name = dtype_buf.value.decode()
        if dtype_name == "BF16":
            # ml_dtypes ships with jax; imported lazily so the PS path (pure
            # f32) keeps working in stripped environments.
            import ml_dtypes

            dtype = ml_dtypes.bfloat16
        else:
            dtype = _DTYPES.get(dtype_name)
        if dtype is None:
            raise ValueError(f"unsupported dtype {dtype_name!r} for {name}")
        buf = (ctypes.c_char * nbytes.value).from_address(ptr)
        # The array's base chain ends at `buf`; anchor the view there so a
        # GC'd SafeTensorsView can't munmap pages a live array still reads
        # (explicit close() remains the caller's contract).
        buf._owner = self
        arr = np.frombuffer(buf, dtype=dtype)
        # The mapping is PROT_READ: an in-place write through a writable
        # view would SIGSEGV, not raise. Make numpy enforce it.
        arr.flags.writeable = False
        dims = tuple(shape[i] for i in range(ndim.value))
        return arr.reshape(dims)

    def close(self) -> None:
        if self._handle:
            self._lib.st_close(self._handle)
            self._handle = None

    def __del__(self) -> None:  # leak guard; safe: arrays anchor self via buf
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self) -> "SafeTensorsView":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


_QUANT_BITS = {"int8": 8, "int4": 4}


def _u8ptr(a: np.ndarray) -> "ctypes._Pointer":
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def quant_chunks(
    src: np.ndarray, chunk: int, codec: str,
    payload_out: np.ndarray, scales_out: np.ndarray,
) -> bool:
    """Chunkwise quantize ``src`` (contiguous f32) in place into the
    caller's payload/scales buffers. Returns False when the native library
    is unavailable (caller runs the bit-exact numpy spec instead)."""
    lib = _load()
    if lib is None:
        return False
    wrote = lib.quant_chunks_f32(
        _ptr(src), src.size, chunk, _QUANT_BITS[codec],
        _u8ptr(payload_out), _ptr(scales_out),
    )
    if wrote < 0:
        raise ValueError(f"quant_chunks_f32 rejected args (codec {codec})")
    return True


def dequant_chunks(
    payload: np.ndarray, scales: np.ndarray, n: int, chunk: int, codec: str,
    dst: np.ndarray,
) -> bool:
    """Invert :func:`quant_chunks` into ``dst`` (f32, ``n`` elements).
    Returns False when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return False
    got = lib.dequant_chunks_f32(
        _u8ptr(payload), _ptr(scales), n, chunk, _QUANT_BITS[codec], _ptr(dst)
    )
    if got < 0:
        raise ValueError(f"dequant_chunks_f32 rejected args (codec {codec})")
    return True
