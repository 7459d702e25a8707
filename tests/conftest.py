"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's testing philosophy (SURVEY.md §4): no real cluster in
CI — multi-chip behavior is exercised on host-platform virtual devices, the
distributed control plane on paused/injected clocks, and protocol logic on an
in-process fake transport.

Tests run on the CPU: JAX_PLATFORMS=cpu is set before jax is imported,
unless HYPHA_ALLOW_TPU=1 targets the on-chip kernel tests.
"""

import os
import sys

import pytest

# Escape hatch for the ON-HARDWARE kernel tests (tests/test_tpu_hw.py):
# `HYPHA_ALLOW_TPU=1 pytest tests/test_tpu_hw.py` leaves the default backend
# alone so the pallas kernels are validated on the chip. The hatch only opens
# when the hardware tests are the TARGETED paths — a leftover exported var
# must not send the whole suite onto the chip.
_ALLOW_TPU = os.environ.get("HYPHA_ALLOW_TPU") == "1" and any(
    "test_tpu_hw" in a for a in sys.argv
)

if not _ALLOW_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Entry points turn the persistent compile cache on (hw.enable_compile_cache)
# and several run inside the test process; one test's compile must not reach
# another through the disk, so the cache itself stays off here and in the
# subprocesses tests start.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-node end-to-end tests (tens of seconds)"
    )
    config.addinivalue_line(
        "markers",
        "fault: chaos/fault-injection tests (hypha_tpu.ft) — filter with "
        "-m fault / -m 'not fault'",
    )


@pytest.fixture(params=["native", "numpy"])
def kernel_backend(request, monkeypatch):
    """Both implementations of the flat f32 kernels: the C++ library, and
    the numpy fallback a host without a toolchain runs."""
    from hypha_tpu import native

    if request.param == "numpy":
        monkeypatch.setattr(native, "_load", lambda: None)
    assert native.native_available() == (request.param == "native")
    return request.param
