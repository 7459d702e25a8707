"""The device policy (hypha_tpu/hw.py) and the entry points that hold to it:
one test per rule. chip_smoke.py's own rehearsal is tests/test_tpu_smoke.py."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import pytest

from hypha_tpu import hw
from hypha_tpu.executor.training import _build_mesh

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize(
    "backend,expected", [("tpu", True), ("cpu", False), ("madeup", False)]
)
def test_only_the_tpu_backend_is_the_accelerator(monkeypatch, backend, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert hw.is_accelerator() is expected
    assert hw.interpret_default() is (not expected)


def test_build_mesh_raises_on_too_few_devices():
    with pytest.raises(ValueError, match="needs 16 devices"):
        _build_mesh({"fsdp": 4, "tp": 4})


@pytest.mark.parametrize("sharding", [None, {}, {"dp": 1}])
def test_build_mesh_unsharded_is_none(sharding):
    assert _build_mesh(sharding) is None


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert hw.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert hw.enable_compile_cache() == want
    assert [value for _option, value in calls] == [want]


def test_control_plane_roles_import_no_jax():
    """Gateway, data node, scheduler and the parameter-server worker run
    beside the one process that holds the chip; none may load jax by import
    (chip_smoke.py checks the running processes for the mapped libtpu)."""
    code = (
        "import sys\n"
        "import hypha_tpu.cli, hypha_tpu.gateway, hypha_tpu.data_node\n"
        "import hypha_tpu.scheduler.orchestrator, hypha_tpu.scheduler.metrics_bridge\n"
        "import hypha_tpu.worker.runtime, hypha_tpu.worker.ps_executor\n"
        "import hypha_tpu.telemetry, hypha_tpu.native, hypha_tpu.codec\n"
        "sys.exit(int('jax' in sys.modules))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=str(REPO), capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
