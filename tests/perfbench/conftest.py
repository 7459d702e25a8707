"""Fixtures for the harness's tests (helpers: ``perfbench_helpers.py``)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:  # the benchmark's package, for the reader tests
    sys.path.insert(0, str(REPO))

from perfbench_helpers import make_root  # noqa: E402


@pytest.fixture
def bench_root(tmp_path: Path) -> Path:
    return make_root(tmp_path)
