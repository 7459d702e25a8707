"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide, 2.1): the
same script and code path at a tiny size. Its verdict must be a failure here,
and for the right reason."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# The smallest GPT-2 the CLI accepts: the slices stay 1024 long, ids < 256.
TINY = [
    "job.model_config.n_layer=1", "job.model_config.n_embd=32",
    "job.model_config.n_head=2", "job.model_config.vocab_size=256",
    "job.max_batch_size=2", "job.avg_samples_between_updates=8",
]


def _run(script: Path, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, timeout=240, cwd=str(script.parent),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run(tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no hypha_tpu package" in last["error"]


def test_chip_smoke_on_cpu_completes_the_job_then_fails_the_device_check():
    """The rehearsal: same script, same code path, tiny model. The job runs
    to its end through the four CLI roles; the verdict is still a failure,
    and only because the worker's device is not a TPU."""
    sets = [x for s in TINY for x in ("--set", s)]
    r = _run(REPO / "chip_smoke.py", "--seed", "0", *sets)
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    by_phase = {x["phase"]: x for x in lines if "phase" in x}
    assert r.returncode != 0, r.stdout
    assert by_phase["job"]["completed_rounds"] == 2, r.stdout
    assert by_phase["worker"]["device"]["platform"] == "cpu"
    checks = {k: v for k, v in by_phase["checks"].items() if k != "phase"}
    failed = sorted(k for k, ok in checks.items() if not ok)
    assert failed == ["attention_is_compiled_flash", "device_is_tpu"], checks
    assert lines[-1]["ok"] is False
    assert set(lines[-1]) == {"ok", "error", "device"}
