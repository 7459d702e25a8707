"""A checkout in miniature for the harness's tests: the benchmark's own
files copied, the program linked, and a manifest whose one cell is tiny."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
DATA = Path(__file__).resolve().parent / "data"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    root.mkdir()
    shutil.copytree(
        REPO / "perfbench", root / "perfbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    for name in ("hypha_tpu", "native"):
        (root / name).symlink_to(REPO / name)
    shutil.copy(DATA / "tiny-gpt2.json", root / "perfbench" / "configs" / "tiny-gpt2.json")
    shutil.copy(DATA / "tiny.h4.json", root / "perfbench" / "traffic" / "tiny.h4.json")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-gpt2", "source": "test only", "reduced": [],
        "file": "perfbench/configs/tiny-gpt2.json", "why": "CPU rehearsal",
    }]
    manifest["workloads"] = [{
        "name": "tiny-gpt2.h4", "config": "tiny-gpt2", "traffic": "tiny.h4",
        "chips": 1, "why": "CPU rehearsal",
    }]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return root


def recorded_cell():
    """The cell the logs under ``data/recorded/`` were taken from:
    gpt2-medium.sync-h8, measured in PR 23 and not admitted (PERF.md 6). Its
    configuration and mix are fixtures here, not files of the benchmark; it
    reports the manifest's metrics."""
    from perfbench import manifest

    m = manifest.load_manifest(REPO)
    cell = manifest.resolve(m["workloads"][0]["name"], REPO, m)
    return dataclasses.replace(
        cell, name="gpt2-medium.sync-h8",
        config=json.loads((DATA / "gpt2-medium.json").read_text()),
        traffic=manifest.load_traffic(DATA / "gpt2-medium.sync-h8.json"),
    )


def bench_cmd(root: Path, *args: str) -> list[str]:
    return [sys.executable, str(root / "perfbench" / "run.py"), *args]


def bench_env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu")


def run_bench(root: Path, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        bench_cmd(root, *args), capture_output=True, text=True, timeout=300,
        cwd=str(root), env=bench_env(),
    )


def rehearsal_result(stderr: str) -> dict:
    """What the run would have printed as its last line on a TPU."""
    lines = [x for x in stderr.splitlines() if x.startswith("rehearsal: ")]
    assert len(lines) == 1, stderr[-3000:]
    return json.loads(lines[0].removeprefix("rehearsal: "))


def notes(stdout: str) -> dict:
    """The earlier lines of stdout by phase (the last of each)."""
    out = {}
    for line in stdout.splitlines():
        obj = json.loads(line)
        out[obj.get("phase")] = obj
    return out


# On a machine that runs five other tests beside a rehearsal, a tiny step can
# take half of what round 0's compiling one did: the one check that is a time.
BY_THE_CLOCK = {"no_recompile_in_window"}


def failing_checks(stdout: str) -> set[str]:
    """The checks a rehearsal broke, the one that is a time apart."""
    checks = {k: v for k, v in notes(stdout)["checks"].items() if k != "phase"}
    return {k for k, ok in checks.items() if not ok} - BY_THE_CLOCK


def all_rounds_sound(result: dict, stdout: str) -> bool:
    """No measured round broke a check, unless it was the one by the clock."""
    checks = notes(stdout)["checks"]
    return result["failed"] == 0 or not checks["no_recompile_in_window"]


def processes_under(root: Path) -> list[str]:
    """Command lines of live processes whose working directory is ``root``."""
    out = []
    for proc in Path("/proc").iterdir():
        if not proc.name.isdigit():
            continue
        try:
            if Path(os.readlink(proc / "cwd")) == root:
                out.append((proc / "cmdline").read_bytes().replace(b"\0", b" ").decode())
        except OSError:
            continue
    return out
