"""CLI tests: init/probe/run subcommands and the quickstart topology as real
OS processes — the reference's manual quickstart (docs/quickstart.md:
gateway + scheduler + workers + data node as local processes) as a test.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import time
try:
    import tomllib
except ImportError:  # Python < 3.11
    import tomli as tomllib
from pathlib import Path

import numpy as np
import pytest
from safetensors.numpy import save_file

REPO = Path(__file__).resolve().parent.parent


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _cli(*args: str, **kw) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "hypha_tpu", *args],
        capture_output=True,
        text=True,
        env=_env(),
        timeout=kw.pop("timeout", 60),
        **kw,
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_init_writes_documented_toml(tmp_path):
    out = tmp_path / "worker.toml"
    r = _cli("worker", "init", "-o", str(out), "--name", "w-test")
    assert r.returncode == 0, r.stderr
    text = out.read_text()
    assert "#" in text  # doc comments
    parsed = tomllib.loads(text)
    assert parsed["name"] == "w-test"
    assert parsed["offer"]["strategy"] == "flexible"


def test_init_all_roles(tmp_path):
    for role in ("gateway", "scheduler", "worker", "data"):
        out = tmp_path / f"{role}.toml"
        r = _cli(role, "init", "-o", str(out))
        assert r.returncode == 0, (role, r.stderr)
        assert out.exists()


def test_run_rejects_bad_config(tmp_path):
    p = tmp_path / "w.toml"
    p.write_text("[offer]\nstrategy = 'greedy'\n")
    r = _cli("worker", "run", "-c", str(p))
    assert r.returncode == 2
    assert "offer.strategy" in r.stderr


class Proc:
    def __init__(self, *args: str, log: Path):
        self.log = open(log, "w")
        self.p = subprocess.Popen(
            [sys.executable, "-m", "hypha_tpu", *args],
            stdout=self.log,
            stderr=subprocess.STDOUT,
            env=_env(),
        )
        self.log_path = log

    def wait_for(self, pattern: str, timeout: float = 60) -> str:
        deadline = time.time() + timeout
        while time.time() < deadline:
            text = self.log_path.read_text()
            m = re.search(pattern, text)
            if m:
                return m.group(0)
            if self.p.poll() is not None:
                raise AssertionError(
                    f"process exited rc={self.p.returncode}:\n{text}"
                )
            time.sleep(0.25)
        raise AssertionError(
            f"pattern {pattern!r} not seen in {timeout}s:\n{self.log_path.read_text()}"
        )

    def stop(self):
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
            try:
                self.p.wait(10)
            except subprocess.TimeoutExpired:
                self.p.kill()
        self.log.close()


@pytest.mark.slow
def test_quickstart_processes(tmp_path):
    """docs/quickstart parity: gateway + data + 2 workers as processes, then
    probe them, then a scheduler process runs a 1-round LeNet-free tiny GPT-2
    job to completion."""
    gw_port = free_port()
    gw_addr = f"127.0.0.1:{gw_port}"

    # dataset
    d = tmp_path / "toy"
    d.mkdir()
    rng = np.random.default_rng(0)
    for i in range(2):
        starts = rng.integers(0, 32, (6, 1))
        ids = (starts + np.arange(16)) % 32
        save_file({"input_ids": ids.astype(np.int32)}, str(d / f"s{i}.safetensors"))

    procs: list[Proc] = []
    try:
        gw = Proc(
            "gateway", "run", "--set", f"network.listen={gw_addr}",
            log=tmp_path / "gw.log",
        )
        procs.append(gw)
        gw.wait_for(r"gateway .* on .*" + str(gw_port), 30)

        # probe the gateway via the CLI
        r = _cli("gateway", "probe", gw_addr, timeout=30)
        assert r.returncode == 0 and "healthy" in r.stdout, r.stdout + r.stderr

        data = Proc(
            "data", "run",
            "--set", f"datasets.toy={d}",
            "--set", f"network.gateways={gw_addr}",
            log=tmp_path / "data.log",
        )
        procs.append(data)
        data.wait_for(r"data node .* on", 30)

        for i in range(2):
            w = Proc(
                "worker", "run", "--name", f"w{i}",
                "--set", "resources.tpu=2",
                "--set", "resources.cpu=4",
                "--set", "offer.strategy=whole",
                "--set", f"network.gateways={gw_addr}",
                "--set", f"work_root={tmp_path / ('w%d' % i)}",
                log=tmp_path / f"w{i}.log",
            )
            procs.append(w)
            w.wait_for(r"worker .* on", 60)

        sched = Proc(
            "scheduler", "run",
            "--set", f"network.gateways={gw_addr}",
            "--set", "job.dataset=toy",
            "--set", "job.model_family=gpt2",
            "--set", "job.model_type=causal-lm",
            "--set", "job.model_config.vocab_size=32",
            "--set", "job.model_config.n_positions=16",
            "--set", "job.model_config.n_embd=16",
            "--set", "job.model_config.n_layer=1",
            "--set", "job.model_config.n_head=2",
            "--set", "job.update_rounds=1",
            "--set", "job.avg_samples_between_updates=8",
            "--set", "job.max_batch_size=2",
            "--set", "job.num_workers=1",
            "--set", "job.inner_lr=0.003",
            log=tmp_path / "sched.log",
        )
        procs.append(sched)
        sched.wait_for(r"completed: 1 rounds", 180)
        assert sched.p.wait(30) == 0
    finally:
        for p in reversed(procs):
            p.stop()


def test_cli_reference_docs_are_fresh():
    """docs/reference/ is GENERATED (hypha_tpu.docgen — the clap-markdown
    role from the reference's build.rs); a hand-edit or a CLI change
    without regeneration fails here. Fix: python -m hypha_tpu.docgen
    docs/reference"""
    import pathlib

    # docgen renders every tool including certutil, whose module imports
    # the `cryptography` package at top level — skip cleanly where the
    # PKI dep isn't installed (the jax_graft CI image).
    pytest.importorskip(
        "cryptography",
        reason="docgen renders certutil docs, which need 'cryptography'",
    )
    from hypha_tpu import docgen

    out_dir = pathlib.Path(__file__).resolve().parents[1] / "docs" / "reference"
    fresh = {"README.md": docgen.render_index()}
    for name in docgen.TOOLS():
        fresh[f"{name}.md"] = docgen.render_tool(name)
    on_disk = {p.name: p.read_text() for p in out_dir.glob("*.md")}
    assert on_disk == fresh
