"""The harness end to end on the CPU at a tiny size (on-chip-measurement
guide, 2.1): the same command and code path as on the chip. Off the TPU the
run completes, prints no result and exits 3; what it would have printed is on
stderr, and it is ``correct: false`` only because the device is not a TPU."""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import time
from pathlib import Path

import pytest

from perfbench_helpers import (
    REPO, RESULT_KEYS, all_rounds_sound, bench_cmd, bench_env, failing_checks, make_root,
    notes, processes_under, rehearsal_result, run_bench,
)

SEED = 3_000_000_019  # more than 32 signed bits hold, as the driver's are


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("plain"))
    r = run_bench(root, "--workload", "tiny-gpt2.h4", "--seed", str(SEED),
                  "--seconds", "20", "--trace", "0")
    return root, r


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("traced"))
    r = run_bench(root, "--workload", "tiny-gpt2.h4", "--seed", "7",
                  "--seconds", "20", "--trace", "1")
    return root, r


def test_off_the_tpu_the_run_prints_no_result_and_exits_3(plain):
    _, r = plain
    assert r.returncode == 3, r.stderr[-3000:]
    for line in r.stdout.splitlines():  # earlier lines only: none is a result
        assert not RESULT_KEYS <= set(json.loads(line))


def test_the_job_ran_and_rounds_were_measured(plain):
    _, r = plain
    result = rehearsal_result(r.stderr)
    assert set(result) == RESULT_KEYS
    assert result["attempted"] >= 2 and all_rounds_sound(result, r.stdout)
    assert set(result["metrics"]) == {"tokens_per_s", "sync_exposed_s", "setup_s"}
    for name, m in result["metrics"].items():
        # At this size the steps are all of a round, and H x the median step
        # can pass the round's wall: only the other two are sure to be positive.
        assert (m["value"] > 0 or name == "sync_exposed_s") and isinstance(m["unit"], str)
    assert result["device"]["platform"] == "cpu"


def test_correct_is_false_only_because_the_device_is_no_tpu(plain):
    _, r = plain
    assert rehearsal_result(r.stderr)["correct"] is False
    assert failing_checks(r.stdout) == {"attention_is_compiled_flash", "device_is_tpu"}


def test_rounds_are_timed_by_the_harness_clock_and_agree_with_the_worker(plain):
    _, r = plain
    rounds = [json.loads(x) for x in r.stdout.splitlines() if '"phase": "round"' in x]
    measured = [x for x in rounds if x["measured"]]
    assert len(measured) >= 2 and not rounds[0]["measured"]
    for x in measured:  # the worker's own wall_s is the cross-check
        assert abs(x["wall_by_harness"] - x["wall_s"]) < 1.0, x


def test_no_child_of_the_run_is_left_alive(plain):
    root, _ = plain
    assert processes_under(root) == []


def test_the_harness_process_never_imports_jax(plain):
    root, _ = plain
    code = (
        "import sys; sys.path.insert(0, '.'); import perfbench.run, perfbench.cluster, "
        "perfbench.readers.derived; print('jax' in sys.modules)"
    )
    out = subprocess.run(
        [os.sys.executable, "-c", code], cwd=str(root), capture_output=True,
        text=True, env=bench_env(), timeout=60,
    )
    assert out.stdout.strip() == "False", out.stderr


def test_logs_go_under_the_ignored_output_directory(plain):
    root, _ = plain
    out = root / "chiprun_out" / "perfbench" / "tiny-gpt2.h4" / "plain"
    assert {p.name for p in out.glob("*.log")} == {
        "gateway.log", "data.log", "ps.log", "w0.log", "scheduler.log",
    }


def test_a_traced_run_reports_the_per_layer_metrics_the_cpu_can_give(traced):
    _, r = traced
    assert r.returncode == 3, r.stderr[-3000:]
    result = rehearsal_result(r.stderr)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    # What every cell reports; a metric with a ``workloads`` list is its own cell's.
    names = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    # No peak for a CPU and no memory statistic from it: those two are left
    # out of the line, as a reader that finds nothing must. And at this size
    # a run may be over before a lease is first renewed (every 20 s), and the
    # worker's clean-up takes under the 10 ms from which it is a span.
    never = {"mfu_step", "hbm_peak_gb"}
    not_always = {"lease_margin_min_s", "renew_late_max_s", "sync_cleanup_s"}
    assert names - never - not_always <= set(result["metrics"]) <= names - never
    assert len(names) == len(manifest["per_layer"]) - 7  # Trinity's seven are not this cell's
    assert "no peak FLOP/s known for device_kind 'cpu'" in r.stderr


def test_a_traced_run_reads_the_programs_spans_and_opens_the_profiler(traced):
    root, r = traced
    out = root / "chiprun_out" / "perfbench" / "tiny-gpt2.h4" / "traced"
    assert {p.name for p in (out / "spans").iterdir()} == {
        "spans-w0.jsonl", "spans-ps.jsonl", "spans-scheduler.jsonl",
    }
    marks = json.loads((out / "profile" / "marks.json").read_text())
    assert marks["stop_wall_ns"] > marks["start_wall_ns"]
    # No TPU plane in a CPU trace: busy time is absent, never invented.
    device = rehearsal_result(r.stderr)["device"]
    assert device["busy_s"] is None and device["window_s"] is None
    assert notes(r.stdout)["profile"]["error"] == "no device events"
    # The raw trace stayed until the readers had run, and is gone now.
    assert not (out / "profile" / "plugins").exists()


def _nothing_of_the_work_directory_is_left(root: Path) -> bool:
    from perfbench import cluster

    link = root / "chiprun_out" / "pb-run"
    return not link.is_symlink() and not link.exists() and not cluster.work_dir_of(root).exists()


def test_the_roles_files_lived_off_the_checkout_and_nothing_of_them_is_left(plain, traced):
    for root, r in (plain, traced):
        assert notes(r.stdout)["end_to_end"]["work_fs"] == "shm"
        assert _nothing_of_the_work_directory_is_left(root)


def test_a_run_ended_by_sigterm_in_mid_round_leaves_no_process_link_or_files(bench_root):
    from perfbench import cluster

    proc = subprocess.Popen(
        bench_cmd(bench_root, "--workload", "tiny-gpt2.h4", "--seed", "5",
                  "--seconds", "60", "--trace", "0"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=str(bench_root), env=bench_env(),
    )
    try:
        w0_log = bench_root / "chiprun_out/perfbench/tiny-gpt2.h4/plain/w0.log"
        end = time.monotonic() + 240
        while time.monotonic() < end:
            if w0_log.is_file() and "round 0 done" in w0_log.read_text(errors="replace"):
                break
            assert proc.poll() is None, proc.stderr.read()[-3000:]
            time.sleep(0.2)
        link = bench_root / "chiprun_out" / "pb-run"
        assert link.is_symlink() and link.resolve() == cluster.work_dir_of(bench_root)
        assert (cluster.work_dir_of(bench_root) / "counting").is_dir()
        assert not cluster.is_stale(cluster.work_dir_of(bench_root))  # its harness is alive
        proc.send_signal(signal.SIGTERM)
        stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 128 + signal.SIGTERM, stderr[-2000:]
    assert not any(RESULT_KEYS <= set(json.loads(x)) for x in stdout.splitlines())
    assert processes_under(bench_root) == []
    assert _nothing_of_the_work_directory_is_left(bench_root)


def test_what_a_run_ended_by_sigkill_leaves_is_swept_by_the_next_run_of_any_checkout(bench_root):
    """SIGKILL, or the driver's time limit: the harness cannot clean up, the
    roles go with it (``PR_SET_PDEATHSIG``), and its work directory stays in
    memory under a name only that checkout would look for. Its owner's mark
    names a dead process, so the next run on the machine, from whatever
    checkout, removes it first (``cluster.sweep_work_dirs``)."""
    from perfbench import cluster

    proc = subprocess.Popen(
        bench_cmd(bench_root, "--workload", "tiny-gpt2.h4", "--seed", "6",
                  "--seconds", "60", "--trace", "0"),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, cwd=str(bench_root), env=bench_env(),
    )
    left = cluster.work_dir_of(bench_root)
    try:
        end = time.monotonic() + 240
        while time.monotonic() < end and not (left / "counting").is_dir():
            assert proc.poll() is None
            time.sleep(0.2)
        assert not cluster.is_stale(left)
    finally:
        proc.kill()
        proc.wait()
    end = time.monotonic() + 30
    while processes_under(bench_root) and time.monotonic() < end:
        time.sleep(0.2)
    assert processes_under(bench_root) == []  # the roles went with their harness
    assert left.is_dir() and cluster.is_stale(left)  # ... and this is what it could not remove
    cluster.sweep_work_dirs()  # what every run does first, whatever its checkout
    assert not left.exists()
    (bench_root / "chiprun_out" / "pb-run").unlink()  # the dangling link is the checkout's own


def _pid_of_role(root: Path, name: str) -> int | None:
    for proc in Path("/proc").iterdir():
        if not proc.name.isdigit():
            continue
        try:
            if Path(os.readlink(proc / "cwd")) != root:
                continue
            argv = (proc / "cmdline").read_bytes().split(b"\0")
        except OSError:
            continue
        if b"--name" in argv and argv[argv.index(b"--name") + 1] == name.encode():
            return int(proc.name)
    return None


def test_a_ps_killed_mid_run_is_named_with_every_roles_log_tail(bench_root):
    proc = subprocess.Popen(
        bench_cmd(bench_root, "--workload", "tiny-gpt2.h4", "--seed", "1",
                  "--seconds", "60", "--trace", "0"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=str(bench_root), env=bench_env(),
    )
    try:
        w0_log = bench_root / "chiprun_out/perfbench/tiny-gpt2.h4/plain/w0.log"
        end = time.monotonic() + 240
        while time.monotonic() < end:
            if w0_log.is_file() and "round 1 done" in w0_log.read_text(errors="replace"):
                break
            assert proc.poll() is None, proc.stderr.read()[-3000:]
            time.sleep(0.2)
        pid = _pid_of_role(bench_root, "ps")
        assert pid is not None
        os.kill(pid, signal.SIGKILL)
        stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    cause = next(x for x in stderr.splitlines() if x.startswith("perfbench: run failed: "))
    assert "role ps died (return code -9)" in cause
    for role in ("gateway", "data", "ps", "w0", "scheduler"):
        assert f"--- {role} (return code" in stderr
    result = rehearsal_result(stderr)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is False and result["failed"] >= 1
    assert result["attempted"] == len([
        x for x in stdout.splitlines() if '"measured": true' in x
    ]) + 1
    assert notes(stdout)["checks"]["no_role_died"] is False
    assert processes_under(bench_root) == []


def test_alone_in_a_directory_the_benchmark_exits_nonzero_with_no_result(tmp_path):
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = run_bench(tmp_path, "--workload", "mistral-7b-d1.steps", "--seed", "0",
                  "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no hypha_tpu package" in r.stderr


def test_an_unknown_workload_is_refused_by_name(bench_root):
    r = run_bench(bench_root, "--workload", "no-such.cell", "--seed", "0",
                  "--seconds", "1", "--trace", "0")
    assert r.returncode == 2 and r.stdout.strip() == ""
    assert "no workload 'no-such.cell'" in r.stderr
