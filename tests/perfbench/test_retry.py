"""A lost lease is the one failure a run tries again (PERF.md 6, 7)."""

from __future__ import annotations

import time

import pytest

from perfbench import cluster, manifest
from perfbench_helpers import REPO

CELL = manifest.resolve(manifest.load_manifest(REPO)["workloads"][0]["name"], REPO)


def run_with(monkeypatch, tmp_path, causes, started_s_ago=0.0):
    """``run_cell`` with the cluster replaced: attempt i ends in causes[i]."""
    seen = []

    def attempt(cell, seed, seconds, run, root, setup_deadline, run_deadline):
        run.cause = causes[len(seen)]
        run.arrivals = {0: 1.0} if run.cause is None else {}
        seen.append(run.attempts)

    monkeypatch.setattr(cluster, "_attempt", attempt)
    monkeypatch.setattr(cluster.measure, "from_logs", lambda *a, **k: None)
    (tmp_path / "chiprun_out" / "perfbench" / CELL.name).mkdir(parents=True)  # not the first run
    t_start = time.monotonic() - started_s_ago
    run = cluster.run_cell(CELL, 1, 51.0, False, t_start, time.time(), tmp_path)
    return run, seen


@pytest.mark.parametrize("when", ["in set-up", "in the window"])
def test_a_lost_lease_starts_the_cluster_once_more(monkeypatch, tmp_path, capsys, when):
    lost = cluster.LEASE_LOST if when == "in set-up" else (
        "lease expired (in the ps log); role scheduler died (return code 1)")
    run, seen = run_with(monkeypatch, tmp_path, [lost, None])
    assert seen == [1, 2] and run.cause is None and run.attempts == 2
    err = capsys.readouterr().err
    assert "perfbench: run failed: lease expired" in err and "once more" in err


def test_a_lease_lost_three_times_is_the_runs_cause(monkeypatch, tmp_path):
    run, seen = run_with(monkeypatch, tmp_path, [cluster.LEASE_LOST] * 3)
    assert seen == [1, 2, 3] and "lease expired" in run.cause


def test_no_second_start_where_it_could_not_end_in_time(monkeypatch, tmp_path):
    run, seen = run_with(monkeypatch, tmp_path, [cluster.LEASE_LOST, None],
                         started_s_ago=cluster.LATER_RETRY_S + 1)
    assert seen == [1] and "lease expired" in run.cause


@pytest.mark.parametrize("cause", [
    "role w0 died (return code -9)",
    # the auction's race (PERF.md 7): a fault of the program, named and reported
    "a request about a lease was refused (in the scheduler log); role scheduler died (return code 1)",
], ids=["a_role_died", "the_auction"])
def test_another_failure_is_not_tried_again(monkeypatch, tmp_path, cause):
    run, seen = run_with(monkeypatch, tmp_path, [cause, None])
    assert seen == [1] and run.cause == cause
