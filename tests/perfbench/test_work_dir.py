"""The roles' work directories and the data: a link from the checkout to a
fresh directory of its own on a memory-backed filesystem (a temporary
directory stands in for ``/dev/shm`` here); no run where that is absent or
short; nothing left behind, and what a killed run of any checkout left is
swept."""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

import manifest_checks
from perfbench import cluster, manifest, run as bench_run
from perfbench_helpers import REPO

GB = 10**9


@pytest.fixture
def shm(tmp_path, monkeypatch):
    d = tmp_path / "shm"
    d.mkdir()
    monkeypatch.setattr(cluster, "SHM", d)
    monkeypatch.setattr(cluster.shutil, "disk_usage",
                        lambda p: type("U", (), {"free": 20 * GB})())
    return d


def checkout(tmp_path, name="checkout"):
    root = tmp_path / name
    root.mkdir()
    return root


def opened(root):
    c = cluster.Cluster(root, root / "chiprun_out" / "perfbench" / "x" / "plain", False)
    c.open_work_dir()
    return c


def test_the_link_is_made_and_the_roles_relative_paths_resolve_through_it(tmp_path, shm):
    root = checkout(tmp_path)
    c = opened(root)
    assert c.work_dir == cluster.work_dir_of(root)
    assert c.run_dir == root / "chiprun_out" / "pb-run" and c.run_dir.is_symlink()
    assert c.run_dir.resolve() == c.work_dir and c.work_dir.parent == shm
    # A role runs in the checkout and is given work_root=chiprun_out/pb-run/ps:
    # as short as before, whatever the checkout's or the target's length.
    work_root = str(c.work / "ps")
    assert work_root == "chiprun_out/pb-run/ps" and len(work_root) < 30
    subprocess.run(
        [sys.executable, "-c", "import os, sys; os.makedirs(sys.argv[1]); "
         "open(os.path.join(sys.argv[1], 'delta.safetensors'), 'w').write('x')", work_root],
        cwd=str(root), check=True, timeout=60)
    assert (c.work_dir / "ps" / "delta.safetensors").read_text() == "x"
    assert [p.name for p in (root / "chiprun_out").iterdir()] == ["pb-run"]  # a link, no bytes
    c.close()


def test_two_checkouts_on_one_machine_share_nothing(tmp_path, shm):
    a, b = opened(checkout(tmp_path, "parent")), opened(checkout(tmp_path, "change"))
    assert a.work_dir != b.work_dir and {a.work_dir.parent, b.work_dir.parent} == {shm}
    (a.run_dir / "f").write_text("a")
    assert not (b.run_dir / "f").exists()
    a.close()
    assert b.run_dir.is_symlink() and b.work_dir.is_dir()  # one's end leaves the other's
    b.close()
    assert list(shm.iterdir()) == []


@pytest.mark.parametrize("why,says", [
    ("shm_short", "has 11.0 GB free, a run wants 16.0 GB"),
    ("shm_absent", "is absent"),
    ("shm_a_file", "is absent"),
])
def test_without_the_ground_there_is_no_run_and_no_result(
        tmp_path, shm, monkeypatch, capsys, why, says):
    """One ground for every run: a run that would have to measure elsewhere
    does not start, says why, prints no result and exits 3, as off the TPU."""
    if why == "shm_short":
        monkeypatch.setattr(cluster.shutil, "disk_usage", lambda p: type("U", (), {"free": 11 * GB})())
    elif why == "shm_absent":
        monkeypatch.setattr(cluster, "SHM", tmp_path / "no-such")
    elif why == "shm_a_file":
        (tmp_path / "file").write_text("")
        monkeypatch.setattr(cluster, "SHM", tmp_path / "file")
    assert says in cluster.no_work_ground()
    monkeypatch.setattr(cluster, "run_cell", lambda *a, **k: pytest.fail("a run started"))
    cell = manifest.load_manifest(REPO)["workloads"][0]["name"]
    assert bench_run.main(["--workload", cell, "--seed", "1", "--trace", "0"]) == 3
    out, err = capsys.readouterr()
    assert out == "" and "no ground to measure on" in err and says in err


def test_with_room_the_ground_is_there(shm):
    assert cluster.no_work_ground() is None
    assert cluster.WORK_FREE_BYTES == 16 * GB  # a quarter over what any cell that fits the chip holds at once


def test_close_leaves_neither_link_nor_target_and_may_be_called_twice(tmp_path, shm):
    c = opened(checkout(tmp_path))
    (c.run_dir / "w0").mkdir()
    (c.run_dir / "w0" / "update.safetensors").write_bytes(b"\0" * 4096)
    target = c.work_dir
    assert c.close() == {} and c.close() == {}
    assert not c.run_dir.is_symlink() and not c.run_dir.exists() and not target.exists()
    assert list(shm.iterdir()) == []


def test_a_stale_link_target_or_directory_is_removed_at_start(tmp_path, shm):
    root = checkout(tmp_path)
    stale = cluster.work_dir_of(root)
    (stale / "ps").mkdir(parents=True)
    (stale / "ps" / "left-by-a-killed-run").write_text("2 GB once")
    (root / "chiprun_out").mkdir()
    (root / "chiprun_out" / "pb-run").symlink_to(tmp_path / "gone")  # dangling
    c = opened(root)
    assert c.run_dir.resolve() == stale and [p.name for p in stale.iterdir()] == [cluster.OWNER]
    c.close()
    # ... and a real directory from a run of before PR 37
    (root / "chiprun_out" / "pb-run" / "w0").mkdir(parents=True)
    c = opened(root)
    assert c.run_dir.is_symlink() and [p.name for p in c.run_dir.iterdir()] == [cluster.OWNER]
    c.close()


def dead_pid() -> int:
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    return p.pid


@pytest.mark.parametrize("left_by,swept", [
    ("a_dead_harness", True),  # SIGKILL, or cut at the driver's time limit
    ("a_pid_that_came_round_again", True),  # alive, but started at another time
    ("a_live_harness", False),  # another checkout's run, still going
    ("no_owner_long_ago", True),
    ("no_owner_just_now", False),  # between mkdir and the owner's mark
])
def test_what_another_checkouts_killed_run_left_is_swept(tmp_path, shm, capsys, left_by, swept):
    """The driver's later checkouts have other paths, so other names: each
    run removes every work directory whose harness is gone, whatever its
    name, or the gigabytes a cut run held would stay until the machine goes."""
    other = shm / "perfbench-0123456789abcdef"
    (other / "w0").mkdir(parents=True)
    (other / "w0" / "delta-3.safetensors").write_bytes(b"\0" * 4096)
    if left_by == "a_dead_harness":
        (other / cluster.OWNER).write_text(f"{dead_pid()} 12345")
    elif left_by == "a_pid_that_came_round_again":
        (other / cluster.OWNER).write_text(f"{os.getpid()} 1")
    elif left_by == "a_live_harness":
        (other / cluster.OWNER).write_text(cluster.owner_mark(os.getpid()))
    elif left_by == "no_owner_long_ago":
        long_ago = time.time() - 3600
        os.utime(other, (long_ago, long_ago))
    (shm / "someone-elses").mkdir()  # not ours by name: never touched
    c = opened(checkout(tmp_path))
    assert other.exists() is not swept and (shm / "someone-elses").is_dir()
    assert (f"removing {other}" in capsys.readouterr().err) is swept
    c.close()
    assert sorted(p.name for p in shm.iterdir()) == sorted(
        ["someone-elses"] + ([] if swept else [other.name]))


def test_a_work_directory_names_its_harness(tmp_path, shm):
    c = opened(checkout(tmp_path))
    mark = (c.work_dir / cluster.OWNER).read_text()
    assert mark == cluster.owner_mark(os.getpid()) and mark.split()[0] == str(os.getpid())
    assert not cluster.is_stale(c.work_dir)
    assert cluster.owner_mark(dead_pid()) is None
    c.close()


def test_the_signal_handlers_systemexit_unwinds_through_close(tmp_path, shm, monkeypatch):
    """``run.py`` turns SIGTERM and SIGINT into ``SystemExit``; ``_attempt``'s
    ``finally`` is what removes link and target on that way out."""
    root = checkout(tmp_path)
    cell = manifest.resolve(manifest.load_manifest(REPO)["workloads"][0]["name"], REPO)
    seen = {}

    def killed_here(root_, env):
        run_dir = root_ / "chiprun_out" / "pb-run"
        seen["link"] = run_dir.is_symlink() and run_dir.resolve() == cluster.work_dir_of(root_)
        raise SystemExit(128 + 15)

    monkeypatch.setattr(cluster, "build_native", killed_here)
    out_dir = root / "chiprun_out" / "perfbench" / cell.name / "plain"
    run = cluster.Run(t_start=0.0, t_wall=0.0, out_dir=out_dir, trace=False)
    with pytest.raises(SystemExit):
        cluster._attempt(cell, 1, 51.0, run, root, 1e18, 1e18)
    assert seen == {"link": True}
    assert not (root / "chiprun_out" / "pb-run").is_symlink()
    assert not (root / "chiprun_out" / "pb-run").exists() and list(shm.iterdir()) == []


@pytest.mark.parametrize("cell_name", manifest_checks.cell_names(manifest.load_manifest(REPO)))
def test_what_a_cell_of_the_manifest_holds_there_at_once_fits_the_floor_with_a_quarter_to_spare(cell_name):
    """Delta, the PS's copy, the update, the worker's copy (4 x 4 B a
    parameter, the count from the file the configuration brings under
    ``data/parameters/``) and the data, and a quarter more, are within the
    one fixed floor: 16 GB / 1.25 = 12.8 GB = 0.8 B parameters, and 16 GB of
    HBM hold 0.77 B at 22 B each, so no configuration that fits the chip
    breaks it, and none has to state a size to the harness."""
    manifest_checks.check_work_dir_floor(manifest.load_manifest(REPO), cell_name, REPO)


def test_a_configuration_without_its_count_is_told_which_file_to_add(tmp_path):
    m = manifest.load_manifest(REPO)
    cell = m["workloads"][0]
    with pytest.raises(AssertionError, match=(
            f"add tests/perfbench/data/parameters/{cell['config']}.json")):
        manifest_checks.check_work_dir_floor(m, cell["name"], tmp_path)


def test_a_cell_too_large_for_the_floor_is_refused_with_its_size(tmp_path, monkeypatch):
    m = manifest.load_manifest(REPO)
    name = m["workloads"][0]["name"]
    held = manifest_checks.check_work_dir_floor(m, name, REPO)
    assert 16 * 480_260_096 < held < 16 * 480_260_096 + GB  # the count's 16 B and the data
    monkeypatch.setattr(cluster, "WORK_FREE_BYTES", int(1.25 * held) - 1)
    with pytest.raises(AssertionError, match="GB in its work directory at once"):
        manifest_checks.check_work_dir_floor(m, name, REPO)
    monkeypatch.setattr(cluster, "WORK_FREE_BYTES", int(1.25 * held) + 1)
    manifest_checks.check_work_dir_floor(m, name, REPO)
