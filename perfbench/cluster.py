"""One run of one cell: the five CLI roles, the window, the clean-up.

The harness never imports jax: worker ``w0`` is the one process that holds
the chip. All children share one process group, which is killed on every
way out, and a run does not return until ``w0`` is gone, so the next run of
a set finds the chip and libtpu's lock free.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import data, logs, measure

BENCH = Path(__file__).resolve().parent
TAIL_LINES = 30
POLL_S = 0.01  # how late a round's close can be seen; a round takes seconds
HOLDERS_EVERY_S = 1.0
LEASE_LOST = "lease expired: the job's lease was lost"
TRACED_ROLES = ("scheduler", "ps", "w0")  # the ones that write round spans
# The contract: a run ends within 360 s, the first of a cell in a checkout
# (it compiles) within 1200 s. Set-up gets what is left after the window.
LATER_SETUP_S = 270.0
FIRST_SETUP_S = 1050.0
# Round 1 is waited for beyond the window, but not beyond the contract's end.
LATER_RUN_S = 330.0
FIRST_RUN_S = 1170.0
# A lost lease is tried again while a whole attempt still fits before that.
LATER_RETRY_S = 160.0
FIRST_RETRY_S = 700.0
# The configuration's reference runs after the roles are gone: its own time
# limit, and never past the contract's end.
LATER_REFERENCE_S = 90.0
FIRST_REFERENCE_S = 400.0
LATER_END_S = 352.0
FIRST_END_S = 1190.0
# Where the roles' files go: memory, not the checkout's file layer, whose
# write-back and unlinking were the benchmark's noise (PERF.md 2). It is the
# one ground every run is measured on: where it is absent or short the run
# fails with the cause (``no_work_ground``), it does not measure elsewhere.
# A test puts a temporary directory here.
SHM = Path("/dev/shm")
# Free there before a run starts, for every cell alike: a cell holds there at
# once delta, the PS's copy, update, the worker's copy (16 B a parameter) and
# the data, and the floor promises a quarter more than that to any cell whose
# model fits the chip: 16 GB / 1.25 = 12.8 GB = 0.8 B parameters, where 16 GB
# of HBM hold 0.77 B at 22 B each. tests/perfbench/test_work_dir.py holds each
# cell of the manifest to it by its configuration's own count.
WORK_FREE_BYTES = 16 * 10**9
OWNER = "owner"  # in a work directory: pid and start time of the harness that made it
STALE_WITHOUT_OWNER_S = 60.0


class RunFailure(Exception):
    """The run cannot go on; the message is the named cause."""


@dataclass
class Run:
    t_start: float  # time.monotonic() when the harness started
    t_wall: float  # time.time() at the same moment
    out_dir: Path
    trace: bool
    events: dict = field(default_factory=dict)  # name -> wall clock
    arrivals: dict = field(default_factory=dict)  # round -> monotonic, as its line arrived
    round_steps: int = 0
    round_tokens: int = 0
    rounds: list = field(default_factory=list)
    measured: list = field(default_factory=list)
    outer: list = field(default_factory=list)
    pushed: dict = field(default_factory=dict)
    device: dict | None = None
    attention: str | None = None
    memory_peak_bytes: int | None = None
    holders: list = field(default_factory=list)
    returncodes: dict = field(default_factory=dict)
    cause: str | None = None
    texts: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    profile: dict | None = None
    checks: dict = field(default_factory=dict)
    margins: dict = field(default_factory=dict)  # check -> the number compared and its limits
    reference: dict | None = None  # what the configuration's reference printed
    attempts: int = 1  # cluster starts; 2 after a lease lost in set-up


def _die_with_parent() -> None:
    # PR_SET_PDEATHSIG: a harness killed outright still takes the roles along.
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


class Child:
    """One CLI role as an OS process, its output in a log file."""

    def __init__(self, name: str, argv: list[str], env: dict, cwd: Path,
                 log_dir: Path, pgid: int) -> None:
        self.name = name
        self.log_path = log_dir / f"{name}.log"
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, stdout=self.log, stderr=subprocess.STDOUT, env=env, cwd=str(cwd),
            process_group=pgid, preexec_fn=_die_with_parent,
        )

    def text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def maps_libtpu(self) -> bool:
        try:
            return "libtpu" in Path(f"/proc/{self.proc.pid}/maps").read_text()
        except OSError:
            return False


class Tail:
    """New whole lines of a growing file."""

    def __init__(self, path: Path) -> None:
        self.f = open(path, "rb")
        self.rest = b""

    def lines(self) -> list[str]:
        chunk = self.f.read()
        if not chunk:
            return []
        *whole, self.rest = (self.rest + chunk).split(b"\n")
        return [x.decode(errors="replace") for x in whole]

    def close(self) -> None:
        self.f.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Cluster:
    """The roles of one run. ``close`` is safe to call twice and from a
    signal handler's unwinding."""

    def __init__(self, root: Path, out_dir: Path, trace: bool) -> None:
        self.root, self.out_dir, self.trace = root, out_dir, trace
        self.children: dict[str, Child] = {}
        self.pgid = 0
        # Data and the roles' work directories, named to the roles relative
        # to the checkout (their working directory): the worker binds a unix
        # socket three levels down, whose path may have 107 bytes, and a
        # checkout or a TMPDIR can be any length. ``open_work_dir`` makes it.
        self.work = Path("chiprun_out") / "pb-run"
        self.run_dir = root / self.work
        self.work_dir: Path | None = None  # what run_dir links to, once made
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = str(root) + os.pathsep + self.env.get("PYTHONPATH", "")
        self.env["PYTHONUNBUFFERED"] = "1"
        self.gateway = f"127.0.0.1:{free_port()}"

    def open_work_dir(self) -> None:
        """``run_dir`` as a link to a fresh directory of this checkout's own
        on ``SHM``. What a killed run left goes first: under this checkout's
        name, and under any name whose harness is dead (a run cut by SIGKILL
        cannot clean up, and what it leaves there is memory)."""
        sweep_work_dirs()
        target = work_dir_of(self.root)
        remove_work_dir(self.run_dir, target)
        self.run_dir.parent.mkdir(parents=True, exist_ok=True)
        self.work_dir = target  # from here on ``close`` removes it
        target.mkdir()
        (target / OWNER).write_text(owner_mark(os.getpid()) or "")
        self.run_dir.symlink_to(target)

    def start(self, name: str, *cli: str) -> Child:
        env, entry = dict(self.env), ["-m", "hypha_tpu"]
        if self.trace and name in TRACED_ROLES:
            # The same cli.main behind the benchmark's own entry point,
            # which switches the program's spans on and, in w0, the profiler.
            entry = [str(BENCH / "traced_entry.py")]
            env["PERFBENCH_SPAN_DIR"] = str(self.out_dir / "spans")
            env["PERFBENCH_NODE"] = name
            if name == "w0":
                env["PERFBENCH_PROFILE_DIR"] = str(self.out_dir / "profile")
        argv = [sys.executable, *entry, *cli]
        child = Child(name, argv, env, self.root, self.out_dir, self.pgid)
        if not self.pgid:
            self.pgid = child.proc.pid  # process_group=0 made it the leader
        self.children[name] = child
        return child

    def wait_for(self, name: str, pattern: str, deadline: float) -> "re.Match[str]":
        child = self.children[name]
        while True:
            m = re.search(pattern, child.text())
            if m:
                return m
            self.check_alive()
            if time.monotonic() > deadline:
                raise RunFailure(f"deadline: {name} did not log {pattern!r} in time")
            time.sleep(0.2)

    def check_alive(self) -> None:
        for c in self.children.values():
            rc = c.proc.poll()
            if rc is not None:
                raise RunFailure(f"role {c.name} died (return code {rc})")

    def holders(self) -> set[str]:
        return {n for n, c in self.children.items() if c.maps_libtpu()}

    def texts(self) -> dict[str, str]:
        return {n: c.text() for n, c in self.children.items()}

    def stop(self, name: str) -> None:
        """SIGTERM and wait, so that span files and the trace are flushed."""
        c = self.children[name]
        if c.proc.poll() is None:
            c.proc.send_signal(signal.SIGTERM)
            try:
                c.proc.wait(20.0)
            except subprocess.TimeoutExpired:
                c.proc.kill()
                c.proc.wait()

    def close(self) -> dict[str, int | None]:
        """Stop every role, the job's owner first and then the chip's
        holder, then the whole group for whatever they started."""
        for name in ("scheduler", "w0", "ps", "data", "gateway"):
            if name in self.children:
                self.stop(name)
        if self.pgid:
            try:
                os.killpg(self.pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        codes = {}
        for n, c in self.children.items():
            c.proc.wait()
            codes[n] = c.proc.returncode
            if not c.log.closed:
                c.log.close()
        remove_work_dir(self.run_dir, self.work_dir)
        return codes


def work_dir_of(root: Path) -> Path:
    """This checkout's own directory on ``SHM``, named from its path: the
    parent's checkout and the change's on one machine share nothing."""
    return SHM / ("perfbench-" + hashlib.sha256(str(root.resolve()).encode()).hexdigest()[:16])


def remove_work_dir(run_dir: Path, target: Path | None) -> None:
    """The link (``shutil.rmtree`` refuses one) or the directory, and what
    the link pointed to."""
    if run_dir.is_symlink():
        run_dir.unlink()
    else:
        shutil.rmtree(run_dir, ignore_errors=True)
    if target is not None:
        shutil.rmtree(target, ignore_errors=True)


def no_work_ground() -> str | None:
    """Why no run can be measured on this machine, or ``None``."""
    if not SHM.is_dir():
        return f"{SHM} is absent"
    free = shutil.disk_usage(SHM).free
    if free < WORK_FREE_BYTES:
        return (f"{SHM} has {free / 1e9:.1f} GB free, a run wants "
                f"{WORK_FREE_BYTES / 1e9:.1f} GB for the roles' files")
    return None


def owner_mark(pid: int) -> str | None:
    """A process by pid and start time (a pid alone comes round again), or
    ``None`` where there is no such process."""
    try:
        stat = Path(f"/proc/{pid}/stat").read_text()
        return f"{pid} {stat.rsplit(')', 1)[1].split()[19]}"
    except (OSError, IndexError):
        return None


def is_stale(work_dir: Path) -> bool:
    """Whether the harness that made ``work_dir`` is gone."""
    try:
        mark = (work_dir / OWNER).read_text()
        return owner_mark(int(mark.split()[0])) != mark
    except (OSError, ValueError, IndexError):
        pass  # no owner yet, or never: stale once it has lain a while
    try:
        return time.time() - work_dir.stat().st_mtime > STALE_WITHOUT_OWNER_S
    except OSError:
        return False


def sweep_work_dirs() -> None:
    """Remove every checkout's work directory on ``SHM`` whose harness is
    dead: the driver's later checkouts have other paths and so other names,
    and what a cut run left is what the cell held, gigabytes of memory until
    someone frees it."""
    for d in SHM.glob("perfbench-*"):
        if d.is_dir() and not d.is_symlink() and is_stale(d):
            print(f"perfbench: removing {d}, left by a run that was killed", file=sys.stderr)
            shutil.rmtree(d, ignore_errors=True)


PROBE = """
import json
import numpy as np
from hypha_tpu import codec, native
print(json.dumps({"ps_kernels": native.native_available(),
                  "cbor_codec": codec.native_codec_active()}), flush=True)
acc, m = np.ones(1 << 21, np.float32), np.zeros(1 << 21, np.float32)
native.fused_mean_nesterov(acc, 2.0, m, 0.7, 0.9, 2)  # the PS's own call, on two threads
assert abs(float(acc[-1]) - 0.665) < 1e-6 and float(m[0]) == 0.5
assert codec.loads(codec.dumps({"k": [1, 2.5, "x"]})) == {"k": [1, 2.5, "x"]}
print("probed", flush=True)
"""


def build_native(root: Path, env: dict) -> None:
    """Both native libraries, built and called once by a short child before
    any role starts, so the roles load them and do not race to compile them.
    They are built ``-march=native`` and rebuilt only by mtime, so a copy
    that came along from another machine can kill the PS at its first outer
    step with an illegal instruction (seen in PR 21 and again in PR 23, when
    the chip tool shipped ``native/build/`` in spite of ``.chiprunignore``).
    A library that dies at the probe is removed and built here."""
    for attempt in (1, 2):
        r = subprocess.run(
            [sys.executable, "-c", PROBE], cwd=str(root), capture_output=True,
            text=True, timeout=600, env=dict(env, JAX_PLATFORMS="cpu"),
        )
        lines = r.stdout.strip().splitlines()
        if r.returncode == 0 and lines and lines[-1] == "probed":
            built = json.loads(lines[0])
            if not all(built.values()):
                raise RunFailure(f"native libraries did not build: {built}")
            return
        if r.returncode < 0 and attempt == 1:
            build = (root / "hypha_tpu").resolve().parent / "native" / "build"
            print(
                f"perfbench: the native libraries in {build} died at the probe "
                f"(signal {-r.returncode}): built on another machine; removing "
                "them so they are built here", file=sys.stderr,
            )
            for so in build.glob("*.so"):
                so.unlink()
            continue
        raise RunFailure(
            f"native build or probe failed (return code {r.returncode}): {r.stderr[-2000:]}"
        )


def reduce_profile(root: Path, out_dir: Path, env: dict) -> dict | None:
    """The profiler's trace to busy time, window and breakdown, in a child
    of its own (it needs jax's reader; it runs after ``w0`` is gone)."""
    marks = out_dir / "profile" / "marks.json"
    if not marks.is_file():
        return None
    r = subprocess.run(
        [sys.executable, "-m", "perfbench.xplane", str(out_dir / "profile"),
         str(out_dir / "spans")],
        cwd=str(root), capture_output=True, text=True, timeout=300,
        env=dict(env, JAX_PLATFORMS="cpu"),
    )
    try:
        out = json.loads(r.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        print(f"perfbench: trace reduction failed: {r.stderr[-2000:]}", file=sys.stderr)
        return None
    return out


def drop_raw_trace(out_dir: Path) -> None:
    """Once the readers of device events have run: the raw trace is tens of
    megabytes a round, and a run writes little that stays."""
    shutil.rmtree(out_dir / "profile" / "plugins", ignore_errors=True)


def run_reference(root: Path, cell, seed: int, timeout: float) -> dict:
    """Round 0's first loss by the configuration's plain reference
    (``perfbench/reference/``), in a process of its own on the chip that
    ``w0`` has released: every role has been waited for by now, so nothing
    of the program is alive and libtpu is mapped by no one. Never raises:
    a reference that could not run says why under ``error``, and the run
    fails by ``reference_ran``, not by the comparison."""
    out: dict = {}
    t0 = time.monotonic()
    if timeout < 15.0:
        out["error"] = f"no time left for the reference ({timeout:.0f} s)"
    else:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(root) + os.pathsep + env.get("PYTHONPATH", "")
        try:
            r = subprocess.run(
                [sys.executable, "-m", "perfbench.reference", "--workload", cell.name,
                 "--seed", str(seed), "--root", str(root)],
                cwd=str(root), capture_output=True, text=True, timeout=timeout, env=env,
            )
            out = json.loads(r.stdout.strip().splitlines()[-1])
            if not isinstance(out, dict):
                raise ValueError(out)
        except subprocess.TimeoutExpired:
            out["error"] = f"the reference did not end within {timeout:.0f} s"
        except (IndexError, ValueError):
            out["error"] = f"return code {r.returncode}: {r.stderr[-1500:]}"
    out["wall_s"] = time.monotonic() - t0
    if "loss" not in out:
        print(f"perfbench: the reference did not run: {out.get('error') or out.get('skipped')}",
              file=sys.stderr)
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             t_wall: float, root: Path) -> Run:
    """Run the cell once. Never raises for a failed run: the cause is in
    ``Run.cause`` and the tails of the roles' logs are on stderr."""
    out_dir = root / "chiprun_out" / "perfbench" / cell.name / ("traced" if trace else "plain")
    run = Run(t_start=t_start, t_wall=t_wall, out_dir=out_dir, trace=trace)
    first = not out_dir.parent.exists()  # of this cell in this checkout: it may compile
    setup_deadline = t_start + (FIRST_SETUP_S if first else LATER_SETUP_S)
    run_deadline = t_start + (FIRST_RUN_S if first else LATER_RUN_S)
    retry_before = t_start + (FIRST_RETRY_S if first else LATER_RETRY_S)
    for run.attempts in (1, 2, 3):
        _attempt(cell, seed, seconds, run, root, setup_deadline, run_deadline)
        # The one failure that is tried again: the job's lease lost. A lease
        # lasts 10 s and is renewed every 6.7 s, so a role whose event loop
        # stalls for 3.3 s (a compile, the PS's outer step, a host that
        # shares its cores) gets the job cancelled: a race in the program
        # (PERF.md 6, 7). The lost attempt stays in ``setup_s``, nothing of
        # it is measured, and stderr and ``cluster_starts`` say it happened.
        if not (
            run.cause and "lease expired" in run.cause
            and run.attempts < 3 and time.monotonic() < retry_before
        ):
            break
        report_failure(run)
        print("perfbench: starting the cluster once more", file=sys.stderr)
    measure.from_logs(run, run.texts, cell.traffic, seconds)
    if cell.config["checks"].get("reference") and run.rounds:
        # Only now: the window is closed, the peak is read and w0 is gone.
        end = t_start + (FIRST_END_S if first else LATER_END_S)
        limit = FIRST_REFERENCE_S if first else LATER_REFERENCE_S
        run.reference = run_reference(
            root, cell, seed, min(limit, end - time.monotonic()))
    if trace:
        run.spans = logs.read_spans(out_dir / "spans")
        run.profile = reduce_profile(root, out_dir, dict(os.environ))
    if run.cause is not None:
        report_failure(run)
    return run


def _attempt(cell, seed: int, seconds: float, run: Run, root: Path,
             setup_deadline: float, run_deadline: float) -> None:
    """Bring the cluster up, watch the window, take it down."""
    out_dir, trace = run.out_dir, run.trace
    shutil.rmtree(out_dir, ignore_errors=True)
    (out_dir / "spans").mkdir(parents=True)
    run.cause, run.arrivals = None, {}
    cluster = Cluster(root, out_dir, trace)
    try:
        cluster.open_work_dir()
        build_native(root, cluster.env)
        data_dir = cluster.run_dir / "counting"
        data.write_dataset(data_dir, cell.traffic, seed)
        gw = cluster.gateway
        net = ["--set", f"network.gateways={gw}"]
        cluster.start("gateway", "gateway", "run", "--set", f"network.listen={gw}")
        cluster.wait_for("gateway", r"gateway \S+ on ", setup_deadline)
        cluster.start("data", "data", "run", "--set", f"datasets.counting={data_dir}", *net)
        cluster.start(
            "ps", "worker", "run", "--name", "ps",
            "--set", "resources.tpu=0", "--set", "resources.cpu=2",
            "--set", f"work_root={cluster.work / 'ps'}", *net,
        )
        cluster.start(
            "w0", "worker", "run", "--name", "w0",
            "--set", "resources.tpu=1", "--set", "resources.cpu=4",
            "--set", "resources.memory=4096", "--set", "offer.strategy=whole",
            "--set", f"work_root={cluster.work / 'w0'}", *net,
        )
        cluster.wait_for("data", r"data node \S+ on ", setup_deadline)
        cluster.wait_for("ps", r"worker \S+ on ", setup_deadline)
        cluster.wait_for("w0", r"worker \S+ on ", setup_deadline)
        sets = [x for s in data.job_sets(cell.config, cell.traffic, seed) for x in ("--set", s)]
        run.events["scheduler_start"] = time.time()
        cluster.start("scheduler", "scheduler", "run", *net, *sets)
        _watch(cluster, run, seconds, setup_deadline, run_deadline)
        if trace:
            _wait_for_profile(cluster, out_dir)
    except RunFailure as e:
        run.cause = e.args[0]
    finally:
        run.holders = sorted(set(run.holders) | cluster.holders())
        run.texts = cluster.texts()
        run.returncodes = cluster.close()
    named = logs.named_cause(run.texts)
    if named and run.cause is not None and named.split(" (")[0] not in run.cause:
        run.cause = f"{named}; {run.cause}"


def _watch(cluster: Cluster, run: Run, seconds: float, setup_deadline: float,
           run_deadline: float) -> None:
    """From dispatch to the end of the window, or to round 1's close where
    that comes later: note the moment each round's line arrives, and who
    holds the chip."""
    tail = Tail(cluster.children["w0"].log_path)
    holders: set[str] = set()
    next_holders = 0.0
    try:
        while True:
            new = tail.lines()
            now = time.monotonic()
            for line in new:
                m = re.search(logs.ROUND_LINE, line)
                if m:
                    run.arrivals.setdefault(int(m.group(1)), now)
                elif re.search(logs.NAMED_CAUSES[0][1], line):
                    raise RunFailure(LEASE_LOST)
            if now >= next_holders:
                cluster.check_alive()
                holders |= cluster.holders()
                run.holders = sorted(holders)
                next_holders = now + HOLDERS_EVERY_S
            if 0 not in run.arrivals:
                if now > setup_deadline:
                    raise RunFailure("deadline: round 0 did not close in time")
            elif 1 not in run.arrivals:
                if now > run_deadline:
                    raise RunFailure("deadline: round 1 did not close in time")
            elif (
                not measure.next_round_fits(run.arrivals, seconds)
                or now - run.arrivals[0] > seconds
            ):
                return
            time.sleep(POLL_S)
    finally:
        tail.close()


def _wait_for_profile(cluster: Cluster, out_dir: Path) -> None:
    """The worker writes its trace after the round it covers; give it time
    before the SIGTERM."""
    marks = out_dir / "profile" / "marks.json"
    end = time.monotonic() + 90.0
    while not marks.is_file() and time.monotonic() < end:
        if cluster.children["w0"].proc.poll() is not None:
            return
        time.sleep(0.5)


def report_failure(run: Run) -> None:
    """The named cause and the last lines of every role's log, on stderr."""
    print(f"perfbench: run failed: {run.cause}", file=sys.stderr)
    for name, text in run.texts.items():
        print(
            f"--- {name} (return code {run.returncodes.get(name)}), last "
            f"{TAIL_LINES} lines of {run.out_dir / (name + '.log')}",
            file=sys.stderr,
        )
        for line in text.splitlines()[-TAIL_LINES:]:
            print(line, file=sys.stderr)
    sys.stderr.flush()
