"""A second witness for what a later round's first loss reads: is it the
published outer step, or a fault in the PS's momentum or fold state?

``python3 tests/perfbench/outer_step_witness.py --workload <cell> --seed <n>
[--rounds 3]`` from the root of a checkout, on the machine that holds the
chip. It runs the cell once by the driver's command and, beside it, keeps a
hard link to every ``delta-<r>.safetensors`` the worker writes and every
``update-<r>.safetensors`` the PS writes for the first ``--rounds`` rounds
(the program unlinks both once the broadcast is merged; a link costs no
copy and is on the same memory-backed filesystem). When the run is over it
replays the outer step in plain numpy on the worker's own deltas,

    m_r = mu * m_{r-1} + delta_r;   update_r = lr * (mu * m_r + delta_r),

and holds every leaf of every update file the PS wrote against it. It
imports nothing of the program and reads nothing the PS computed but the
file it compares. Not part of a run of the benchmark: ``run.py`` never
calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
from safetensors import safe_open

LR, MU = 0.7, 0.9  # node_config's defaults, which no cell changes (PERF.md 4)
KEPT = re.compile(r"^(delta|update)-(\d+)\.safetensors$")


def replay(keep: Path, rounds: int, lr: float = LR, mu: float = MU) -> list[dict]:
    """Per round: the largest gap between the PS's update and the replay over
    all leaves, as a share of that leaf's largest update, and the norms that
    say what the momentum carried. Leaf by leaf, so that it fits beside
    nothing else: the momentum of every leaf is kept (one model's worth)."""
    momentum: dict[str, np.ndarray] = {}
    out = []
    for r in range(rounds):
        d_path, u_path = keep / f"delta-{r}.safetensors", keep / f"update-{r}.safetensors"
        if not (d_path.is_file() and u_path.is_file()):
            break
        worst, worst_leaf = 0.0, None
        d_sq = u_sq = m_sq = 0.0
        with safe_open(str(d_path), "np") as deltas, safe_open(str(u_path), "np") as updates:
            names = sorted(deltas.keys())
            assert names == sorted(updates.keys()), "the update has other leaves than the delta"
            for name in names:
                delta = deltas.get_tensor(name).astype(np.float32)
                m = momentum[name] = mu * momentum.get(name, np.float32(0.0)) + delta
                mine = np.float32(lr) * (np.float32(mu) * m + delta)
                theirs = updates.get_tensor(name).astype(np.float32)
                scale = float(np.max(np.abs(mine))) or 1.0
                gap = float(np.max(np.abs(theirs - mine))) / scale
                if gap > worst:
                    worst, worst_leaf = gap, name
                d_sq += float(np.vdot(delta, delta))
                u_sq += float(np.vdot(theirs, theirs))
                m_sq += float(np.vdot(m, m))
        out.append({"round": r, "leaves": len(names), "worst_gap": worst, "worst_leaf": worst_leaf,
                    "delta_norm": d_sq ** 0.5, "update_norm": u_sq ** 0.5, "momentum_norm": m_sq ** 0.5})
    return out


def keep_links(run_dir: Path, keep: Path, rounds: int, stop: threading.Event) -> None:
    """Hard links to the worker's deltas and the PS's updates as they appear."""
    while not stop.is_set():
        for role, kind in (("w0", "delta"), ("ps", "update")):
            for path in run_dir.glob(f"{role}/**/{kind}-*.safetensors"):
                m = KEPT.match(path.name)
                if m and int(m.group(2)) < rounds:
                    try:
                        os.link(path, keep / path.name)
                    except OSError:
                        pass  # there already, or gone between the glob and the link
        stop.wait(0.02)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rounds", type=int, default=3)
    args = parser.parse_args(argv)
    root = Path.cwd()
    run_dir = root / "chiprun_out" / "pb-run"
    keep = Path("/dev/shm") / f"witness-of-perfbench-{os.getpid()}"  # not a name the harness sweeps
    keep.mkdir()
    stop = threading.Event()
    linker = threading.Thread(target=keep_links, args=(run_dir, keep, args.rounds, stop), daemon=True)
    try:
        linker.start()
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, "perfbench/run.py", "--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "51", "--trace", "0"], cwd=str(root), capture_output=True, text=True)
        stop.set()
        linker.join()
        notes = {}
        for line in r.stdout.splitlines():
            try:
                o = json.loads(line)
            except ValueError:
                continue
            if o.get("phase") == "round":
                notes.setdefault("rounds", []).append(
                    {k: o.get(k) for k in ("round", "loss_first", "loss_last", "loss_mean")})
            elif "correct" in o:
                notes["result"] = {k: o[k] for k in ("correct", "attempted", "failed", "metrics")}
        print(json.dumps({"run": {"rc": r.returncode, "wall_s": time.monotonic() - t0, **notes},
                          "kept": sorted(p.name for p in keep.iterdir())}), flush=True)
        if r.returncode != 0:
            print(r.stderr[-3000:], file=sys.stderr)
        rows = replay(keep, args.rounds)
        for row in rows:
            print(json.dumps({"replay": row}), flush=True)
        first = rows[0]["delta_norm"] if rows else None
        print(json.dumps({"witness": {
            "rounds_replayed": len(rows), "lr": LR, "mu": MU,
            "worst_gap": max((x["worst_gap"] for x in rows), default=None),
            # what a worker that has nothing left to learn gets: momentum alone
            "update_over_first_delta": [x["update_norm"] / first for x in rows] if first else None,
            "delta_over_first_delta": [x["delta_norm"] / first for x in rows] if first else None,
        }}), flush=True)
        return 0 if rows else 1
    finally:
        stop.set()
        shutil.rmtree(keep, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
