"""The quickest proof that hypha-tpu still starts on the chip.

Default (one chip): the README quickstart as a script — one DiLoCo job on
localhost through the normal entry points, ``python -m hypha_tpu`` gateway /
data / worker / scheduler, training GPT-2-small at its full published size
for two outer rounds on counting sequences written from ``--seed``. The
worker ``w0`` is the one process that holds the TPU; the gateway, the data
node, the scheduler, the parameter-server worker (``resources.tpu=0``) and
this script never initialise a JAX backend, and the script checks that.

``--chips 4``: only the sharded train step (``job.sharding``, fsdp=2 x tp=2)
against the same steps unsharded on one of those chips, in this one process.

Every line on stdout is one JSON object. The last is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
with the device as the process that ran the step reported it. Any phase
that fails ends the run with ``"ok": false`` and a non-zero exit code; no
flag turns the device check off. A CPU rehearsal (``JAX_PLATFORMS=cpu``)
shrinks the model with ``--set job.model_config.<field>=...``, completes
the job, and then fails at that check.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
from importlib import metadata
from pathlib import Path

HERE = Path(__file__).resolve().parent
LOG_DIR = HERE / "chiprun_out" / "chip_smoke"
SEQ = 1024  # GPT-2's published context; the slices are written at this length
DATA_MODULUS = 256  # counting sequences: next id = (id + 1) % 256, ids < 50257
ROUNDS = 2
DEADLINE_S = 1000.0  # the contract allows 1200 s, compilation included
# A run whose worker did not get the TPU can only rehearse control flow at
# a tiny size; it gets this long, so a full-size job on a CPU fails soon.
REHEARSAL_DEADLINE_S = 300.0

# The job, as `scheduler run --set` strings; the user's --set come after
# these and win. Batch: the auction sizes it as offered/required chips, so a
# one-chip worker selling whole and a job asking 1/16 chip per sample gives 16.
JOB_SETS = [
    "job.dataset=counting",
    "job.model_family=gpt2",
    "job.model_preset=small",
    "job.model_type=causal-lm",
    f"job.update_rounds={ROUNDS}",
    "job.num_workers=1",
    "job.worker_tpu=0.0625",
    "job.max_batch_size=16",
    "job.avg_samples_between_updates=128",
]
SHARDING_SETS = ["job.sharding.fsdp=2", "job.sharding.tp=2"]
FOUR_CHIP_STEPS = 4
# Sharded (XLA dense attention, reductions split over tp) against unsharded
# (flash kernel): both compute in bf16, whose rounding step is 2**-8; two
# roundings apart is the bound, set here before any run.
LOSS_RTOL = 2.0**-7


# What executor/training.py logs before it builds the model.
DEVICE_LINE = r"device: platform=(\S+) kind='([^']*)' count=(\d+)"


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def versions() -> dict:
    out = {"python": sys.version.split()[0]}
    for dist in ("jax", "jaxlib", "libtpu", "flax", "optax", "numpy"):
        try:
            out[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            out[dist] = None
    return out


def counting_batch(rng, rows: int):
    import numpy as np

    starts = rng.integers(0, DATA_MODULUS, (rows, 1))
    return ((starts + np.arange(SEQ)) % DATA_MODULUS).astype(np.int32)


# ------------------------------------------------------------------ one chip


class Child:
    """One CLI role as an OS process, its output in a log file."""

    def __init__(self, name: str, args: list[str], env: dict) -> None:
        self.name = name
        self.log_path = LOG_DIR / f"{name}.log"
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "hypha_tpu", *args],
            stdout=self.log, stderr=subprocess.STDOUT, env=env, cwd=str(HERE),
        )

    def text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def wait_for(self, pattern: str, deadline: float) -> "re.Match[str]":
        while True:
            m = re.search(pattern, self.text())
            if m:
                return m
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"logging {pattern!r}"
                )
            if time.monotonic() > deadline:
                raise SmokeFailure(f"{self.name}: {pattern!r} not logged in time")
            time.sleep(0.25)

    def maps_libtpu(self) -> bool:
        """Whether the live process has mapped libtpu — what initialising
        the TPU backend does, and importing jax does not."""
        try:
            return "libtpu" in Path(f"/proc/{self.proc.pid}/maps").read_text()
        except OSError:
            return False

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_fields(line: str) -> dict:
    """``key=value`` pairs of one log line, numbers typed."""
    out: dict = {}
    for key, raw in re.findall(r"(\w+)=('[^']*'|\S+)", line):
        raw = raw.strip("'")
        try:
            out[key] = int(raw)
        except ValueError:
            try:
                out[key] = float(raw)
            except ValueError:
                out[key] = {"True": True, "False": False, "None": None}.get(raw, raw)
    return out


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def one_chip(args) -> dict:
    import numpy as np
    from safetensors.numpy import save_file

    # Built here once (no jax involved), so five children starting together
    # load the libraries and do not race to compile them.
    from hypha_tpu import codec, native

    built = {
        "ps_kernels": native.native_available(),
        "cbor_codec": codec.native_codec_active(),
    }
    emit({"phase": "native", **built})
    if not all(built.values()):
        raise SmokeFailure(f"native libraries did not build: {built}")

    deadline = time.monotonic() + DEADLINE_S
    run_dir = Path(tempfile.mkdtemp(prefix="hypha-smoke-"))
    data_dir = run_dir / "counting"
    data_dir.mkdir()
    rng = np.random.default_rng(args.seed)
    for i in range(8):  # 512 sequences: two rounds of 128 and to spare
        save_file(
            {"input_ids": counting_batch(rng, 64)},
            str(data_dir / f"slice_{i:04d}.safetensors"),
        )

    env = dict(os.environ)  # JAX_COMPILATION_CACHE_DIR passes through
    env["PYTHONPATH"] = str(HERE) + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    gw_addr = f"127.0.0.1:{free_port()}"
    net = ["--set", f"network.gateways={gw_addr}"]
    children: dict[str, Child] = {}

    def start(name: str, *cli: str) -> Child:
        children[name] = Child(name, list(cli), env)
        return children[name]

    try:
        start("gateway", "gateway", "run", "--set", f"network.listen={gw_addr}")
        children["gateway"].wait_for(r"gateway \S+ on ", deadline)
        start("data", "data", "run", "--set", f"datasets.counting={data_dir}", *net)
        # No chip to sell: the auction cannot land the train job here.
        start(
            "ps", "worker", "run", "--name", "ps",
            "--set", "resources.tpu=0", "--set", "resources.cpu=2",
            "--set", f"work_root={run_dir / 'ps'}", *net,
        )
        start(
            "w0", "worker", "run", "--name", "w0",
            "--set", "resources.tpu=1", "--set", "resources.cpu=4",
            "--set", "resources.memory=4096", "--set", "offer.strategy=whole",
            "--set", f"work_root={run_dir / 'w0'}", *net,
        )
        children["data"].wait_for(r"data node \S+ on ", deadline)
        children["ps"].wait_for(r"worker \S+ on ", deadline)
        w0 = children["w0"]
        w0.wait_for(r"worker \S+ on ", deadline)
        cache_dir = w0.wait_for(r"compile cache: (\S+)", deadline).group(1)
        emit({
            "phase": "cluster", "roles": sorted(children), "gateway": gw_addr,
            "compile_cache": cache_dir, "cache_entries": cache_entries(cache_dir),
        })

        sets = [x for s in JOB_SETS + args.set for x in ("--set", s)]
        t_job = time.monotonic()
        sched = start("scheduler", "scheduler", "run", *net, *sets)
        holders: set[str] = set()  # who mapped libtpu, sampled while alive
        device_seen = False
        while sched.proc.poll() is None:
            if time.monotonic() > deadline:
                raise SmokeFailure("scheduler did not finish in time")
            if w0.proc.poll() is not None:
                raise SmokeFailure(f"worker w0 died rc={w0.proc.returncode}")
            holders.update(n for n, c in children.items() if c.maps_libtpu())
            if not device_seen and (m := re.search(DEVICE_LINE, w0.text())):
                device_seen = True
                if m.group(1) != "tpu":
                    deadline = min(deadline, t_job + REHEARSAL_DEADLINE_S)
            time.sleep(1.0)
        job_wall = time.monotonic() - t_job
        if sched.proc.returncode != 0:
            raise SmokeFailure(f"scheduler exited rc={sched.proc.returncode}")
        done = re.search(r"completed: (\d+) rounds", sched.text())
        if not done:
            raise SmokeFailure("scheduler exited 0 without 'completed: N rounds'")
        holders.update(n for n, c in children.items() if c.maps_libtpu())
        logs = {n: c.text() for n, c in children.items()}
    except SmokeFailure:
        for c in children.values():
            emit({
                "log": c.name, "returncode": c.proc.poll(),
                "tail": c.text().splitlines()[-30:],
            })
        raise
    finally:
        for c in reversed(list(children.values())):
            c.stop()
        shutil.rmtree(run_dir, ignore_errors=True)

    # ---- what the processes said
    dev = re.search(DEVICE_LINE, logs["w0"])
    att = re.search(r"attention path: (.*)", logs["w0"])
    if not dev or not att:
        raise SmokeFailure("worker w0 logged no device or attention line")
    device = {"platform": dev.group(1), "kind": dev.group(2), "count": int(dev.group(3))}
    attention = att.group(1)
    holders = sorted(holders)
    rounds = [
        parse_fields(m.group(0))
        for m in re.finditer(r"round \d+ done: .*", logs["w0"])
    ]
    outer = [
        parse_fields(m.group(0))
        for m in re.finditer(r"ps outer step: .*", logs["ps"])
    ]
    pushed = len(re.findall(r"round \d+ delta 1/1 \(from w0\)", logs["ps"]))
    setup = re.search(r"setup done: setup_s=(\S+)", logs["w0"])
    emit({
        "phase": "worker", "device": device, "attention": attention,
        "setup_s": float(setup.group(1)) if setup else None,
    })
    for r in rounds:
        emit({"phase": "round", **r})
    for o in outer:
        emit({"phase": "outer_step", **o})
    emit({
        "phase": "job", "completed_rounds": int(done.group(1)),
        "wall_s": round(job_wall, 1), "deltas_pushed": pushed,
        "cache_entries": cache_entries(cache_dir),
        "libtpu_mapped_by": holders,
    })

    # ---- what must be true
    checks = {
        "two_rounds_completed": int(done.group(1)) == ROUNDS and len(rounds) == ROUNDS,
        "delta_pushed_each_round": pushed == ROUNDS,
        "outer_update_each_round": len(outer) == ROUNDS,
        "native_ps_and_codec_loaded": bool(outer) and all(
            o["native_kernels"] is True and o["native_cbor"] is True for o in outer
        ),
        "losses_finite": bool(rounds) and all(
            r["nonfinite"] == 0 and math.isfinite(r["loss_mean"]) for r in rounds
        ),
        "loss_fell": len(rounds) == ROUNDS
        and rounds[-1]["loss_mean"] < rounds[0]["loss_mean"],
        # A recompile in round 1 would cost what round 0's first step did.
        "no_recompile_in_round_1": len(rounds) == ROUNDS
        and rounds[1]["first_step_s"] < 0.5 * rounds[0]["first_step_s"],
        "only_w0_holds_the_chip": holders in ([], ["w0"]),
        "attention_is_compiled_flash": attention.startswith(
            "pallas flash kernel, compiled"
        ),
        "device_is_tpu": device["platform"] == "tpu" and holders == ["w0"],
    }
    emit({"phase": "checks", **checks})
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SmokeFailure(f"checks failed: {failed}", device)
    return device


# ---------------------------------------------------------------- four chips


def four_chips(args) -> dict:
    """Sharded against unsharded, in this one process — the only phase."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from hypha_tpu import cli
    from hypha_tpu.executor.train import TrainState, build_optimizer, make_train_step
    from hypha_tpu.executor.training import _build_mesh, _init_model
    from hypha_tpu.hw import enable_compile_cache
    from hypha_tpu.messages import (
        Fetch, Receive, Reference, Send, TrainExecutorConfig,
    )
    from hypha_tpu.parallel import param_sharding
    from hypha_tpu.parallel.sharding import batch_spec

    cache_dir = enable_compile_cache()
    # The job exactly as `scheduler run` would read it from these --set.
    sets = [x for s in JOB_SETS + SHARDING_SETS + args.set for x in ("--set", s)]
    conf = cli._load_config(
        "scheduler", cli.build_parser().parse_args(["scheduler", "run", *sets])
    )
    job = conf.job.to_job()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit({
        "phase": "setup", "device": device, "compile_cache": cache_dir,
        "sharding": job.sharding, "batch": job.rounds.max_batch_size,
        "steps": FOUR_CHIP_STEPS,
    })
    ids = counting_batch(np.random.default_rng(args.seed), job.rounds.max_batch_size)
    unused = Reference.from_peers(["unused"], "unused")

    def run(sharding: dict | None) -> dict:
        cfg = TrainExecutorConfig(
            model=job.model, data=Fetch(unused), updates=Send(unused),
            results=Receive(unused), optimizer=job.inner_optimizer,
            batch_size=len(ids), sharding=sharding,
        )
        batch = {"input_ids": ids}
        model, params, causal_lm, has_aux = _init_model(cfg, None, HERE, batch)
        mesh = _build_mesh(cfg.sharding)
        state = TrainState.create(params, build_optimizer(cfg.optimizer))
        step = make_train_step(model.apply, causal_lm=causal_lm, has_aux=has_aux)
        if mesh is not None:  # run_training's placement
            state = jax.device_put(state, param_sharding(state, mesh))
            batch = jax.device_put(batch, NamedSharding(mesh, batch_spec()))
        # For its text only: the steps below go through the jitted step, as
        # run_training's do.
        t0 = time.monotonic()
        text = step.lower(state, batch).compile().as_text()
        compile_s = time.monotonic() - t0
        per_device: dict[int, int] = {}
        for leaf in jax.tree.leaves(state.params):
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] = (
                    per_device.get(shard.device.id, 0) + shard.data.nbytes
                )
        losses, step_s = [], []
        for _ in range(FOUR_CHIP_STEPS):
            t0 = time.monotonic()
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
            step_s.append(round(time.monotonic() - t0, 4))
        return {
            "sharding": sharding,
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "attention": "pallas flash kernel, compiled"
            if "tpu_custom_call" in text else "XLA dense",
            "param_bytes_per_device": dict(sorted(per_device.items())),
            "collectives": {
                op: n for op in (
                    "all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute",
                ) if (n := len(re.findall(rf"\b{op}(?:-start)?\(", text)))
            },
            "compile_s": round(compile_s, 2), "step_s": step_s,
            "step_programs": step._cache_size(), "losses": losses,
            "peak_bytes_per_device_so_far": {
                d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.local_devices()
            },
        }

    sharded = run(job.sharding)
    emit({"phase": "sharded", **sharded})
    single = run(None)
    emit({"phase": "unsharded", **single})

    pairs = list(zip(sharded["losses"], single["losses"]))
    checks = {
        "losses_finite": all(math.isfinite(x) for p in pairs for x in p),
        "losses_agree": all(abs(a - b) <= LOSS_RTOL * abs(b) for a, b in pairs),
        "params_on_every_device": len(sharded["param_bytes_per_device"]) == 4
        and min(sharded["param_bytes_per_device"].values()) > 0,
        "unsharded_on_one_device": len(single["param_bytes_per_device"]) == 1,
        "collectives_present": bool(sharded["collectives"]),
        "device_is_tpu_x4": device["platform"] == "tpu" and device["count"] == 4,
    }
    emit({
        "phase": "checks", "loss_rtol": LOSS_RTOL,
        "max_rel_diff": max(abs(a - b) / abs(b) for a, b in pairs), **checks,
    })
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SmokeFailure(f"checks failed: {failed}", device)
    return device


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="handed through to `scheduler run` after the job's own",
    )
    args = parser.parse_args(argv)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run the finallys
    emit({"phase": "start", "chips": args.chips, "seed": args.seed, **versions()})
    try:
        if not (HERE / "hypha_tpu" / "cli.py").is_file():
            raise SmokeFailure(f"no hypha_tpu package beside {Path(__file__).name}")
        sys.path.insert(0, str(HERE))
        LOG_DIR.mkdir(parents=True, exist_ok=True)
        device = four_chips(args) if args.chips == 4 else one_chip(args)
    except SmokeFailure as e:
        verdict = {"ok": False, "error": e.args[0]}
        if len(e.args) > 1:
            verdict["device"] = e.args[1]
        emit(verdict)
        return 1
    except Exception as e:  # anything else a phase raised: same verdict
        traceback.print_exc()
        emit({"ok": False, "error": repr(e)})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
