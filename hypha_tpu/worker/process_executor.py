"""Process executor: run the training executor as a supervised subprocess.

Reference: crates/worker/src/executor/process.rs:78-198 — per-job work dir
``hypha-{uuid}`` containing the bridge socket; the configured command is
spawned with ``{SOCKET_PATH}`` / ``{WORK_DIR}`` / ``{JOB_JSON}`` placeholder
substitution in args (also exported as environment variables), plus
``{TRACE_DIR}`` / ``{TRACE_NODE}``: this worker's round-trace directory
(empty while tracing is off) and its peer id, which is how the child joins
the trace (telemetry.trace); stdout is
piped through the worker's log; cancellation sends SIGTERM and escalates to
SIGKILL after a 5 s grace period; the work dir is cleaned up afterwards.
"""

from __future__ import annotations

import asyncio
import json
import logging
import shutil
import signal
import uuid
from dataclasses import dataclass, field
from pathlib import Path

from .. import aio
from .. import messages
from ..messages import JobSpec
from ..network.node import Node
from ..telemetry import trace
from .bridge import Bridge
from .connectors import Connector
from .job_manager import Execution, JobExecutor

__all__ = ["ProcessExecutor", "GRACE_S"]

log = logging.getLogger("hypha.worker.process")

GRACE_S = 5.0  # SIGTERM -> SIGKILL escalation (process.rs:146-193)


@dataclass(slots=True)
class ProcessExecutor(JobExecutor):
    """Spawns ``cmd args...`` per job (config runtime=process,
    crates/worker/src/config.rs:135-141)."""

    node: Node
    cmd: str
    args: list[str] = field(default_factory=list)
    work_root: Path = field(default_factory=lambda: Path("/tmp"))
    keep_work_dir: bool = False

    async def execute(
        self, job_id: str, spec: JobSpec, scheduler_peer: str
    ) -> Execution:
        work_dir = Path(self.work_root) / f"hypha-{uuid.uuid4().hex[:12]}"
        work_dir.mkdir(parents=True, mode=0o700)
        # Durable control plane (ft.durable): the adoption grace and the
        # live-round probe ride the bridge exactly like the in-process
        # executor's — the subprocess boundary changes nothing about the
        # scheduler re-adoption handshake.
        grace = float(
            getattr(spec.executor.train, "adopt_grace_s", 0) or 0
        ) if spec.executor.train is not None else 0.0
        probe_target: list = []

        def probe(progress) -> None:
            for execution in probe_target:
                if progress.round > execution.round:
                    execution.round = progress.round

        from .slice_cache import SliceCache

        bridge = Bridge(
            self.node,
            work_dir,
            job_id,
            scheduler_peer,
            Connector(
                self.node, scheduler_peer,
                slice_cache=SliceCache(Path(self.work_root) / "slice-cache"),
            ),
            status_retry_s=grace,
            progress_probe=probe,
        )
        socket_path = await bridge.start()
        job_json = json.dumps(messages.to_json_dict(spec))
        tracing = trace.active()
        subst = {
            "SOCKET_PATH": str(socket_path),
            "WORK_DIR": str(work_dir),
            "JOB_JSON": job_json,
            "TRACE_DIR": str(tracing.trace_dir.resolve()) if tracing else "",
            "TRACE_NODE": self.node.peer_id,
        }
        argv = [self.cmd] + [_substitute(a, subst) for a in self.args]
        proc = await asyncio.create_subprocess_exec(
            *argv,
            stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.STDOUT,
            env=_env_with(subst),
            cwd=str(work_dir),
        )
        log.info("job %s: spawned pid %s: %s", job_id, proc.pid, argv[:2])
        # Tree-reduce (hypha_tpu.stream.reduce): the reducer consumes
        # fabric pushes, so it lives HERE in the runtime, not in the
        # spawned executor process.
        from ..stream.reduce import maybe_start_reducer

        reducer = maybe_start_reducer(self.node, spec)
        execution = _ProcessExecution(
            job_id, proc, bridge, work_dir, self.keep_work_dir,
            reducer=reducer,
        )
        execution.adopt_grace_s = grace or None
        probe_target.append(execution)
        execution.start_supervision()
        return execution


def _substitute(arg: str, subst: dict[str, str]) -> str:
    for key, value in subst.items():
        arg = arg.replace("{" + key + "}", value)
    return arg


def _env_with(subst: dict[str, str]) -> dict[str, str]:
    import os

    env = dict(os.environ)
    env.update(subst)
    return env


class _ProcessExecution(Execution):
    def __init__(
        self,
        job_id: str,
        proc: asyncio.subprocess.Process,
        bridge: Bridge,
        work_dir: Path,
        keep_work_dir: bool,
        reducer=None,
    ) -> None:
        super().__init__(job_id)
        self.proc = proc
        self.bridge = bridge
        self.work_dir = work_dir
        self.keep_work_dir = keep_work_dir
        self.reducer = reducer
        self._cancelled = False
        self._tasks: list[asyncio.Task] = []

    def start_supervision(self) -> None:
        self._tasks.append(
            aio.spawn(self._pump_stdout(), what="executor stdout pump", logger=log)
        )
        self._tasks.append(
            aio.spawn(self._supervise(), what="executor supervise", logger=log)
        )

    async def _pump_stdout(self) -> None:
        """Pipe executor stdout through our log (process.rs:140-169)."""
        assert self.proc.stdout is not None
        while True:
            line = await self.proc.stdout.readline()
            if not line:
                return
            log.info("[%s] %s", self.job_id, line.decode(errors="replace").rstrip())

    async def _supervise(self) -> None:
        rc = await self.proc.wait()
        if self.reducer is not None:
            await self.reducer.stop()
        await self.bridge.stop()
        if not self.keep_work_dir:
            await asyncio.to_thread(  # process.rs:191-192
                shutil.rmtree, self.work_dir, ignore_errors=True
            )
        if self._cancelled:
            self.finish("cancelled")
        elif rc == 0:
            self.finish("completed")
        else:
            self.finish("failed", f"exit code {rc}")

    async def cancel(self) -> None:
        """SIGTERM, then SIGKILL after the grace period (process.rs:146-193)."""
        if self._cancelled or self.proc.returncode is not None:
            return
        self._cancelled = True
        try:
            self.proc.send_signal(signal.SIGTERM)
        except ProcessLookupError:
            return
        try:
            await asyncio.wait_for(self.proc.wait(), GRACE_S)
        except asyncio.TimeoutError:
            log.warning("job %s ignored SIGTERM; killing", self.job_id)
            try:
                self.proc.kill()
            except ProcessLookupError:
                pass
