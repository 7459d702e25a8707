"""Worker runtime: the full compute-selling node.

Composes the worker stack the way the ``hypha-worker`` binary wires its
Arbiter (crates/worker/src/bin/hypha-worker.rs:219-233):

    Node                  — fabric endpoint (mTLS identity, RPC, gossip,
                            streams, discovery)
    StaticResourceManager — configured capacity minus live reservations
    LeaseManager          — atomic reserve + ledger
    JobManager            — routes jobs to executors
    Arbiter               — auction + leases + dispatch + prune
    health                — readiness = listening + bootstrapped
                            (hypha-worker.rs:85-87,199-200)

Default executor table (crates/worker/src/config.rs:114-191):
    ("train", "diloco-transformer")  → in-process JAX executor (TPU-native
                                       default) or a configured process
                                       executor (reference behavior)
    ("aggregate", "parameter-server") → in-runtime parameter server
"""

from __future__ import annotations

import logging
from pathlib import Path

from ..health import serve_health
from ..messages import AGGREGATE_EXECUTOR_NAME, INFER_EXECUTOR_NAME, TRAIN_EXECUTOR_NAME
from ..network.fabric import Transport
from ..network.node import Node
from ..resources import Resources
from .arbiter import Arbiter, OfferConfig
from .job_manager import JobExecutor, JobManager
from .lease_manager import LeaseManager
from .process_executor import ProcessExecutor
from .ps_executor import ParameterServerExecutor
from .resources_mgr import StaticResourceManager
from .train_executor import InProcessTrainExecutor

__all__ = ["WorkerNode", "TRAIN_EXECUTOR_NAME", "AGGREGATE_EXECUTOR_NAME"]

log = logging.getLogger("hypha.worker")


class WorkerNode:
    def __init__(
        self,
        transport: Transport,
        *,
        resources: Resources,
        peer_id: str | None = None,
        offer: OfferConfig | None = None,
        executors: dict[tuple[str, str], JobExecutor] | None = None,
        train_runtime: str = "in-process",  # "in-process" | "process"
        train_cmd: str | None = None,
        train_args: list[str] | None = None,
        work_root: Path | str = "/tmp",
        max_batches: int | None = None,
        node: Node | None = None,
        **node_kwargs,
    ) -> None:
        # ``node`` injection: the CLI passes an mTLS-secured Node.
        self.node = node or Node(transport, peer_id=peer_id, **node_kwargs)
        self.resource_manager = StaticResourceManager(resources)
        self.lease_manager = LeaseManager(self.resource_manager)
        work_root = Path(work_root)
        if executors is None:
            executors = {}
            if train_runtime == "process":
                if not train_cmd:
                    raise ValueError("train_runtime=process needs train_cmd")
                executors[("train", TRAIN_EXECUTOR_NAME)] = ProcessExecutor(
                    node=self.node,
                    cmd=train_cmd,
                    args=train_args
                    or [
                        "-m",
                        "hypha_tpu.executor.training",
                        "--socket", "{SOCKET_PATH}",
                        "--work-dir", "{WORK_DIR}",
                        "--job", "{JOB_JSON}",
                        "--trace-dir", "{TRACE_DIR}",
                        "--trace-node", "{TRACE_NODE}",
                    ],
                    work_root=work_root,
                )
            else:
                executors[("train", TRAIN_EXECUTOR_NAME)] = InProcessTrainExecutor(
                    node=self.node, work_root=work_root, max_batches=max_batches
                )
            executors[("aggregate", AGGREGATE_EXECUTOR_NAME)] = (
                ParameterServerExecutor(self.node, work_root, cpus=resources.cpu)
            )
            # Serving (net-new; BASELINE config 4): every worker can host
            # infer jobs — the model loads lazily on dispatch.
            from .infer_executor import InProcessInferExecutor

            executors[("infer", INFER_EXECUTOR_NAME)] = InProcessInferExecutor(
                self.node, work_root
            )
        self.job_manager = JobManager(self.node, executors)
        self.arbiter = Arbiter(
            node=self.node,
            lease_manager=self.lease_manager,
            job_manager=self.job_manager,
            offer=offer or OfferConfig(),
        )
        self._health = None
        self._ready = False

    @property
    def peer_id(self) -> str:
        return self.node.peer_id

    async def start(self, listen: list[str] | None = None) -> None:
        await self.node.start(listen)
        # Bandwidth gauges on the process-global registry: worker fabrics
        # hosting PS shards and serving executors never pass through a
        # cli.py entrypoint in tests/benches, yet their inbound/outbound
        # byte counters are exactly what shard/serve benches read.
        from ..telemetry import global_telemetry, instrument_node

        instrument_node(
            global_telemetry().meter(f"hypha.node.{self.peer_id}"), self.node
        )
        self._health = serve_health(self.node, lambda: self._ready)
        await self.node.wait_for_bootstrap()
        await self.arbiter.start()
        self._ready = True
        log.info("worker %s ready (%s)", self.peer_id, self.resource_manager.capacity())

    async def stop(self) -> None:
        self._ready = False
        from ..telemetry import global_telemetry

        # Mirror of start()'s gauge registration: a long pytest/bench
        # process starts hundreds of workers, and leaked gauge closures
        # would pin every dead Node (and report its frozen byte counters
        # as a live fabric) for process lifetime.
        global_telemetry().meter(f"hypha.node.{self.peer_id}").remove_gauges()
        if self._health is not None:
            self._health.close()
        await self.arbiter.stop()
        await self.node.stop()
