"""The node CLI: ``hypha-tpu {gateway|scheduler|worker|data} {init|probe|run}``.

Reference: every binary exposes the same three subcommands
(e.g. crates/scheduler/src/bin/hypha-scheduler.rs:459-548) —

  * ``init``  — emit a documented default config TOML
                (crates/data/src/bin/hypha-data.rs:239-272);
  * ``probe`` — dial an address and run the health protocol
                (hypha-scheduler.rs:494-535);
  * ``run``   — layered config (TOML ← HYPHA_* env ← CLI) → validate →
                role runtime → serve until SIGINT/SIGTERM → ordered
                shutdown (§3.3 bootstrap skeleton).

Certificate management lives in the separate ``hypha-certutil`` CLI
(hypha_tpu.certutil).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import sys
from pathlib import Path

from . import config as cfg
from .node_config import (
    DataNodeConfig,
    GatewayConfig,
    SchedulerConfig,
    WorkerConfig,
)

log = logging.getLogger("hypha.cli")

_SCHEMAS = {
    "gateway": GatewayConfig,
    "scheduler": SchedulerConfig,
    "worker": WorkerConfig,
    "data": DataNodeConfig,
}


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------


def _load_config(role: str, args) -> object:
    builder = cfg.builder(_SCHEMAS[role])
    if args.config:
        builder.with_toml(args.config)
    builder.with_env("HYPHA_")
    overrides = {}
    for item in args.set or []:
        key, sep, value = item.partition("=")
        if not sep:
            raise cfg.ConfigError(f"--set needs key=value, got {item!r}")
        overrides[key.strip()] = _parse_cli_value(value.strip())
    if args.name:
        overrides["name"] = args.name
    built = builder.with_overrides(overrides, "cli").build().validate()
    return built.value


def _parse_cli_value(raw: str):
    """``--set`` values are strings; interpret them as TOML values so ints,
    floats, bools and arrays come through typed. Bare strings stay strings."""
    try:  # py3.11+ stdlib; tomli on 3.10 (same fallback as config.py)
        import tomllib
    except ModuleNotFoundError:
        import tomli as tomllib  # type: ignore[no-redef]

    try:
        return tomllib.loads(f"v = {raw}")["v"]
    except tomllib.TOMLDecodeError:
        return raw


def _make_node(conf, *, registry_server: bool = False, peer_id: str | None = None):
    """Transport from the TLS section: mTLS when configured, plain TCP
    otherwise (dev mode)."""
    from .network.node import Node

    node_kwargs = dict(
        bootstrap=list(conf.network.gateways),
        registry_server=registry_server,
        exclude_cidrs=list(conf.network.exclude_cidrs),
        # Non-gateway nodes hold circuit reservations at their gateways so
        # NAT'd peers stay reachable (reference listens on relay circuits by
        # default, crates/network/src/listen.rs:25-131).
        relay_listen=not registry_server and getattr(conf.network, "relay", True),
        advertise_listen=getattr(conf.network, "advertise_listen", True),
    )
    if conf.tls.enabled():
        from .network.secure import secure_node

        node = secure_node(
            conf.tls.cert,
            conf.tls.key,
            conf.tls.trust,
            conf.tls.crls or None,
            **node_kwargs,
        )
    else:
        from .network.fabric import TcpTransport

        node = Node(TcpTransport(), peer_id=peer_id or conf.name, **node_kwargs)
    if getattr(conf.network, "mux", False):
        from .network.mux import MuxTransport

        node.transport = MuxTransport(node.transport)
    node.external_addrs = list(conf.network.external)
    return node


def _telemetry_for(conf, node=None):
    """Provider bundle from the config's telemetry section; OTEL_* env wins
    (reference wiring: hypha-scheduler.rs:55-94, docs/worker.md:188-218).
    ``telemetry.trace_dir`` switches the round-trace spans on, under the
    node's name."""
    from .telemetry import init_telemetry, instrument_node, trace

    if conf.telemetry.trace_dir:
        trace.enable(conf.telemetry.trace_dir, node=conf.name)
    telemetry = init_telemetry(
        service_name=conf.telemetry.service_name or f"hypha-{conf.name}",
        endpoint=conf.telemetry.endpoint,
        sample_ratio=conf.telemetry.sample_ratio,
        attributes=conf.telemetry.attributes,
    )
    if node is not None:
        instrument_node(telemetry.meter("hypha.node"), node)
    return telemetry


async def _serve_until_signal(*stoppables) -> None:
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # non-unix
            pass
    await stop.wait()
    log.info("shutting down")
    for s in stoppables:
        await s.stop()


def _cmd_init(role: str, args) -> int:
    schema = _SCHEMAS[role]()
    if args.name:
        schema.name = args.name
    text = cfg.to_toml(schema)
    out = Path(args.output or f"{role}.toml")
    out.write_text(text)
    print(f"wrote {out}")
    return 0


def _cmd_probe(role: str, args) -> int:
    async def main() -> bool:
        from .health import probe
        from .network.fabric import TcpTransport
        from .network.node import Node

        if args.config:
            conf = _load_config(role, args)
            node = _make_node(conf, peer_id=f"probe-{conf.name}")
        else:
            node = Node(TcpTransport(), peer_id="probe")
        await node.start(["127.0.0.1:0"])
        try:
            return await probe(node, args.addr, timeout=args.timeout)
        finally:
            await node.stop()

    healthy = asyncio.run(main())
    print("healthy" if healthy else "unhealthy")
    return 0 if healthy else 1


# --------------------------------------------------------------------------
# run per role
# --------------------------------------------------------------------------


async def _run_gateway(conf: GatewayConfig) -> None:
    from .gateway import Gateway

    gw = Gateway(None, node=_make_node(conf, registry_server=True))
    telemetry = _telemetry_for(conf, gw.node)
    try:
        await gw.start(list(conf.network.listen))
        print(f"gateway {gw.peer_id} on {gw.node.listen_addrs}", flush=True)
        await _serve_until_signal(gw)
    finally:
        telemetry.shutdown()


async def _run_data(conf: DataNodeConfig) -> None:
    from .data_node import DataNode

    dn = DataNode(
        None,
        {name: Path(p) for name, p in conf.datasets.items()},
        node=_make_node(conf),
    )
    telemetry = _telemetry_for(conf, dn.node)
    try:
        await dn.start(list(conf.network.listen))
        print(f"data node {dn.peer_id} on {dn.node.listen_addrs}", flush=True)
        await _serve_until_signal(dn)
    finally:
        telemetry.shutdown()


async def _run_worker(conf: WorkerConfig) -> None:
    from .worker.arbiter import OfferConfig
    from .worker.runtime import WorkerNode

    # Join the pod slice BEFORE any backend touch, so jax.devices() is
    # global and one replica's mesh spans this worker's hosts.
    from .parallel.multihost import MultihostConfig, initialize

    if conf.multihost.coordinator_address:
        initialize(
            MultihostConfig(
                coordinator_address=conf.multihost.coordinator_address,
                num_processes=conf.multihost.num_processes,
                process_id=conf.multihost.process_id,
            )
        )
    else:
        initialize()  # JAX_COORDINATOR_ADDRESS / _NUM_PROCESSES / _PROCESS_ID env
    # The in-process executors compile here; config only, no backend touch.
    from .hw import enable_compile_cache

    log.info("compile cache: %s", enable_compile_cache())
    if conf.resources.tpu > 0 and conf.executor.runtime == "in-process":
        # This process will run the step, so it takes the chip now, before
        # it sells anything: libtpu's start-up holds the GIL for seconds,
        # and inside a job that stalls this event loop past the 10 s lease —
        # the arbiter then cancels the job it was starting.
        import jax

        devices = jax.devices()
        log.info(
            "backend up: platform=%s kind=%r count=%d",
            devices[0].platform, devices[0].device_kind, len(devices),
        )
    node = _make_node(conf)
    worker = WorkerNode(
        None,
        resources=conf.resources.to_resources(),
        offer=OfferConfig(
            price=conf.offer.price, floor=conf.offer.floor, strategy=conf.offer.strategy
        ),
        train_runtime=conf.executor.runtime,
        train_cmd=conf.executor.cmd or None,
        train_args=list(conf.executor.args) or None,
        work_root=conf.work_root,
        node=node,
    )
    telemetry = _telemetry_for(conf, worker.node)
    try:
        await worker.start(list(conf.network.listen))
        print(f"worker {worker.peer_id} on {worker.node.listen_addrs}", flush=True)
        await _serve_until_signal(worker)
    finally:
        telemetry.shutdown()


async def _run_scheduler(conf: SchedulerConfig) -> None:
    from .scheduler.metrics_bridge import AimConnector, NoOpConnector
    from .scheduler.orchestrator import Orchestrator

    node = _make_node(conf)
    telemetry = _telemetry_for(conf, node)
    tracer = telemetry.tracer("hypha.scheduler")
    await node.start(list(conf.network.listen))
    print(f"scheduler {node.peer_id} on {node.listen_addrs}", flush=True)
    try:
        await node.wait_for_bootstrap()
        if conf.job.kind == "serve":
            # Inference deployment (BASELINE config 4): buy a worker via the
            # auction, dispatch the serving job, hold it elastically until
            # SIGINT/SIGTERM.
            from .scheduler.serving import ServingSupervisor

            # Live metrics plane for serve deployments: a collector on
            # this scheduler node ingests the serving workers' registry
            # reports + ServeLoad relays, journals metrics-<name>.jsonl,
            # and answers `telemetry.top <addr>` queries. Off by default.
            collector = None
            if conf.job.metrics_plane:
                from .telemetry.metrics_plane import MetricsCollector

                collector = MetricsCollector(
                    node,
                    # Prefix-matches the supervisor's dispatched job ids
                    # ("serve-<name>-<slot>-<uuid>"), so the serving
                    # workers' reports are accepted.
                    f"serve-{conf.job.serve_name}",
                    slo_rules=list(conf.job.slo_rules),
                    journal_dir=conf.job.metrics_dir or None,
                ).start()
            sup = ServingSupervisor(
                node,
                conf.job.to_model_spec(),
                conf.job.serve_name,
                resources=conf.job.worker_resources(),
                price=conf.job.worker_price(),
                max_new_tokens=conf.job.serve_max_new_tokens,
                max_batch=conf.job.serve_max_batch,
                num_workers=conf.job.serve_workers,
                queue_limit=conf.job.serve_queue_limit,
                pool_block_size=conf.job.serve_block_size,
                pool_blocks=conf.job.serve_blocks,
                pool_prefill_chunk=conf.job.serve_prefill_chunk,
                pool_prefix_cache=conf.job.serve_prefix_cache,
                pool_spec_ngram=conf.job.serve_spec_ngram,
                pool_spec_draft=conf.job.serve_spec_draft,
                pool_ragged=conf.job.serve_ragged,
                pool_kv_quant=conf.job.serve_kv_quant,
                pool_spec_layers=conf.job.serve_spec_layers,
                fleet_cache=conf.job.serve_fleet_cache,
                kv_migration=conf.job.serve_kv_migration,
                fleet_digest_k=conf.job.serve_digest_k,
                prefix_affinity=conf.job.serve_prefix_affinity,
                eos_token_id=(
                    None
                    if conf.job.serve_eos_token_id < 0
                    else conf.job.serve_eos_token_id
                ),
                report_metrics_s=(
                    conf.job.metrics_interval_s
                    if conf.job.metrics_plane
                    else None
                ),
                metrics=collector,
            )
            print(
                f"serving {conf.job.serve_name!r} "
                f"x{conf.job.serve_workers}; ctrl-c to stop",
                flush=True,
            )
            runner = asyncio.create_task(sup.run())
            with tracer.span("serve_job", {"serve_name": conf.job.serve_name}):
                # Watch the supervisor too: if it dies, surface the error
                # now instead of sitting signal-parked while serving nothing.
                signal_task = asyncio.create_task(_serve_until_signal())
                await asyncio.wait(
                    {signal_task, runner}, return_when=asyncio.FIRST_COMPLETED
                )
                signal_task.cancel()
            await sup.stop()
            await runner
            if collector is not None:
                await collector.close()
            return
        connector = (
            AimConnector(conf.status_bridge) if conf.status_bridge else NoOpConnector()
        )
        orch = Orchestrator(node, metrics_connector=connector)
        with tracer.span("run_job", {"dataset": conf.job.dataset}):
            result = await orch.run(
                conf.job.to_job(), max_attempts=conf.job.max_attempts
            )
        print(f"job {result.job_id} completed: {result.rounds} rounds", flush=True)
    finally:
        await node.stop()
        telemetry.shutdown()


_RUNNERS = {
    "gateway": _run_gateway,
    "scheduler": _run_scheduler,
    "worker": _run_worker,
    "data": _run_data,
}


def _cmd_run(role: str, args) -> int:
    conf = _load_config(role, args)
    try:
        asyncio.run(_RUNNERS[role](conf))
    except KeyboardInterrupt:
        pass
    return 0


# --------------------------------------------------------------------------
# argument parsing
# --------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="hypha-tpu", description="TPU-native decentralized training runtime"
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    roles = parser.add_subparsers(dest="role", required=True)
    for role in _SCHEMAS:
        rp = roles.add_parser(role, help=f"{role} node")
        cmds = rp.add_subparsers(dest="cmd", required=True)

        p_init = cmds.add_parser("init", help="write a documented default config")
        p_init.add_argument("-o", "--output", help=f"path (default {role}.toml)")
        p_init.add_argument("--name", help="node name")

        p_probe = cmds.add_parser("probe", help="health-check a running node")
        p_probe.add_argument("addr", help="host:port to probe")
        p_probe.add_argument("-c", "--config", help="config TOML (for TLS credentials)")
        p_probe.add_argument("--timeout", type=float, default=10.0)
        p_probe.add_argument("--set", action="append", metavar="KEY=VALUE")
        p_probe.add_argument("--name")

        p_run = cmds.add_parser("run", help="run the node")
        p_run.add_argument("-c", "--config", help="config TOML")
        p_run.add_argument(
            "--set", action="append", metavar="KEY=VALUE",
            help="override a config key (dotted paths ok)",
        )
        p_run.add_argument("--name", help="override node name")
    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    try:
        if args.cmd == "init":
            return _cmd_init(args.role, args)
        if args.cmd == "probe":
            return _cmd_probe(args.role, args)
        return _cmd_run(args.role, args)
    except cfg.ConfigError as e:
        print(f"config error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
