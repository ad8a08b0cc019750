"""CLI entrypoints.

``python -m seldon_core_tpu.transport.cli microservice <Interface> [REST|GRPC]``
mirrors the reference wrapper CLI (`python/seldon_core/microservice.py:177-322`):
import the user class, typed params from PREDICTIVE_UNIT_PARAMETERS, optional
state restore (--persistence), annotations file, log level, tracing, then serve.

``... engine`` boots a whole predictor graph from ENGINE_PREDICTOR (base64
JSON spec), the role of the reference's JVM engine bootstrap
(`engine/.../EnginePredictor.java:58-108`) — but serving the graph in-process.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import logging
import os
import sys
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

ANNOTATIONS_FILE = "/etc/podinfo/annotations"


def load_annotations(path: str = ANNOTATIONS_FILE) -> Dict[str, str]:
    """k8s downward-API annotations file: `key="value"` lines
    (`python/seldon_core/microservice.py:90-113`)."""
    annotations: Dict[str, str] = {}
    if not os.path.exists(path):
        return annotations
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or "=" not in line:
                continue
            key, _, value = line.partition("=")
            annotations[key.strip()] = value.strip().strip('"')
    return annotations


def setup_logging() -> None:
    level = os.environ.get("SELDON_LOG_LEVEL", "INFO").upper()
    logging.basicConfig(
        level=getattr(logging, level, logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )


def _start_serving() -> None:
    """First thing in every serving command: logging, then the compile
    cache and the start ledger's build listeners (tracing/start.py) — before
    anything can compile."""
    from seldon_core_tpu.tracing import start
    from seldon_core_tpu.utils import configure_compile_cache

    setup_logging()
    configure_compile_cache()
    start.get_ledger().listen()


@contextlib.contextmanager
def _device_footprint_on_exit():
    """Wraps the serve call of the commands chip_smoke.py drives
    (microservice, edge): on the way out — while what was served is still
    alive — each device's memory goes into the log, and the start ledger:
    the stages of the start and, by program, what every build of the
    process's life cost (docs/observability.md "Start-up")."""
    from seldon_core_tpu.parallel.topology import log_device_memory
    from seldon_core_tpu.tracing import start

    try:
        yield
    finally:
        log_device_memory()
        stages, builds = start.get_ledger().snapshot()
        logger.info("start ledger: %s", json.dumps(stages))
        logger.info("program builds: %s", json.dumps(builds))


def import_interface(name: str):
    """Import `Name` from module `Name`, or `pkg.mod.Class` dotted form."""
    sys.path.insert(0, os.getcwd())
    if "." in name:
        module_name, _, class_name = name.rpartition(".")
    else:
        module_name = class_name = name
    module = importlib.import_module(module_name)
    return getattr(module, class_name)


def build_component(interface_name: str, persistence: bool = False):
    from seldon_core_tpu.contracts.parameters import parse_parameters
    from seldon_core_tpu.runtime.persistence import (
        PersistenceThread,
        ReplicaSync,
        restore_component,
    )

    from seldon_core_tpu.tracing import start

    klass = import_interface(interface_name)
    parameters = parse_parameters()
    # the start's stages (tracing/start.py): `import` ends here, the
    # component's own load() moves on to `load.weights` and `load.rest`
    ledger = start.get_ledger()
    ledger.advance("construct")
    component = None
    restored_shared = False
    if persistence:
        component = restore_component(klass)
        restored_shared = component is not None
    if component is None:
        component = klass(**parameters)
    if hasattr(component, "load"):
        component.load()
    threads = []
    if persistence:
        thread = PersistenceThread(component)
        thread.start()
        threads.append(thread)
        # stateful routers under replicated serving additionally share their
        # feedback counters across replicas (G-counter ReplicaSync)
        if hasattr(component, "stats_snapshot"):
            sync = ReplicaSync(component, store=thread.store)
            if not sync.restore_own() and restored_shared and hasattr(component, "reset_local_stats"):
                # The shared-key snapshot predates replica-keyed sync (legacy
                # single-key persistence). Exactly ONE replica may adopt those
                # counts as its own — an exclusive claim decides which; the
                # rest zero their counters and learn the history as peers.
                if thread.store.save_if_absent(f"{sync.key}:legacy-claim", sync.rid):
                    logger.info("adopted legacy persisted counters as replica %s", sync.rid)
                else:
                    component.reset_local_stats()
            sync.sync()  # publish + pull peers NOW, not after one period
            sync.start()
            threads.append(sync)
            import atexit

            atexit.register(sync.stop)  # final publish on shutdown
    ledger.advance("listen")
    return component, threads


def run_microservice(args: argparse.Namespace) -> None:
    _start_serving()
    _bootstrap_multihost()
    component, _ = build_component(args.interface_name, persistence=args.persistence)
    port = args.port or int(os.environ.get("PREDICTIVE_UNIT_SERVICE_PORT", "5000"))
    unit_id = os.environ.get("PREDICTIVE_UNIT_ID", "")
    api = (args.api or os.environ.get("API_TYPE", "REST")).upper()
    logger.info("serving %s as %s on port %d", args.interface_name, api, port)
    annotations = load_annotations()
    if api == "REST":
        from seldon_core_tpu.transport.rest import make_component_app, serve

        with _device_footprint_on_exit():
            serve(make_component_app(component, unit_id=unit_id, annotations=annotations),
                  host=args.host, port=port)
    elif api == "GRPC":
        from seldon_core_tpu.transport.grpc_server import serve_component

        with _device_footprint_on_exit():
            serve_component(component, host=args.host, port=port, unit_id=unit_id,
                            annotations=annotations)
    else:
        raise SystemExit(f"Unknown API type {api} (use REST or GRPC)")


def _bootstrap_multihost() -> None:
    """Join the multi-host device world when the environment describes one
    (JAX_COORDINATOR_ADDRESS etc.) — must run before any component load in
    every serving entrypoint; single-host is a no-op."""
    from seldon_core_tpu.parallel.multihost import initialize as multihost_init

    multihost_init()


def run_engine(args: argparse.Namespace) -> None:
    _start_serving()
    _bootstrap_multihost()
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.transport.rest import make_engine_app, serve

    # Spec from file, ENGINE_PREDICTOR env, or the default SIMPLE_MODEL the
    # reference engine uses when unconfigured (`EnginePredictor.java:122-141`).
    from seldon_core_tpu.tracing import start

    spec = _load_spec(args.spec)
    annotations = load_annotations()
    start.get_ledger().advance("construct")
    engine = GraphEngine(spec, annotations=annotations)
    start.get_ledger().advance("listen")
    metrics = MetricsRegistry(predictor=spec.name)
    port = args.port or int(os.environ.get("ENGINE_SERVER_PORT", "8000"))
    logger.info("engine serving predictor %r on port %d", spec.name, port)
    api = (args.api or "REST").upper()
    if api == "GRPC":
        from seldon_core_tpu.transport.grpc_server import serve_engine

        serve_engine(engine, host=args.host, port=port, metrics=metrics,
                     annotations=annotations)
    elif api == "IPC":
        # native shared-memory data plane: N frontend processes attach as
        # IPCClient workers, this process owns the device (transport/ipc.py)
        import asyncio

        from seldon_core_tpu.transport.ipc import IPCEngineServer

        if not args.ipc_base:
            raise SystemExit("--api IPC needs --ipc-base <path>")
        server = IPCEngineServer(engine, args.ipc_base, n_workers=args.ipc_workers)
        logger.info("engine serving over IPC at %s (%d workers)", args.ipc_base, args.ipc_workers)
        asyncio.run(server.serve_forever())
    else:
        serve(make_engine_app(engine, metrics=metrics, annotations=annotations),
              host=args.host, port=port)


def _load_spec(path: Optional[str]):
    from seldon_core_tpu.contracts.graph import PredictorSpec, load_predictor_spec_from_env

    if path:
        with open(path) as f:
            return PredictorSpec.from_dict(json.load(f))
    spec = load_predictor_spec_from_env()
    if spec is None:
        spec = PredictorSpec.from_dict(
            {"name": "default", "graph": {"name": "simple", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}
        )
    return spec


def run_edge(args: argparse.Namespace) -> None:
    """Serve a predictor graph behind the native edge (native/edge.cc).

    All-builtin graphs compile to an edge program and execute entirely in the
    compiled edge process; anything else keeps the edge as the HTTP frontend
    with this process running the Python/XLA engine behind the shared-memory
    ring (the reference's engine-pod split, collapsed onto one host)."""
    import subprocess
    import tempfile

    _start_serving()
    from seldon_core_tpu.runtime.edgeprogram import (
        EDGE_BINARY,
        build_edge_binaries,
        compile_edge_program,
        fallback_program,
        write_program,
    )

    if not build_edge_binaries():
        raise SystemExit("native toolchain unavailable; use `engine` instead")
    spec = _load_spec(args.spec)
    deployment = os.environ.get("DEPLOYMENT_NAME", "")
    program = compile_edge_program(spec, deployment=deployment)
    port = args.port or int(os.environ.get("ENGINE_SERVER_PORT", "8000"))
    tmp = tempfile.mkdtemp(prefix="seldon-edge-")
    openapi_path = os.path.join(tmp, "openapi.json")
    from seldon_core_tpu.transport.openapi import engine_spec

    with open(openapi_path, "w") as f:
        json.dump(engine_spec(), f)

    grpc_port = args.grpc_port or int(os.environ.get("ENGINE_SERVER_GRPC_PORT", "0"))
    if program is not None:
        # pure-builtin graph: the edge process needs no Python at all
        # (native gRPC included when a gRPC port is configured)
        prog_path = write_program(program, os.path.join(tmp, "program.json"))
        logger.info("graph compiled natively; edge serving on port %d", port)
        argv = [
            EDGE_BINARY, "--program", prog_path, "--port", str(port),
            "--openapi", openapi_path, "--workers", str(args.workers),
            "--max-inflight", str(args.max_inflight),
        ]
        if grpc_port:
            argv += ["--grpc-port", str(grpc_port)]
        os.execv(EDGE_BINARY, argv)

    # The graph needs Python — build the engine, then try the DEVICE_MODEL
    # compile: graphs of builtins + real model leaves still execute natively
    # in the edge, which ships only packed tensors (ring kind 2) to this
    # process's ModelExecutor. Anything else (remote nodes, seeded routers,
    # custom transformers) keeps full-graph ring fallback (kind 0).
    import asyncio

    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.runtime.remote import RemoteComponent
    from seldon_core_tpu.transport.ipc import (
        IPCEngineServer,
        ModelExecutor,
        cleanup_rings,
        default_ring_dir,
        ring_geometry,
    )

    engine = GraphEngine(spec, annotations=load_annotations())
    # the compiler owns device eligibility (unit type/children/method
    # checks live in compile_edge_program); pass every in-process component
    eligible = {
        st.unit.name: st.component
        for st in engine.state.walk()
        if st.component is not None
        and not isinstance(st.component, RemoteComponent)
    }
    program = compile_edge_program(spec, deployment=deployment,
                                   device_components=eligible)
    executor = None
    if program is not None and program.get("deviceModels"):
        executor = ModelExecutor(
            [eligible[name] for name in program["deviceModels"]])
        logger.info("warming device-model compile caches (all batch buckets)")
        executor.warm()
        prog_path = write_program(program, os.path.join(tmp, "program.json"))
        logger.info(
            "graph compiled natively with %d device model(s): %s",
            len(program["deviceModels"]), ", ".join(program["deviceModels"]),
        )
    else:
        prog_path = write_program(
            fallback_program(spec, deployment=deployment),
            os.path.join(tmp, "program.json"),
        )
    # rings live on tmpfs (default_ring_dir docstring: disk-backed MAP_SHARED
    # pays a journal fault per cleaned page — ~20x ping-pong latency)
    ring_dir = None if args.ipc_base else default_ring_dir()
    base = args.ipc_base or os.path.join(ring_dir, "ring")
    # One edge process per worker, each with its own response ring (an edge's
    # internal fork cannot be used here: forked loops would race on one ring).
    n_workers = max(1, args.workers)
    # drain up to 256 frames per FFI crossing: under a 512-stream gRPC load
    # one cycle then feeds the micro-batcher a full compile bucket instead
    # of four 64-frame nibbles (pop_many is one C call either way)
    capacity, slot_size = ring_geometry(executor.models if executor else ())
    server = IPCEngineServer(engine, base, n_workers=n_workers,
                             capacity=capacity, slot_size=slot_size,
                             model_executor=executor, batch=256)
    edge_argv_tail = []
    if grpc_port:
        # the edge serves gRPC on every plane: native for builtin/device
        # tensor traffic, full-proto ring frames (kind 3/4) into this
        # engine process for everything else — one port, every graph
        edge_argv_tail = ["--grpc-port", str(grpc_port)]
    edges = [
        subprocess.Popen(
            [
                EDGE_BINARY, "--program", prog_path, "--port", str(port),
                "--ring", base, "--ring-worker", str(w), "--openapi", openapi_path,
                "--max-inflight", str(args.max_inflight),
            ] + edge_argv_tail
        )
        for w in range(n_workers)
    ]
    logger.info(
        "graph needs the Python engine; %d edge frontend(s) on port %d, ring %s",
        n_workers, port, base,
    )

    async def run():
        serve_task = asyncio.ensure_future(server.serve_forever())
        try:
            while all(e.poll() is None for e in edges):
                await asyncio.sleep(0.2)
        finally:
            server.stop()
            await serve_task

    try:
        with _device_footprint_on_exit():
            asyncio.run(run())
    except KeyboardInterrupt:
        logger.info("interrupted; stopping the edge frontends")
    finally:
        for e in edges:
            if e.poll() is None:
                e.terminate()
        cleanup_rings(base, n_workers)
        if ring_dir is not None:
            import shutil

            shutil.rmtree(ring_dir, ignore_errors=True)


def run_loadtest_native(args: argparse.Namespace) -> None:
    """Drive the native closed-loop loadgen and (optionally) write the
    benchmark report the driver/judge reads."""
    import subprocess

    from seldon_core_tpu.runtime.edgeprogram import LOADGEN_BINARY, build_edge_binaries

    if not build_edge_binaries():
        raise SystemExit("native toolchain unavailable")
    cmd = [
        LOADGEN_BINARY, "--host", args.host, "--port", str(args.port),
        "--connections", str(args.connections), "--duration", str(args.duration),
        "--warmup", str(args.warmup), "--label", args.label,
    ]
    if args.body:
        cmd += ["--body", args.body]
    if args.path:
        cmd += ["--path", args.path]
    out = subprocess.run(cmd, capture_output=True, text=True)
    sys.stdout.write(out.stdout)
    sys.stderr.write(out.stderr)
    if out.returncode not in (0, 3):
        raise SystemExit(out.returncode)
    if args.report:
        report = json.loads(out.stdout.strip().splitlines()[-1])
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)


def run_render(args: argparse.Namespace) -> None:
    import yaml

    from seldon_core_tpu.contracts.graph import SeldonDeploymentSpec
    from seldon_core_tpu.controlplane import render_manifests
    from seldon_core_tpu.controlplane.render import DEFAULT_ENGINE_IMAGE

    with open(args.file) as f:
        raw = yaml.safe_load(f)
    sdep = SeldonDeploymentSpec.from_dict(raw)
    manifests = render_manifests(
        sdep,
        namespace=args.namespace,
        engine_image=args.engine_image or DEFAULT_ENGINE_IMAGE,
        tpu_chips=args.tpu_chips,
        tpu_topology=args.tpu_topology,
    )
    if args.format == "json":
        print(json.dumps(manifests, indent=2))
    else:
        print(yaml.safe_dump_all(manifests, sort_keys=False))


def run_request_logger(args: argparse.Namespace) -> None:
    setup_logging()
    from seldon_core_tpu.observability.request_logger import make_logger_app
    from seldon_core_tpu.transport.rest import serve

    serve(make_logger_app(), host=args.host, port=args.port)


def run_loadtest(args: argparse.Namespace) -> None:
    from seldon_core_tpu.benchmarks import loadgen

    loadgen.main(args)


def run_convert(args: argparse.Namespace) -> None:
    setup_logging()
    from seldon_core_tpu.models.convert import convert_checkpoint

    out = convert_checkpoint(args.hf_path, args.out_dir, dtype=args.dtype)
    print(out)


def run_render_chart(args: argparse.Namespace) -> None:
    """Render a deploy/charts chart without the helm binary (the in-repo
    subset renderer; `helm template` produces the same output)."""
    from seldon_core_tpu.controlplane.charts import render_chart

    values = {}
    if args.values:
        import yaml

        with open(args.values) as f:
            values = yaml.safe_load(f) or {}
    for name, text in render_chart(args.chart, values, namespace=args.namespace):
        print(f"---\n# Source: {os.path.basename(args.chart)}/templates/{name}")
        print(text)


def run_analytics(args: argparse.Namespace) -> None:
    from seldon_core_tpu.observability.dashboards import write_artifacts

    for path in write_artifacts(args.out):
        print(path)


def run_loadtest_worker(args: argparse.Namespace) -> None:
    from seldon_core_tpu.benchmarks.fleet import worker_serve

    worker_serve(args.listen, host=args.host, once=args.once, token=args.token)


def run_loadtest_fleet(args: argparse.Namespace) -> None:
    from seldon_core_tpu.benchmarks.fleet import run_distributed, run_local_fleet

    workers = [w.strip() for w in args.workers.split(",") if w.strip()]
    n_workers = len(workers) or max(args.local_workers, 1)

    per_worker = None
    if args.contract:
        if args.grpc:
            raise SystemExit("--contract payloads are REST-only (the native gRPC "
                             "generator uses its fixed proto request)")
        # contract-conforming payloads, a distinct draw per worker — the
        # fleet analogue of the reference's locust drivers sampling the
        # contract's feature ranges (predict_rest_locust.py:17-53); the
        # native generator replays its body, so variety is per worker
        from seldon_core_tpu.client.contract import generate_batch, load_contract

        contract = load_contract(args.contract)
        per_worker = [
            {"body": json.dumps({"data": {"ndarray": generate_batch(
                contract, max(args.batch, 1), seed=i).tolist()}})}
            for i in range(n_workers)
        ]
    job = {
        "host": args.host,
        "port": args.port,
        "connections": args.connections,
        "duration": args.duration,
        "grpc": args.grpc,
        "body": args.body,
        "path": args.path,
    }
    if workers:
        report = run_distributed(workers, job, per_worker=per_worker, token=args.token)
    else:
        report = run_local_fleet(job, n_workers, per_worker=per_worker)
    out = json.dumps(report, indent=2)
    print(out)
    if args.report:
        with open(args.report, "w") as f:
            f.write(out)


def run_operator(args: argparse.Namespace) -> None:
    setup_logging()
    from seldon_core_tpu.controlplane.operator import (
        FileCluster,
        KubectlCluster,
        Operator,
        Reconciler,
    )

    if args.kubectl:
        cluster: Any = KubectlCluster()
    else:
        cluster = FileCluster(args.cluster)
    reconciler = Reconciler(
        cluster,
        namespace=args.namespace,
        engine_image=args.engine_image,
        tpu_chips=args.tpu_chips,
        tpu_topology=args.tpu_topology,
    )
    op = Operator(args.crs, reconciler, interval=args.interval, status_dir=args.status_dir)
    if args.once:
        op.run_once()
    else:
        op.run_forever()


def main(argv: Optional[list] = None) -> None:
    parser = argparse.ArgumentParser(prog="seldon-core-tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    ms = sub.add_parser("microservice", help="serve one component")
    ms.add_argument("interface_name")
    ms.add_argument("api", nargs="?", default=None, help="REST or GRPC")
    ms.add_argument("--port", type=int, default=None)
    ms.add_argument("--host", default="0.0.0.0")
    ms.add_argument("--persistence", action="store_true")
    ms.set_defaults(func=run_microservice)

    eng = sub.add_parser("engine", help="serve a predictor graph in-process")
    eng.add_argument("--spec", default=None, help="path to PredictorSpec JSON")
    eng.add_argument("--api", default="REST")
    eng.add_argument("--port", type=int, default=None)
    eng.add_argument("--host", default="0.0.0.0")
    eng.add_argument("--ipc-base", default=None, help="ring path base for --api IPC")
    eng.add_argument("--ipc-workers", type=int, default=4)
    eng.set_defaults(func=run_engine)

    from seldon_core_tpu.client.testers import add_tester_args, tester_main

    tester = sub.add_parser(
        "tester", help="contract-fuzz a microservice (seldon-core-tester equivalent)"
    )
    add_tester_args(tester, endpoint_kind="microservice")
    tester.set_defaults(func=tester_main)

    api_tester = sub.add_parser(
        "api-tester", help="contract-fuzz an engine/gateway (seldon-core-api-tester equivalent)"
    )
    add_tester_args(api_tester, endpoint_kind="engine")
    api_tester.set_defaults(func=tester_main)

    render = sub.add_parser("render", help="SeldonDeployment CR -> k8s manifests (operator logic)")
    render.add_argument("file", help="CR or spec JSON/YAML file")
    render.add_argument("--namespace", default="default")
    render.add_argument("--engine-image", default=None)
    render.add_argument("--tpu-chips", type=int, default=1)
    render.add_argument("--tpu-topology", default=None)
    render.add_argument("--format", default="yaml", choices=["yaml", "json"])
    render.set_defaults(func=run_render)

    op = sub.add_parser(
        "operator", help="watch SeldonDeployment CRs and reconcile the cluster"
    )
    op.add_argument("--crs", required=True, help="directory of CR JSON/YAML files")
    op.add_argument("--cluster", default="./cluster", help="FileCluster root dir")
    op.add_argument("--kubectl", action="store_true", help="apply via kubectl instead")
    op.add_argument("--namespace", default="default")
    op.add_argument("--engine-image", default=None)
    op.add_argument("--tpu-chips", type=int, default=1)
    op.add_argument("--tpu-topology", default=None)
    op.add_argument("--interval", type=float, default=2.0)
    op.add_argument("--status-dir", default=None,
                    help="status output dir (default <crs>/.status; set when --crs is read-only)")
    op.add_argument("--once", action="store_true", help="single reconcile pass")
    op.set_defaults(func=run_operator)

    cv = sub.add_parser(
        "convert-llama", help="HF Llama checkpoint -> servable native checkpoint"
    )
    cv.add_argument("hf_path", help="local HF snapshot directory (or hub id if cached)")
    cv.add_argument("out_dir")
    cv.add_argument("--dtype", default="bfloat16")
    cv.set_defaults(func=run_convert)

    an = sub.add_parser(
        "analytics", help="write Prometheus rules + Grafana dashboard artifacts"
    )
    an.add_argument("--out", default="deploy/analytics")
    an.set_defaults(func=run_analytics)

    rl = sub.add_parser("request-logger", help="CloudEvents message-pair logger service")
    rl.add_argument("--port", type=int, default=2222)
    rl.add_argument("--host", default="0.0.0.0")
    rl.set_defaults(func=run_request_logger)

    edge = sub.add_parser("edge", help="serve a graph behind the native C++ edge")
    edge.add_argument("--spec", default=None, help="path to PredictorSpec JSON")
    edge.add_argument("--port", type=int, default=None)
    edge.add_argument("--grpc-port", type=int, default=None,
                      help="gRPC port (default env ENGINE_SERVER_GRPC_PORT; "
                           "native for builtin graphs, Python engine otherwise)")
    edge.add_argument("--workers", type=int, default=1, help="SO_REUSEPORT event loops")
    edge.add_argument("--max-inflight", type=int, default=4096,
                      help="overload-shed threshold: parked in-flight predictions "
                           "beyond this get HTTP 429 / gRPC RESOURCE_EXHAUSTED")
    edge.add_argument("--ipc-base", default=None, help="ring path base for fallback mode")
    edge.set_defaults(func=run_edge)

    ltn = sub.add_parser("loadtest-native", help="native closed-loop load generator")
    ltn.add_argument("host")
    ltn.add_argument("port", type=int)
    ltn.add_argument("--connections", type=int, default=32)
    ltn.add_argument("--duration", type=float, default=10.0)
    ltn.add_argument("--warmup", type=float, default=1.0)
    ltn.add_argument("--body", default=None)
    ltn.add_argument("--path", default=None)
    ltn.add_argument("--label", default="rest")
    ltn.add_argument("--report", default=None, help="write JSON report to this file")
    ltn.set_defaults(func=run_loadtest_native)

    rc = sub.add_parser("render-chart", help="render a deploy/charts helm chart (no helm needed)")
    rc.add_argument("chart", help="chart directory, e.g. deploy/charts/seldon-mab")
    rc.add_argument("--values", default=None, help="values override YAML file")
    rc.add_argument("--namespace", default="seldon-system")
    rc.set_defaults(func=run_render_chart)

    ltw = sub.add_parser("loadtest-worker", help="fleet slave: run loadgen jobs sent over TCP")
    ltw.add_argument("--listen", type=int, required=True)
    ltw.add_argument("--host", default="127.0.0.1",
                     help="bind address; non-loopback requires --token")
    ltw.add_argument("--token", default=None,
                     help="shared secret jobs must carry (required off-loopback)")
    ltw.add_argument("--once", action="store_true")
    ltw.set_defaults(func=run_loadtest_worker)

    ltf = sub.add_parser(
        "loadtest-fleet",
        help="fleet master: local multi-process or remote-worker load generation",
    )
    ltf.add_argument("host")
    ltf.add_argument("port", type=int)
    ltf.add_argument("--local-workers", type=int, default=0,
                     help="spawn N generator processes on this host")
    ltf.add_argument("--workers", default="",
                     help="comma-separated host:port loadtest-worker addresses")
    ltf.add_argument("--connections", type=int, default=32, help="per worker")
    ltf.add_argument("--duration", type=float, default=10.0)
    ltf.add_argument("--grpc", action="store_true")
    ltf.add_argument("--body", default=None)
    ltf.add_argument("--contract", default=None,
                     help="contract.json: each worker replays a distinct payload "
                          "drawn from the feature ranges (REST only)")
    ltf.add_argument("--batch", type=int, default=1, help="rows per contract payload")
    ltf.add_argument("--path", default=None)
    ltf.add_argument("--token", default=None,
                     help="shared secret for remote workers bound off-loopback")
    ltf.add_argument("--report", default=None, help="write merged JSON report here")
    ltf.set_defaults(func=run_loadtest_fleet)

    lt = sub.add_parser("loadtest", help="async load generator (locust equivalent)")
    lt.add_argument("host")
    lt.add_argument("port", type=int)
    lt.add_argument("--clients", type=int, default=16)
    lt.add_argument("--duration", type=float, default=10.0)
    lt.add_argument("--batch", type=int, default=1)
    lt.add_argument("--contract", default=None)
    lt.add_argument("--grpc", action="store_true")
    lt.set_defaults(func=run_loadtest)

    args = parser.parse_args(argv)
    args.func(args)


if __name__ == "__main__":
    main()
