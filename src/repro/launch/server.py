"""HTTP serving launcher: N ``VisionEngine`` replicas behind the asyncio
front-end (``serve/transport.py``) and the SLO-aware router
(``serve/router.py``).

    # vgg16 at its published shape on one chip
    python -m repro.launch.server --model vgg16 --img 224 --width 1.0 \
        --classes 1000 --buckets 8,32

    # 2 in-process replicas of the reduced-width vgg16
    python -m repro.launch.server --workers 2

    # multi-host-shaped: each worker its own subprocess + engine
    python -m repro.launch.server --workers 2 --spawn

    curl -s localhost:8080/healthz
    curl -s -XPOST localhost:8080/v1/infer -d '{"images": [[[[...]]]]}'

On boot the launcher prints ``LISTENING <port>`` on stdout (the
machine-readable readiness line the load generator and ``spawn_worker``
wait for).  In-process workers share one ``ScheduleCache`` — schedule
planning stays pay-once across replicas exactly as it is across buckets
— and warm up sequentially before the socket opens, so the first wire
request hits steady-state compiled forwards.

Shutdown is the clean preemption drain: SIGTERM/SIGINT trips a
``PreemptionGuard``, new ``/v1/infer`` requests are refused 503 while
everything in flight completes, worker threads drain, and the obs
artifacts (``--trace``/``--metrics-json``) still emit.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import threading
import time
from typing import List, Optional, Sequence

# --backend choice -> core/engine.py execution policy
VISION_POLICIES = {"auto": "auto", "interpret": "pallas",
                   "reference": "reference"}

__all__ = ["ServerHandle", "start_server", "build_workers", "boot_report",
           "visible_tpu_chips", "main"]


def visible_tpu_chips() -> int:
    """TPU chips this host exposes to JAX, counted the way JAX itself
    detects them (PCI ids) — without initializing a backend, which would
    take the chips away from spawned workers.  0 when ``JAX_PLATFORMS``
    rules the TPU out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    return num_available_tpu_chips_and_device_id()[0]


def boot_report(workers) -> dict:
    """What the in-process workers run on: the JAX device platform,
    ``device_kind`` and count, the execution mode the engines resolved
    (``core/engine.py:resolve_execution``), and the fold kernel launches
    of one forward by dataflow and by output rows per tap dot
    (``fold_rows_per_dot``, 1 where rows are not packed), read off the
    first worker's compiled forwards (empty before a bucket is compiled,
    or where the convs do not run on the fold kernels)."""
    import collections

    import jax

    from repro.core.engine import resolve_execution
    devices = jax.devices()
    compiler = workers[0].worker.engine.compiler
    mode, interpret = resolve_execution(compiler.policy)
    net = (compiler.network_for(compiler.buckets[0]) if compiler.buckets
           else None)
    launches = collections.Counter(net.fold_dataflows if net else ())
    packed = collections.Counter(net.fold_rows_per_dot if net else ())
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices), "mode": mode,
            "interpret": interpret, "fold_launches": dict(launches),
            "fold_rows_per_dot": dict(sorted(packed.items()))}


def build_workers(model: str, n: int, *, img: int = 32,
                  width_mult: float = 0.0625, classes: int = 10,
                  policy: str = "auto",
                  buckets: Sequence[int] = (1, 2, 4, 8),
                  precision: str = "fp32", seed: int = 0,
                  tracer=None, warmup: bool = True):
    """N in-process replicas: one ``VisionEngine`` + ``EngineWorker``
    thread each, all compiling over ONE shared ``ScheduleCache`` (the
    second replica's planning is pure cache hits).  Warmup runs
    sequentially on the calling thread, before any worker serves.  Every
    replica runs on JAX's default device."""
    import jax

    from repro.core.engine import ScheduleCache
    from repro.models.zoo import get_conv_model
    from repro.serve.router import LocalWorker
    from repro.serve.transport import EngineWorker
    from repro.serve.vision import VisionEngine

    spec = get_conv_model(model)
    params = spec.init_params(jax.random.PRNGKey(seed),
                              width_mult=width_mult, img=img,
                              classes=classes)
    graph = spec.to_graph()
    cache = ScheduleCache()
    workers: List[LocalWorker] = []
    for i in range(n):
        engine = VisionEngine(params, graph, img=img, policy=policy,
                              buckets=tuple(buckets), cache=cache,
                              tracer=tracer if i == 0 else None,
                              precision=precision)
        workers.append(LocalWorker(
            f"w{i}", EngineWorker(f"w{i}", engine).start(warmup=warmup)))
    return workers


@dataclasses.dataclass
class ServerHandle:
    """A running server: the asyncio loop lives on a daemon thread, so
    tests and the load generator drive it from plain sync code."""
    host: str
    port: int
    server: object            # serve/transport.py:TransportServer
    router: object            # serve/router.py:Router
    workers: list             # LocalWorker / RemoteWorker
    loop: asyncio.AbstractEventLoop
    thread: threading.Thread
    guard: object = None
    tracer: object = None
    boot: Optional[dict] = None   # boot_report() of in-process workers

    def run(self, coro, timeout: float = 120.0):
        """Run a coroutine on the server loop from sync code."""
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def stop(self, drain: bool = True) -> None:
        """Stop accepting, drain every worker, tear the loop down."""
        self.run(self.server.shutdown())
        for w in self.workers:
            if hasattr(w, "worker"):            # local: drain the thread
                w.worker.stop(drain=drain)
            elif hasattr(w, "terminate"):       # remote: SIGTERM drain
                w.terminate()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(30.0)


def start_server(model: str = "vgg16", *, host: str = "127.0.0.1",
                 port: int = 0, n_workers: int = 1, spawn: bool = False,
                 img: int = 32, width_mult: float = 0.0625,
                 classes: int = 10, policy: str = "auto",
                 buckets: Sequence[int] = (1, 2, 4, 8),
                 precision: str = "fp32", seed: int = 0,
                 guard=None, tracer=None, registry=None,
                 access_log: Optional[str] = None,
                 probe_interval_s: float = 0.0,
                 workers=None) -> ServerHandle:
    """Boot the serving tier and return a live ``ServerHandle``.

    ``workers`` overrides construction entirely (tests inject fakes);
    ``spawn`` builds subprocess replicas via ``spawn_worker`` instead of
    in-process engine threads.  In-process workers print their
    ``boot_report`` (device and execution mode) once built.

    On a TPU host ``spawn`` allows one worker: each worker process opens
    every chip the host exposes, and a chip belongs to one process, so a
    second worker would fail or hang at start-up."""
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.router import Router, spawn_worker
    from repro.serve.transport import TransportServer

    boot = None
    if workers is None:
        if spawn:
            chips = visible_tpu_chips()
            if chips and n_workers > 1:
                raise ValueError(
                    f"--spawn with {n_workers} workers on a TPU host: each "
                    f"worker process opens every chip the host exposes "
                    f"({chips}), and a chip belongs to one process. Run "
                    f"in-process workers (drop --spawn) or --workers 1.")
            tail = ["--model", model, "--backend-policy", policy,
                    "--img", str(img), "--width", str(width_mult),
                    "--classes", str(classes), "--precision", precision,
                    "--seed", str(seed),
                    "--buckets", ",".join(str(b) for b in buckets)]
            workers = [spawn_worker(f"w{i}", tail)
                       for i in range(n_workers)]
        else:
            workers = build_workers(
                model, n_workers, img=img, width_mult=width_mult,
                classes=classes, policy=policy, buckets=buckets,
                precision=precision, seed=seed, tracer=tracer)
            boot = boot_report(workers)
            print("# boot " + " ".join(f"{k}={v!r}" if isinstance(v, str)
                                       else f"{k}={v}"
                                       for k, v in boot.items()),
                  flush=True)
    router = Router(workers, buckets)
    if registry is None:
        registry = MetricsRegistry(max_series=2048)
    server = TransportServer(router, host=host, port=port,
                             registry=registry, tracer=tracer,
                             guard=guard, access_log=access_log)
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever,
                              name="transport-loop", daemon=True)
    thread.start()
    bound = asyncio.run_coroutine_threadsafe(
        server.start(probe_interval_s), loop).result(60.0)
    return ServerHandle(host=host, port=bound, server=server,
                        router=router, workers=workers, loop=loop,
                        thread=thread, guard=guard, tracer=tracer, boot=boot)


def _drain_and_exit(handle: ServerHandle, args) -> None:
    """The SIGTERM discipline: stop admitting (the guard already flips
    ``/v1/infer`` to 503), let in-flight work finish, then tear down."""
    deadline = time.monotonic() + args.drain_timeout_s
    while time.monotonic() < deadline:
        if all(w.inflight == 0 for w in handle.workers):
            break
        time.sleep(0.05)
    handle.stop(drain=True)
    if args.trace and handle.tracer is not None:
        handle.tracer.save(args.trace)
        print(f"# wrote Chrome trace ({len(handle.tracer.events)} "
              f"events) to {args.trace}")
    if args.metrics_json:
        snap = handle.server.registry.snapshot()
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote metrics snapshot to {args.metrics_json}")


def main(argv: Optional[Sequence[str]] = None) -> None:
    from repro.models.zoo import conv_model_names
    ap = argparse.ArgumentParser(
        description="HTTP serving front-end over VisionEngine workers")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 = let the OS pick (printed as LISTENING)")
    ap.add_argument("--workers", type=int, default=1,
                    help="number of VisionEngine replicas")
    ap.add_argument("--spawn", action="store_true",
                    help="one subprocess per worker (multi-host-shaped) "
                         "instead of in-process engine threads")
    ap.add_argument("--model", default="vgg16",
                    choices=conv_model_names())
    ap.add_argument("--backend", choices=sorted(VISION_POLICIES),
                    default="auto",
                    help="vision execution: auto / interpret / reference")
    ap.add_argument("--backend-policy", default="",
                    help=argparse.SUPPRESS)   # spawn_worker passes the
    #                                           raw core-engine policy
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--width", type=float, default=0.0625)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--buckets", default="1,2,4,8")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "int8"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probe-interval-s", type=float, default=2.0,
                    help="healthz-probe cadence for quarantined workers")
    ap.add_argument("--drain-timeout-s", type=float, default=60.0)
    ap.add_argument("--access-log", default="",
                    help="append one line per wire request here "
                         "(e.g. server_access.log)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write a Chrome trace with the transport track")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="write the registry snapshot at shutdown")
    args = ap.parse_args(argv)

    from repro.ft.fault_tolerance import PreemptionGuard
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    policy = args.backend_policy or VISION_POLICIES[args.backend]
    buckets = tuple(int(b) for b in args.buckets.split(","))
    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer(time.monotonic)
    with PreemptionGuard() as guard:
        handle = start_server(
            args.model, host=args.host, port=args.port,
            n_workers=args.workers, spawn=args.spawn, img=args.img,
            width_mult=args.width, classes=args.classes, policy=policy,
            buckets=buckets, precision=args.precision, seed=args.seed,
            guard=guard, tracer=tracer,
            access_log=args.access_log or None,
            probe_interval_s=args.probe_interval_s)
        # the machine-readable readiness line (load generator + spawn)
        print(f"LISTENING {handle.port}", flush=True)
        mode = "spawned subprocesses" if args.spawn else "in-process"
        print(f"# serving {args.model} on {args.host}:{handle.port} "
              f"with {args.workers} {mode} worker(s), policy={policy}, "
              f"buckets={list(buckets)}", flush=True)
        while not guard.requested:
            time.sleep(0.1)
        print("# preemption requested: draining", flush=True)
        _drain_and_exit(handle, args)
    print("# drained cleanly", flush=True)


if __name__ == "__main__":
    main()
