"""The process that holds the chip.

    python3 chipbench/server_child.py '<json arguments>'

Boots the served path in process — ``launch/server.py:start_server``
over one ``VisionEngine`` worker with the cell's buckets and the
configuration's shapes, on weights that the benchmark makes on the
device from the seed in one jitted call — and prints
``@@chipbench {"ready": ...}`` on stdout.  It then waits for one
``{"finish": ...}`` line on stdin, answers it and exits.  Every other
stdout line (the program's own) is relayed by the parent to stderr.

``finish`` reads the device's peak memory, stops the server and frees
the engine, then runs the plain reference (``models/<family>.py``, float
32 at HIGHEST) over the bodies the parent names, and, in a traced run,
reduces the profiler trace and the ``Tracer`` spans.

The process exits nonzero, before any ``ready`` line, when JAX finds no
TPU (or fewer chips than the cell asks for) or the program is not beside
the benchmark.
"""
from __future__ import annotations

import base64
import functools
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROTO = "@@chipbench "
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _paths() -> None:
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


class ServerSide:
    """Boot, serve, and answer the reference for one run (usable in
    process too, which is how the tests drive a run without a chip)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, *,
                 trace: bool = False, chips: int = 1,
                 require_tpu: bool = True):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.trace, self.chips = bool(trace), int(chips)
        self.require_tpu = require_tpu
        self.compile_times = []
        self.handle = self.engine = self.params = self.tracer = None
        self.trace_dir = None
        self._listener = None

    # -- boot ----------------------------------------------------------------
    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compile_times.append(time.monotonic())

    def _device(self, jax) -> dict:
        devices = jax.devices()
        platform = devices[0].platform
        if self.require_tpu and platform != "tpu":
            raise SystemExit(f"chipbench: JAX found no TPU (platform "
                             f"{platform!r}, {len(devices)} device(s)); "
                             "the benchmark runs on the chip only")
        if len(devices) < self.chips:
            raise SystemExit(f"chipbench: the cell needs {self.chips} "
                             f"chips, JAX sees {len(devices)}")
        return {"platform": platform, "kind": devices[0].device_kind,
                "count": len(devices)}

    def boot(self) -> dict:
        import jax

        from chipbench import spec
        from chipbench.models.common import seed_key
        from repro.launch.server import boot_report, start_server
        from repro.models.zoo import get_conv_model
        from repro.obs.trace import Tracer
        from repro.serve.router import LocalWorker
        from repro.serve.transport import EngineWorker
        from repro.serve.vision import VisionEngine

        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        device = self._device(jax)
        t0 = time.monotonic()
        model = spec.model_module(self.cfg["family"])
        self.params = jax.jit(functools.partial(
            model.init_params, cfg=self.cfg))(seed_key(self.seed))
        jax.block_until_ready(self.params)
        t_params = time.monotonic() - t0
        self.tracer = Tracer(time.monotonic) if self.trace else None
        program = get_conv_model(self.cfg["program_model"])
        buckets = tuple(int(b) for b in self.mix["buckets"])
        self.engine = VisionEngine(
            self.params, program.to_graph(), img=int(self.cfg["img"]),
            chan=int(self.cfg["channels"]), policy="auto", buckets=buckets,
            tracer=self.tracer)
        worker = LocalWorker("w0", EngineWorker("w0", self.engine)
                             .start(warmup=True))
        boot = boot_report([worker])
        if self.require_tpu and (boot["mode"] != "pallas"
                                 or boot["interpret"]):
            raise SystemExit(f"chipbench: the server does not run the "
                             f"compiled fold kernels: {boot}")
        self.handle = start_server(self.cfg["program_model"],
                                   workers=[worker], buckets=buckets,
                                   tracer=self.tracer)
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._sync(jax)
        return {"port": self.handle.port, "host": self.handle.host,
                "device": device, "boot": boot,
                "params_s": t_params,
                "warmup_s": {str(k): v for k, v in
                             sorted(self.engine.warmup_s.items())},
                "boot_s": time.monotonic() - t0}

    @staticmethod
    def _sync(jax) -> None:
        with jax.profiler.TraceAnnotation("chipbench_sync",
                                          mono_ns=time.monotonic_ns()):
            pass

    # -- finish --------------------------------------------------------------
    def finish(self, window, keys) -> dict:
        import jax
        import numpy as np

        from chipbench import devtrace, spec, traffic
        out = {"compiles": list(self.compile_times)}
        stats = jax.devices()[0].memory_stats() or {}
        out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        rb = self.engine.metrics_dict()["robustness"]
        out["robustness"] = {k: rb[k] for k in (
            "degraded_batches", "failed", "lost_requests", "shed",
            "expired")}
        xplane = None
        events = []
        if self.trace:
            self._sync(jax)
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(self.trace_dir, "**",
                                           "*.xplane.pb"), recursive=True)
            xplane = found[0] if found else None
            events = list(self.tracer.events)
        self.handle.stop()
        self.handle = self.engine = None
        gc.collect()
        jax.monitoring.unregister_event_duration_listener(self._listener)

        model = spec.model_module(self.cfg["family"])
        fwd = jax.jit(lambda p, x: model.forward(p, x, self.cfg, "highest"))
        block = int(self.mix.get("reference_block", 8))
        ref = {}
        keys = [tuple(k) for k in keys]
        imgs = [traffic.pool_images(self.seed, k, self.cfg) for k in keys]
        flat = np.concatenate(imgs) if imgs else None
        t0 = time.monotonic()
        rows = []
        for i in range(0, 0 if flat is None else len(flat), block):
            chunk = flat[i:i + block]
            pad = np.zeros((block - len(chunk),) + chunk.shape[1:],
                           chunk.dtype)
            rows.append(np.asarray(fwd(self.params, np.concatenate(
                [chunk, pad])))[:len(chunk)])
        if rows:
            logits = np.concatenate(rows).astype(np.float32)
            off = 0
            for k, x in zip(keys, imgs):
                ref[f"{k[0]},{k[1]}"] = base64.b64encode(
                    logits[off:off + len(x)].tobytes()).decode("ascii")
                off += len(x)
        out["reference"] = ref
        out["reference_s"] = time.monotonic() - t0

        if self.trace:
            spans = devtrace.reduce_spans(events, window)
            out["spans"] = spans
            if xplane is not None:
                ops, syncs = devtrace.read_xplane(xplane)
                dev = devtrace.reduce_device(
                    ops, syncs, window, devtrace.host_activity(events))
                out["device_trace"] = dev
                if dev:
                    out["fold_events"] = [
                        (s, e) for p, _, s, e, f in ops
                        if f and p == dev["planes"][0]]
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return out


def _say(obj: dict) -> None:
    sys.stdout.write(PROTO + json.dumps(obj) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    _paths()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chipbench: the program (src/repro) is not "
                         f"beside the benchmark: {e}")
    enable_compile_cache()
    args = json.loads((argv or sys.argv[1:])[0])
    side = ServerSide(args["cfg"], args["mix"], args["seed"],
                      trace=args["trace"], chips=args["chips"])
    try:
        _say({"ready": side.boot()})
        line = sys.stdin.readline()
        if not line:
            return 1
        req = json.loads(line)["finish"]
        _say({"finished": side.finish(tuple(req["window"]), req["keys"])})
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
