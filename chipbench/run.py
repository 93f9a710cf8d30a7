#!/usr/bin/env python3
"""One run of one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload vgg16-224.bulk --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration (``configs/<name>.json``), its traffic mix
(``traffic/<name>.json``) and its metrics are looked up by name in
``BENCHMARK.json`` (``spec.py``).  Two processes:

* a child (``server_child.py``) holds the chip: it serves the
  configuration over HTTP through ``launch/server.py:start_server`` and,
  after the window, runs the plain reference;
* this process never loads JAX: it encodes the request bodies from the
  seed while the child boots, drives ``POST /v1/infer`` for ``--seconds``
  (``loadgen.py``), and reduces the records to the metrics.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (``metrics/<name>.py``), from a run with the profiler
and the server's ``Tracer`` on.  Either way every response is compared
with the reference (``check.py``).  The last line of stdout is the
result as one JSON object; the last lines of stderr are the numbers
compared, each beside its limit.  Without a TPU, or without the program
beside the benchmark, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, devtrace, flops, loadgen, peaks, spec, \
    traffic  # noqa: E402
from chipbench.server_child import PROTO  # noqa: E402

BOOT_TIMEOUT_S = 1100.0
FINISH_TIMEOUT_S = 300.0
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class RunError(Exception):
    """The run cannot produce a result; no result line is printed."""


class Child:
    """``server_child.py`` in its own process, spoken to over its stdin
    and stdout.  Its compile cache is the checkout's ``.jax_cache``, and
    every program is cached, however quick to compile, so that a run
    after the first compiles nothing."""

    def __init__(self, cfg: dict, mix: dict, seed: int, trace: bool,
                 chips: int):
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        # libtpu logs to the fixed /tmp/tpu_logs unless told otherwise
        env.setdefault("TPU_LOG_DIR", "disabled")
        arg = json.dumps({"cfg": cfg, "mix": mix, "seed": seed,
                          "trace": trace, "chips": chips})
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "server_child.py"), arg],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
            env=env, text=True, bufsize=1)
        self._msgs: "queue.Queue" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith(PROTO):
                self._msgs.put(json.loads(line[len(PROTO):]))
            else:
                sys.stderr.write(line)
        self._msgs.put(None)

    def _wait(self, what: str, timeout: float) -> dict:
        try:
            msg = self._msgs.get(timeout=timeout)
        except queue.Empty:
            raise RunError(f"the server gave no {what} in {timeout:.0f}s")
        if msg is None or what not in msg:
            raise RunError(f"the server exited before {what} (exit code "
                           f"{self.proc.wait()})")
        return msg[what]

    def boot(self) -> dict:
        return self._wait("ready", BOOT_TIMEOUT_S)

    def finish(self, window, keys) -> dict:
        self.proc.stdin.write(json.dumps(
            {"finish": {"window": list(window),
                        "keys": [list(k) for k in keys]}}) + "\n")
        self.proc.stdin.flush()
        return self._wait("finished", FINISH_TIMEOUT_S)

    def close(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._reader.join(timeout=10)


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (an inf stays an inf)."""
    ys = sorted(values)
    return ys[max(0, math.ceil(q / 100.0 * len(ys)) - 1)]


def ok(r) -> bool:
    return r.status == 200 and r.served_by == "primary"


def end_to_end(outcome, mix: dict, setup_s: float) -> dict:
    lo, hi = outcome.window
    out = {"setup_s": setup_s}
    if mix["loop"] == "closed":
        done = [r.n_images for r in outcome.records
                if ok(r) and lo < r.t_done <= hi]
        out["images_per_s"] = sum(done) / (hi - lo)
    else:
        lat = [(r.t_done - r.t_due) * 1e3 if ok(r) else math.inf
               for r in outcome.records if lo <= r.t_due < hi]
        out["latency_p50_ms"] = nearest_rank(lat, 50)
        out["latency_p95_ms"] = nearest_rank(lat, 95)
    return out


def layer_records(outcome, fin: dict, cfg: dict, mix: dict,
                  device: dict) -> dict:
    """What the per-layer readers (``metrics/<name>.py``) read: the
    cell, the peak, FLOPs per image, the client's send-to-last-byte times
    of the requests sent in the window, the ``Tracer`` reduction
    (``devtrace.reduce_spans``), the device trace's and, in a cell of one
    bucket, the fold kernels' roofline sums."""
    model = spec.model_module(cfg["family"])
    layers = model.layers(cfg)
    lo, hi = outcome.window
    sent = [r.t_done - r.t_send for r in outcome.records
            if ok(r) and lo <= r.t_send < hi]
    rec = {"cfg": cfg, "mix": mix, "window": (lo, hi),
           "peak": peaks.peak_for(device["kind"]),
           "flops_per_image": flops.flops_per_image(layers),
           "client_latency_s": {"n": len(sent), "sum": sum(sent)},
           "spans": fin.get("spans"), "device_trace": fin.get("device_trace"),
           "fold": None}
    dev = rec["device_trace"]
    buckets = mix["buckets"]
    if dev and fin.get("fold_events") and len(buckets) == 1:
        launches = flops.conv_launches(layers, int(buckets[0]))
        rec["fold"] = devtrace.fold_roofline(
            [tuple(f) for f in fin["fold_events"]], dev["window_trace"],
            len(launches),
            [flops.roofline_min_s(x, rec["peak"]) for x in launches])
    return rec


def run_cell(server, bench: dict, cell: dict, cfg: dict, mix: dict,
             seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> dict:
    """Boot ``server`` (a ``Child``, or an in-process ``ServerSide``),
    drive one window, compare, and return the result object."""
    keys = traffic.pool_keys(mix)
    bodies = {k: traffic.body(traffic.pool_images(seed, k, cfg))
              for k in keys}
    ready = server.boot()
    host, port = ready["host"], ready["port"]
    if mix["loop"] == "closed":
        outcome = asyncio.run(loadgen.closed_loop(
            host, port, traffic.closed_plan(mix, seed), bodies, seconds))
    else:
        outcome = asyncio.run(loadgen.open_loop(
            host, port, traffic.open_schedule(mix, seed, seconds), bodies,
            seconds))
    setup_s = outcome.first_send - t_start
    used = sorted({r.key for r in outcome.records})
    fin = server.finish(outcome.window, used)
    for r in outcome.records:
        r.parse()
    cmp = check.compare(outcome.records,
                        check.decode_reference(fin["reference"]))
    lo, hi = outcome.window
    device = dict(ready["device"],
                  memory_peak_bytes=fin.get("memory_peak_bytes"))
    late = [r.t_send - r.t_due for r in outcome.records if r.t_send]
    run = {"window_s": hi - lo,
           "requests_in_window": sum(1 for r in outcome.records
                                     if lo <= r.t_send < hi),
           "compiles_in_window": sum(1 for t in fin["compiles"]
                                     if lo <= t <= hi),
           "send_late_p95_ms": nearest_rank(late, 95) * 1e3 if late
           else None,
           "send_late_max_ms": max(late) * 1e3 if late else None,
           "boot_s": ready["boot_s"], "warmup_s": ready["warmup_s"],
           "reference_s": fin["reference_s"],
           "robustness": fin["robustness"]}
    names = [m["name"] for m in (spec.per_layer_metrics(bench, cell["name"])
                                 if trace else
                                 spec.end_to_end_metrics(bench,
                                                         cell["name"]))]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    result = {"correct": check.correct(cmp, cfg),
              "attempted": len(outcome.records), "failed": cmp["failed"]}
    if trace:
        rec = layer_records(outcome, fin, cfg, mix, device)
        values = {n: spec.metric_reader(n)(rec) for n in names}
        dev = fin.get("device_trace") or {}
        if dev:
            device.update(busy_s=dev["busy_s"], window_s=dev["window_s"])
            result["breakdown"] = {"device_ops": dev["device_ops"],
                                   "idle_gaps": dev["idle_gaps"]}
            run["clock_drift_s"] = dev["clock_drift_s"]
    else:
        e2e = end_to_end(outcome, mix, setup_s)
        values = {n: e2e.get(n) for n in names}
    result["metrics"] = {n: {"value": v, "unit": units[n]}
                         for n, v in values.items()
                         if v is not None and math.isfinite(v)}
    result["device"] = device
    result["run"] = run
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in check.checks(cmp, cfg)}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    child = Child(cfg, mix, args.seed, bool(args.trace), int(cell["chips"]))
    try:
        result = run_cell(child, bench, cell, cfg, mix, args.seed,
                          args.seconds, bool(args.trace))
    except RunError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    finally:
        child.close()
    if child.proc.returncode:
        print(f"chipbench: the server exited with "
              f"{child.proc.returncode}", file=sys.stderr)
        return 1
    print("run: " + json.dumps(result["run"]), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
