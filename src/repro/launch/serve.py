"""Serving launcher: token requests through the BatchEngine, or — with
``--vision`` — an image request stream through the continuous-batching
vision engine (``serve/vision.py``).

    python -m repro.launch.serve --arch qwen3-4b --requests 8
    python -m repro.launch.serve --vision --requests 32 --backend interpret
    python -m repro.launch.serve --vision --model resnet18 --requests 16
    python -m repro.launch.serve --vision --model mobilenetv2 --requests 16

The vision path serves a deterministic mixed-size request stream through
the bucketed ``CompiledNetwork`` forwards of any registered conv model
(``models/zoo.py``, ``--model``) and merges its measured metrics (KIPS,
latency percentiles, slot occupancy, fold-reuse rates, robustness
counters) into ``BENCH_vgg.json``: per-model under
``serving_by_model.<name>``, with the legacy flat ``serving`` section
still tracking vgg16 (the original CI smoke contract) so older tooling
keeps working.

The vision path runs under a ``PreemptionGuard``: on SIGTERM/SIGINT the
engine stops admitting new requests, drains everything in flight, and
still emits its metrics — a clean preemption drain instead of a dropped
queue.

``--chaos SEED`` switches to the deterministic fault-injection smoke
(``serve/chaos.py``): the same stream is served under an injected fault
schedule (``--chaos-profile`` kernel-fault | nan | slow-batch | mixed)
and every recovery invariant is verified — zero lost requests, bitwise
surviving responses, the profile's expected degraded/shed counters
nonzero.  A violated invariant exits nonzero (the CI chaos job's
contract); metrics land under ``chaos_by_model.<name>``, never touching
the serving sections.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import api
from repro.serve.engine import BatchEngine, Request

# --backend choice -> core/engine.py execution policy
VISION_POLICIES = {"auto": "auto", "interpret": "pallas",
                   "reference": "reference"}


def merge_bench_json(summary: dict, path: str = "BENCH_vgg.json",
                     model: Optional[str] = None,
                     section: str = "serving") -> None:
    """Merge the serving section into the perf snapshot, preserving the
    micro-bench sections ``benchmarks/run.py`` wrote (and tolerating a
    missing or corrupt file — same discipline as the tuning cache).

    With ``model`` the metrics land under ``<section>_by_model.<model>``
    so each model's snapshot survives the others' runs; the legacy flat
    ``serving`` section is only (re)written for vgg16 — or when no model
    is named — never clobbered by another model's serve.  Chaos runs pass
    ``section="chaos"`` and land under ``chaos_by_model`` only, so a
    fault-injected run can never overwrite the healthy serving numbers
    the perf gate compares.  Model-agnostic sections (``model=None`` —
    the transport load generator aggregates across workers) write the
    flat ``data[section]`` directly."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    if not isinstance(data, dict):
        data = {}
    if model is not None:
        by_key = f"{section}_by_model"
        by_model = data.get(by_key)
        if not isinstance(by_model, dict):
            by_model = {}
        by_model[model] = summary
        data[by_key] = by_model
    if model is None or (section == "serving" and model == "vgg16"):
        data[section] = summary
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    key = (f"{section}_by_model.{model}" if model is not None
           else section)
    print(f"# wrote {section} metrics into {path} under {key!r}")


def make_obs(args):
    """(tracer, registry) per the ``--trace`` / ``--metrics-json`` flags
    — ``None`` for whichever is off, so the serving hot paths keep their
    no-op recorders."""
    tracer = registry = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer(time.monotonic)
    if args.metrics_json:
        from repro.obs.metrics import MetricsRegistry
        registry = MetricsRegistry()
    return tracer, registry


def lint_into_registry(registry, model: str, *, img: int,
                       width_mult: float) -> None:
    """Fold the static verifier's finding counts into the registry so one
    snapshot carries perf + robustness + lint health."""
    from repro.analysis.foldlint import lint_model
    summary = lint_model(model, img=img, width_mult=width_mult)
    rep = summary["report"]
    by_sev = {}
    for f in rep["findings"]:
        by_sev[f["severity"]] = by_sev.get(f["severity"], 0) + 1
    for sev in ("error", "warning", "info"):
        registry.counter("foldlint_findings_total",
                         "Static verifier findings by severity",
                         severity=sev).set_total(by_sev.get(sev, 0))
    registry.gauge("foldlint_ok", "1 when no error-severity findings"
                   ).set(1.0 if summary["ok"] else 0.0)


def write_obs_artifacts(args, tracer, registry) -> None:
    if tracer is not None:
        tracer.save(args.trace)
        print(f"# wrote Chrome trace ({len(tracer.events)} events) "
              f"to {args.trace}")
    if registry is not None:
        lint_into_registry(registry, args.model, img=args.img,
                           width_mult=args.width)
        with open(args.metrics_json, "w") as f:
            json.dump(registry.snapshot(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote metrics snapshot ({len(registry)} series) "
              f"to {args.metrics_json}")


def chaos_main(args) -> dict:
    """The deterministic fault-injection smoke: serve under an injected
    fault schedule, verify every recovery invariant, exit nonzero on any
    violation (``ChaosVerificationError`` propagates to the caller)."""
    from repro.serve.chaos import chaos_summary
    tracer, registry = make_obs(args)
    summary = chaos_summary(
        args.model, profile=args.chaos_profile, seed=args.chaos,
        requests=args.requests, img=args.img, width_mult=args.width,
        policy=VISION_POLICIES[args.backend],
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        deadline_s=args.deadline_s if args.deadline_s > 0 else 0.001,
        deadline_every=args.deadline_every,
        hang_timeout_s=args.hang_timeout_s, tracer=tracer,
        registry=registry, verbose=True)
    write_obs_artifacts(args, tracer, registry)
    merge_bench_json(summary, args.bench_json, model=args.model,
                     section="chaos")
    return summary


def vision_main(args) -> dict:
    from repro.ft.fault_tolerance import PreemptionGuard
    from repro.launch.mesh import make_local_mesh
    from repro.serve.vision import serving_summary
    if args.chaos is not None:
        return chaos_main(args)
    mesh = None
    if args.mesh:
        data, model_par = (int(t) for t in args.mesh.lower().split("x"))
        mesh = make_local_mesh(data, model_par)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    tracer, registry = make_obs(args)
    with PreemptionGuard() as guard:    # SIGTERM -> stop admitting, drain
        summary = serving_summary(
            args.model, requests=args.requests, img=args.img,
            width_mult=args.width, policy=VISION_POLICIES[args.backend],
            buckets=buckets, mesh=mesh, seed=args.seed,
            autotune=args.autotune, tuning_path=args.tuning_path or None,
            deadline_s=args.deadline_s or None,
            deadline_every=args.deadline_every,
            guard=guard, tracer=tracer, registry=registry,
            precision=args.precision, verbose=True)
    write_obs_artifacts(args, tracer, registry)
    # int8 serves land under their own section so the fp32 serving
    # baselines the perf gate compares are never clobbered
    section = "serving" if args.precision == "fp32" else \
        f"serving_{args.precision}"
    merge_bench_json(summary, args.bench_json, model=args.model,
                     section=section)
    return summary


def token_main(args) -> None:
    cfg = get_config(args.arch, reduced=not args.full)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = BatchEngine(cfg, params, batch=args.batch,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    reqs = []
    for i in range(args.requests):
        r = Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.new_tokens)
        reqs.append(r)
        engine.submit(r)
    engine.run()
    dt = time.monotonic() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.output) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {list(r.prompt)} -> {r.output}")


def main():
    from repro.models.zoo import conv_model_names
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    # token serving
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=12)
    # vision serving
    ap.add_argument("--vision", action="store_true",
                    help="serve an image stream through the compiled "
                         "fold-schedule engine instead of token decode")
    ap.add_argument("--model", default="vgg16",
                    choices=conv_model_names(),
                    help="registered conv model to serve (models/zoo.py)")
    ap.add_argument("--backend", choices=sorted(VISION_POLICIES),
                    default="auto",
                    help="vision execution: auto (backend policy), "
                         "interpret (Pallas fold kernels, interpreted "
                         "off-TPU), reference")
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--width", type=float, default=0.0625,
                    help="model width multiplier")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "int8"],
                    help="streaming precision for the compiled forwards; "
                         "int8 metrics merge under serving_int8_by_model")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="comma-separated batch bucket widths")
    ap.add_argument("--mesh", default="",
                    help='optional "DATAxMODEL" local mesh, e.g. "2x1"')
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--tuning-path", default="")
    ap.add_argument("--bench-json", default="BENCH_vgg.json")
    # observability (DESIGN.md §11)
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write a Chrome trace-event JSON of the full "
                         "request lifecycle (open in Perfetto)")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="write the bounded metrics-registry snapshot "
                         "(perf + robustness + foldlint health)")
    # robustness / fault injection
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request SLO in seconds (0 = no deadlines); "
                         "requests past it are shed or expired")
    ap.add_argument("--deadline-every", type=int, default=1,
                    help="attach the deadline to every Nth request "
                         "(1 = all)")
    ap.add_argument("--hang-timeout-s", type=float, default=30.0,
                    help="watchdog hang threshold for a single dispatch")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run the deterministic fault-injection smoke "
                         "with this seed instead of the plain serve "
                         "(vision only; exits nonzero on any recovery-"
                         "invariant violation)")
    ap.add_argument("--chaos-profile", default="mixed",
                    choices=["kernel-fault", "nan", "slow-batch", "mixed"],
                    help="which fault schedule --chaos injects")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.vision:
        vision_main(args)
    else:
        token_main(args)


if __name__ == "__main__":
    main()
