#!/usr/bin/env python3
"""Smoke test of the served path on TPU.

    python chip_smoke.py              # one chip: vgg16 served over HTTP
    python chip_smoke.py --chips 4    # four chips: the 2x2-mesh engine only

One chip: boots the HTTP server through ``launch/server.py:start_server``
with vgg16 at its published shape (width 1.0, 224x224 input, 1000
classes, weights drawn from ``--seed``) and ``policy="auto"``, sends
seeded requests of 1-8 images over HTTP so that every batch bucket is
used, and checks that

* JAX runs on a TPU and the engine resolved the Pallas fold kernels,
  compiled (``interpret=False``);
* every response is 200 and ``served_by == "primary"``, and the server
  counts no degraded batch, failed request or lost request — a batch
  that needed the reference fallback fails the smoke;
* the served logits agree with an independent float32 reference —
  ``vgg.forward`` on ``kernels/ops.py``'s ``"xla"`` conv under
  ``jax.default_matmul_precision("highest")`` — on the same images,
  within ``RTOL`` and with the same top-1 class on every image.

Four chips (``--chips 4``): the ``VisionEngine`` on a 2x2 mesh (batch on
``data``, N_F on ``model``) against the single-device engine on the same
requests; nothing else runs.

The script exits nonzero, and prints no result line, when JAX finds no
TPU, when the repository's ``src/`` is not beside it, or when any check
fails.  Its last line on stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

MODEL = "vgg16"
WIDTH, IMG, CLASSES = 1.0, 224, 1000
BUCKETS = (1, 2, 4, 8)

# Logit tolerance, relative to the largest reference logit magnitude of
# the image.  The fold kernels contract fp32 operands at
# Precision.HIGHEST with fp32 accumulation (``kernels/conv2d_ws.py:
# _row_taps``), and so do the dense head and the reference; the two
# differ only in the order of their fp32 sums.  Reordering a K-term fp32
# dot moves it by at most K * 2**-24 of its absolute sum — 2.7e-4 for
# vgg16's deepest reduction (K = 512 * 3 * 3) — and by about sqrt(K) *
# 2**-24 (4e-6) in the typical case, so across 16 layers reordering stays
# well below 1e-3.  A contraction that rounds its operands to bf16
# (2**-9 per product) would already miss it in one layer.
RTOL = 1e-3


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu(jax, chips: int) -> dict:
    """The device check: a TPU with at least ``chips`` chips, else exit
    nonzero before any result is printed."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"JAX found no TPU: platform {platform!r} with "
             f"{len(devices)} device(s); this smoke runs on the chip only")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPU chips, JAX sees "
             f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def request_sizes(rng):
    """Eight requests of 1..8 images in seeded order: sent one at a time,
    each forms its own batch, so every bucket width is served."""
    return [int(n) for n in rng.permutation(8) + 1]


def reference_logits(params, images):
    """The float32 reference: ``vgg.forward`` with the ``"xla"`` conv,
    every contraction at Precision.HIGHEST, in batches of 8."""
    import jax
    import numpy as np

    from repro.models import vgg
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: vgg.forward(p, x, impl="xla"))
        out = []
        for i in range(0, len(images), 8):
            chunk = images[i:i + 8]
            pad = np.zeros((8 - len(chunk),) + chunk.shape[1:], chunk.dtype)
            out.append(np.asarray(fwd(params, np.concatenate([chunk, pad]))
                                  )[:len(chunk)])
    return np.concatenate(out)


def check_logits(label: str, got, ref) -> None:
    """Per image: |got - ref| <= RTOL * max|ref|, and equal top-1."""
    import numpy as np
    for i, (g, r) in enumerate(zip(got, ref)):
        if not np.isfinite(g).all():
            fail(f"{label} image {i}: non-finite logits")
        err = float(np.max(np.abs(g - r)))
        scale = float(np.max(np.abs(r)))
        if err > RTOL * scale:
            fail(f"{label} image {i}: max |diff| {err:.6g} exceeds "
                 f"{RTOL} x max |ref| = {RTOL * scale:.6g}")
        if int(np.argmax(g)) != int(np.argmax(r)):
            fail(f"{label} image {i}: top-1 {int(np.argmax(g))} != "
                 f"reference {int(np.argmax(r))}")


def check_server_stats(stats: dict, sent: int) -> None:
    """The server's ``/stats``: every request counted, and no batch that
    needed the reference fallback, no failed and no lost request."""
    totals = stats["totals"]
    degraded = sum(w["engine"]["robustness"]["degraded_batches"]
                   for w in stats["workers"].values())
    print(f"server totals: {json.dumps(totals)} "
          f"degraded_batches={degraded}", flush=True)
    if degraded or totals["failed"] or totals["lost_requests"]:
        fail(f"the server needed its fallback or lost work: "
             f"degraded_batches={degraded}, failed={totals['failed']}, "
             f"lost_requests={totals['lost_requests']}")
    if totals["requests"] != sent:
        fail(f"server counted {totals['requests']} requests, sent {sent}")


def cache_entries(path: str) -> set:
    """The compiled programs in the persistent cache at ``path``."""
    if not os.path.isdir(path):
        return set()
    return {f for f in os.listdir(path) if f.endswith("-cache")}


def http_call(conn, method: str, path: str, payload=None):
    body = None if payload is None else json.dumps(payload)
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, json.loads(resp.read())


def serve_phase(jax, seed: int) -> None:
    """vgg16 at full width over HTTP on one chip (see module doc)."""
    import numpy as np

    from repro.launch.server import start_server
    from repro.serve.transport import encode_images_payload

    t0 = time.monotonic()
    handle = start_server(MODEL, port=0, n_workers=1, img=IMG,
                          width_mult=WIDTH, classes=CLASSES, policy="auto",
                          buckets=BUCKETS, seed=seed)
    try:
        boot = handle.boot
        print(f"boot: {json.dumps(boot)} ({time.monotonic() - t0:.1f}s "
              "incl. warmup)", flush=True)
        if boot["platform"] != "tpu" or boot["mode"] != "pallas" \
                or boot["interpret"] is not False:
            fail(f"the server does not run compiled Pallas fold kernels "
                 f"on the TPU: {boot}")
        engine = handle.workers[0].worker.engine
        for b, s in sorted(engine.warmup_s.items()):
            print(f"compile+warmup bucket {b}: {s:.2f}s", flush=True)
        df = sorted({sch.dataflow for _, sch in
                     engine.compiler.network_for(BUCKETS[-1])
                     .layer_schedules})
        print(f"fold dataflows in use: {df}", flush=True)

        rng = np.random.default_rng(seed)
        sizes = request_sizes(rng)
        images = [rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)
                  for n in sizes]
        conn = http.client.HTTPConnection(handle.host, handle.port,
                                          timeout=900)
        served = []
        for i, x in enumerate(images):
            t = time.monotonic()
            status, obj = http_call(conn, "POST", "/v1/infer",
                                    encode_images_payload(x))
            print(f"request {i}: images={len(x)} status={status} "
                  f"served_by={obj.get('served_by')} "
                  f"wall={time.monotonic() - t:.3f}s", flush=True)
            if status != 200 or obj.get("served_by") != "primary":
                fail(f"request {i}: status {status}, served_by "
                     f"{obj.get('served_by')!r}, error {obj.get('error')!r}")
            served.append(np.asarray(obj["logits"], np.float32))
        status, stats = http_call(conn, "GET", "/stats")
        conn.close()
        if status != 200:
            fail(f"/stats answered {status}")
        check_server_stats(stats, len(images))
        stats_mem = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats_mem:
            print(f"peak device memory: {stats_mem['peak_bytes_in_use']} "
                  "bytes", flush=True)

        got = np.concatenate(served)
        ref = reference_logits(engine.params, np.concatenate(images))
        err = np.max(np.abs(got - ref), axis=1) / np.max(np.abs(ref),
                                                         axis=1)
        print(f"reference check: {len(got)} images, max relative error "
              f"{float(err.max()):.3e} (RTOL {RTOL}), top-1 agreement "
              f"{int((got.argmax(1) == ref.argmax(1)).sum())}/{len(got)}",
              flush=True)
        check_logits("served", got, ref)
    finally:
        handle.stop()


def mesh_phase(jax, seed: int) -> None:
    """The VisionEngine on a 2x2 mesh against the single-device engine
    on the same requests (four chips)."""
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.models.zoo import get_conv_model
    from repro.serve.vision import VisionEngine

    spec = get_conv_model(MODEL)
    params = spec.init_params(jax.random.PRNGKey(seed), width_mult=WIDTH,
                              img=IMG, classes=CLASSES)
    mesh = make_local_mesh(2, 2)
    single = VisionEngine(params, spec.to_graph(), img=IMG, policy="auto",
                          buckets=BUCKETS)
    sharded = VisionEngine(params, spec.to_graph(), img=IMG, policy="auto",
                           buckets=BUCKETS, mesh=mesh)
    mesh_devices = set(mesh.devices.flat)
    placed = {d for leaf in jax.tree.leaves(sharded.params)
              for d in leaf.sharding.device_set}
    short = [path for path, leaf in
             jax.tree_util.tree_flatten_with_path(sharded.params)[0]
             if leaf.sharding.device_set != mesh_devices]
    print(f"mesh {dict(mesh.shape)}: params on {len(placed)} device(s); "
          f"leaves not on all {len(mesh_devices)}: {len(short)}", flush=True)
    if placed != mesh_devices or short:
        fail(f"params are not placed on all {len(mesh_devices)} mesh "
             f"devices: {short[:3]}")
    for name, eng in (("single", single), ("mesh", sharded)):
        eng.warmup()
        for b, s in sorted(eng.warmup_s.items()):
            print(f"{name} compile+warmup bucket {b}: {s:.2f}s", flush=True)

    rng = np.random.default_rng(seed)
    sizes = request_sizes(rng)
    images = [rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)
              for n in sizes]
    outs = {}
    for name, eng in (("single", single), ("mesh", sharded)):
        reqs = [eng.submit(x) for x in images]
        eng.run()
        rb = eng.metrics_dict()["robustness"]
        if any(r.served_by != "primary" for r in reqs) or \
                rb["degraded_batches"] or rb["failed"] or \
                rb["lost_requests"]:
            fail(f"{name} engine needed its fallback or lost work: {rb}")
        outs[name] = np.concatenate([r.logits for r in reqs])
        print(f"{name}: served {len(reqs)} requests, batches/bucket "
              f"{eng.metrics.per_bucket}", flush=True)
    got, ref = outs["mesh"], outs["single"]
    bitwise = bool(np.array_equal(got, ref))
    err = np.max(np.abs(got - ref), axis=1) / np.max(np.abs(ref), axis=1)
    print(f"mesh vs single: {len(got)} images, bitwise={bitwise}, max "
          f"relative error {float(err.max()):.3e} (RTOL {RTOL}), top-1 "
          f"agreement {int((got.argmax(1) == ref.argmax(1)).sum())}/"
          f"{len(got)}", flush=True)
    check_logits("mesh", got, ref)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: vgg16 over HTTP; 4: only the 2x2-mesh engine "
                         "against the single-device engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repository's src/ is not beside {__file__}: {e}")
    cache_dir = enable_compile_cache()
    import jax
    device = require_tpu(jax, args.chips)
    before = cache_entries(cache_dir)
    print(f"device: {json.dumps(device)}; compile cache {cache_dir} "
          f"({len(before)} entries)", flush=True)
    t0 = time.monotonic()
    if args.chips == 4:
        mesh_phase(jax, args.seed)
    else:
        serve_phase(jax, args.seed)
    new = sorted(f.rsplit("-", 2)[0] for f in
                 cache_entries(cache_dir) - before)
    print(f"done in {time.monotonic() - t0:.1f}s; compile cache entries "
          f"{len(before)} -> {len(before) + len(new)}, new: {new}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
