#!/usr/bin/env python3
"""Readings that a cell's ``logit_err`` limit is set from, on the chip.

    python3 chipbench/control.py --workload vgg16-224.bulk --seeds 1,2,3

For each seed, at the cell's own size and on the bodies a run of that
seed sends: the plain reference at HIGHEST, the control (the same
reference in the three-pass bf16 split, ``models/common.py``), and the
served engine's compiled forward (``VisionEngine`` with the cell's
buckets, every body submitted at once).  Prints one JSON line per seed
with the program's and the control's ``logit_err`` (``check.py``'s
measure).  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    sys.path.insert(0, _p)


def logit_err(got, want) -> float:
    import numpy as np
    return float((np.abs(got - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np

    from chipbench import spec, traffic
    from chipbench.models.common import seed_key
    from repro.models.zoo import get_conv_model
    from repro.serve.vision import VisionEngine
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    model = spec.model_module(cfg["family"])
    init = jax.jit(functools.partial(model.init_params, cfg=cfg))
    fwd = {p: jax.jit(lambda prm, x, p=p: model.forward(prm, x, cfg, p))
           for p in ("highest", "bf16x3")}
    block = int(mix.get("reference_block", 8))
    graph = get_conv_model(cfg["program_model"]).to_graph()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        params = init(seed_key(seed))
        keys = traffic.pool_keys(mix)
        x = np.concatenate([traffic.pool_images(seed, k, cfg)
                            for k in keys])
        ref = {}
        for p, f in fwd.items():
            ref[p] = np.concatenate([np.asarray(f(params, x[i:i + block]))
                                     for i in range(0, len(x), block)])
        engine = VisionEngine(params, graph, img=int(cfg["img"]),
                              chan=int(cfg["channels"]), policy="auto",
                              buckets=tuple(mix["buckets"]))
        reqs = [engine.submit(traffic.pool_images(seed, k, cfg))
                for k in keys]
        engine.run()
        served = np.concatenate([r.logits for r in reqs])
        print(json.dumps({
            "seed": seed, "images": len(x),
            "served_by": sorted({r.served_by for r in reqs}),
            "program_err": logit_err(served, ref["highest"]),
            "control_err": logit_err(ref["bf16x3"], ref["highest"])}),
            flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
