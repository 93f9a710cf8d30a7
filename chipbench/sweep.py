#!/usr/bin/env python3
"""Finds an online cell's knee by one sweep on the chip.

    python3 chipbench/sweep.py --workload resnet18-cifar32.online \\
        --seed 5 --seconds 10 --rates 100,200,300,400 [--write]

Boots the cell's server once, then offers the mix's open loop at each
rate in turn, ascending, for ``--seconds`` each, letting the queue drain
between rates.  A rate holds when the backlog (requests sent and not yet
answered, sampled every 50 ms) does not grow over the window: its mean
over the last quarter stays within twice its mean over the first quarter
plus two requests.
The knee is the highest rate that holds below the first that does not.
``--write`` stores 0.8 x the knee as the mix's ``rate_rps``.  Each
rate's offered and answered rate, backlog and p95 latency are printed as
one JSON line, the knee last.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import check, loadgen, run, spec, traffic  # noqa: E402


def _mean(xs):
    return sum(xs) / len(xs) if xs else 0.0


def judge(outcome, rate: float, seconds: float) -> dict:
    lo, hi = outcome.window
    q = (hi - lo) / 4
    first = [n for t, n in outcome.backlog if lo <= t < lo + q]
    last = [n for t, n in outcome.backlog if hi - q <= t < hi]
    answered = sum(1 for r in outcome.records
                   if r.t_done is not None and r.t_done <= hi)
    lat = [(r.t_done - r.t_due) * 1e3 if r.t_done else math.inf
           for r in outcome.records]
    return {"rate_rps": rate, "offered": len(outcome.records) / seconds,
            "answered_in_window_rps": answered / (hi - lo),
            "backlog_first_q": _mean(first), "backlog_last_q": _mean(last),
            "p50_ms": run.nearest_rank(lat, 50),
            "p95_ms": run.nearest_rank(lat, 95),
            "holds": _mean(last) <= 2 * _mean(first) + 2}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated req/s, ascending")
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    child = run.Child(cfg, mix, args.seed, False, int(cell["chips"]))
    rows, records = [], []
    try:
        bodies = {k: traffic.body(traffic.pool_images(args.seed, k, cfg))
                  for k in traffic.pool_keys(mix)}
        ready = child.boot()
        for rate in (float(r) for r in args.rates.split(",")):
            sched = traffic.open_schedule(mix, args.seed, args.seconds, rate)
            out = asyncio.run(loadgen.open_loop(
                ready["host"], ready["port"], sched, bodies, args.seconds))
            records += out.records
            rows.append(judge(out, rate, args.seconds))
            print(json.dumps(rows[-1]), flush=True)
            if not rows[-1]["holds"]:
                break
        fin = child.finish((0.0, 0.0), sorted({r.key for r in records}))
    except run.RunError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    finally:
        child.close()
    for r in records:
        r.parse()
    cmp = check.compare(records, check.decode_reference(fin["reference"]))
    held = [r["rate_rps"] for r in rows if r["holds"]]
    knee = max(held) if held else None
    result = {"knee_rps": knee, "device": ready["device"],
              "correct": check.correct(cmp, cfg), "checks": cmp}
    if knee and args.write:
        path = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
        mix["rate_rps"] = round(0.8 * knee, 1)
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")
        result["rate_rps_written"] = mix["rate_rps"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
