"""Reduction of the server's stage spans (``serve/transport.py``,
``serve/vision.py``) to the sums the HTTP-front, batching and
engine-step metrics read.

``reduce(events, window)`` returns keys that ``devtrace.reduce_spans``
does not have, each ``{"n": count, "sum": seconds}``:

* ``codec_s``: per ``POST /v1/infer`` wire request whose span starts in
  the window, the wall time of its ``decode`` plus ``encode`` children;
* ``queue_wait_s``: per request served by the primary path and handed
  over in the window, its ``queued_ms`` (hand-off to the batch that took
  it), from the request's lifetime span;
* ``host_offcpu_s``: per pair of consecutive ``kernel`` spans inside the
  window, the wall gap between them less the engine thread's CPU time
  in it (``cpu_start_s``/``cpu_end_s``): time the thread was off the
  CPU, waiting for the interpreter lock or blocked.

A program without these spans or stamps gives ``n == 0``.
"""
from __future__ import annotations

from typing import Dict, Sequence

from chipbench.devtrace import Interval, span_intervals

INFER = "POST /v1/infer"


def _total(values) -> Dict[str, float]:
    values = list(values)
    return {"n": len(values), "sum": float(sum(values))}


def reduce(events: Sequence[dict], window: Interval) -> dict:
    lo, hi = window
    parts: Dict[int, Dict[str, float]] = {}   # wire span id -> codec parts
    for ev in events:
        a = ev.get("args", {})
        if ev.get("ph") == "X" and ev.get("cat") == "transport" \
                and ev["name"] in ("decode", "encode") and "parent_id" in a:
            parts.setdefault(a["parent_id"], {})[ev["name"]] = \
                ev.get("dur", 0.0) * 1e-6
    codec = []
    for s, _, a in span_intervals(events, name=INFER, cat="transport"):
        kids = parts.get(a.get("span_id"), {})
        if lo <= s < hi and len(kids) == 2:
            codec.append(kids["decode"] + kids["encode"])

    queued = [a["queued_ms"] * 1e-3
              for s, _, a in span_intervals(events, cat="request")
              if a.get("served_by") == "primary" and "queued_ms" in a
              and lo <= s - a.get("inbox_ms", 0.0) * 1e-3 < hi]

    kern = [(s, e, a) for s, e, a in span_intervals(
        events, name="kernel", cat="device") if "cpu_start_s" in a]
    offcpu = [(s2 - e1) - (a2["cpu_start_s"] - a1["cpu_end_s"])
              for (_, e1, a1), (s2, _, a2) in zip(kern, kern[1:])
              if lo <= e1 and s2 <= hi]
    return {"codec_s": _total(codec), "queue_wait_s": _total(queued),
            "host_offcpu_s": _total(offcpu)}
