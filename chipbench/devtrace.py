"""Reduction of a JAX profiler trace (``.xplane.pb``) and the server's
``Tracer`` spans to the numbers the per-layer metrics read.

Clocks.  Device and host events of one xplane share the trace's time
base.  The server process stamps that base against ``time.monotonic``
with a ``chipbench_sync`` annotation (carrying ``mono_ns``) right after
the profiler starts and right before it stops; the two stamps give the
offset and show whether the clocks drift apart.  ``time.monotonic`` is
the clock of the load generator's records and of the ``Tracer`` spans,
so with the offset every device interval can be clipped to the window
and set against what the host was doing.

Device busy time is the union of the intervals of the operations on a
device's ``XLA Ops`` line; the idle share is 1 minus busy over the
window.  An op's event name there is its HLO instruction text; the fold
kernels of ``kernels/conv2d_ws.py`` are the program's only Mosaic custom
calls (``custom_call_target="tpu_custom_call"``), since their
``pallas_call``s carry no name of their own yet.
"""
from __future__ import annotations

import collections
from typing import Iterable, List, Optional, Sequence, Tuple

SYNC = "chipbench_sync"
OPS_LINE = "XLA Ops"
FOLD_PATTERNS = ("tpu_custom_call",)

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle intervals of [lo, hi] between ``merged`` busy ones."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def is_fold(name: str) -> bool:
    return any(p in name for p in FOLD_PATTERNS)


def short_name(name: str) -> str:
    """``%forward.22 = f32[32,512,14,14]{...} custom-call(...), ...`` ->
    ``%forward.22 f32[32,512,14,14] custom-call``."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name[:120]
    shape, _, rest = rhs.partition(" ")
    op = rest.split("(", 1)[0]
    return f"{lhs} {shape.split('{', 1)[0]} {op}"[:120]


def read_xplane(path: str):
    """(device ops, sync stamps) of a trace file.

    Device ops: ``(plane, name, start_s, end_s, fold)`` on every
    ``/device:`` plane's ``XLA Ops`` line, in the trace's seconds.  Sync
    stamps: ``(trace_s, monotonic_s)`` of every ``chipbench_sync``
    annotation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, syncs = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((plane.name, short_name(ev.name),
                                ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                is_fold(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        mono = dict(ev.stats).get("mono_ns")
                        if mono is not None:
                            syncs.append((ev.start_ns * 1e-9,
                                          int(mono) * 1e-9))
    return ops, syncs


def clock_offset(syncs: Sequence[Tuple[float, float]]
                 ) -> Tuple[Optional[float], Optional[float]]:
    """(monotonic - trace seconds, drift between the first and last
    stamp); (None, None) without a stamp."""
    if not syncs:
        return None, None
    offs = [m - t for t, m in syncs]
    return offs[0], max(offs) - min(offs)


def fold_roofline(folds: Sequence[Interval], window: Interval,
                  convs_per_forward: int,
                  min_s_per_conv: Sequence[float]) -> Optional[dict]:
    """``folds``: one plane's fold-kernel events (start, end).  In
    launch order, event i is conv ``i % convs_per_forward`` of a
    forward, which holds where the trace starts before the first forward
    and ends after the last one, and every forward has one batch width.
    Returns the summed device time and roofline-minimum time of the
    events that start in ``window``, or None where the events are not
    whole forwards."""
    folds = sorted(folds)
    if not folds or len(folds) % convs_per_forward:
        return None
    lo, hi = window
    dev = roof = 0.0
    n = 0
    for i, (s, e) in enumerate(folds):
        if lo <= s < hi:
            dev += e - s
            roof += min_s_per_conv[i % convs_per_forward]
            n += 1
    if not n:
        return None
    return {"events": n, "device_s": dev, "roofline_min_s": roof}


def reduce_device(ops, syncs, window_mono: Interval,
                  host_spans: Sequence[Tuple[str, float, float]] = (),
                  top: int = 10) -> dict:
    """Busy time, the top device operations and the longest idle gaps in
    the window (given on ``time.monotonic``).  Where the clocks agree
    (the sync stamps drift by under 1 ms; device and host events of the
    trace agree to about a millisecond), each gap is labelled by the
    ``host_spans`` (name, start, end on ``time.monotonic``) span that
    covers most of it, with the share it covers; "no host span" where
    none does."""
    off, drift = clock_offset(syncs)
    if off is None:
        return {}
    lo, hi = window_mono[0] - off, window_mono[1] - off
    planes = sorted({p for p, *_ in ops})
    busy, top_ops, all_gaps = [], collections.Counter(), []
    for plane in planes:
        mine = [o for o in ops if o[0] == plane]
        merged = merge(clip(((s, e) for _, _, s, e, _ in mine), lo, hi))
        busy.append(sum(b - a for a, b in merged))
        for _, name, s, e, _ in mine:
            if e > lo and s < hi:
                top_ops[name] += min(e, hi) - max(s, lo)
        all_gaps += gaps(merged, lo, hi)
    agree = drift is not None and drift < 1e-3
    labelled = []
    for a, b in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        label = "unattributed"
        if agree:
            cover = collections.Counter()
            for name, s, e in host_spans:
                ov = min(e - off, b) - max(s - off, a)
                if ov > 0:
                    cover[name] += ov
            label = "no host span"
            if cover:
                name, ov = cover.most_common(1)[0]
                label = f"{name} {100 * ov / (b - a):.0f}%"
        labelled.append([label, b - a])
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "planes": planes,
        "clock_drift_s": drift,
        "device_ops": [[n, s] for n, s in top_ops.most_common(top)],
        "idle_gaps": labelled,
        "window_trace": (lo, hi),
    }


# -- the server's Tracer spans ------------------------------------------------

def span_intervals(events: Sequence[dict], *, name: Optional[str] = None,
                   cat: Optional[str] = None
                   ) -> List[Tuple[float, float, dict]]:
    """(start, end, args) in seconds of the complete spans that match."""
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        if name is not None and ev["name"] != name:
            continue
        if cat is not None and ev.get("cat") != cat:
            continue
        s = ev["ts"] * 1e-6
        out.append((s, s + ev.get("dur", 0.0) * 1e-6, ev.get("args", {})))
    return sorted(out, key=lambda x: x[0])


def reduce_spans(events: Sequence[dict], window: Interval) -> dict:
    """What the per-layer metrics read from the ``Tracer``: the lifetimes
    of the requests submitted in ``window``, the kernel (dispatch to
    readback) spans that end in it, the images and bucket slots they
    served, and the host gaps between them."""
    lo, hi = window
    life = [e - s for s, e, _ in span_intervals(events, cat="request")
            if lo <= s < hi]
    kern = span_intervals(events, name="kernel", cat="device")
    inside = [(s, e, a) for s, e, a in kern if lo <= e <= hi]
    host_gaps = [s2 - e1 for (_, e1, _), (s2, _, _) in zip(kern, kern[1:])
                 if lo <= e1 and s2 <= hi]
    return {
        "lifetimes_s": {"n": len(life), "sum": sum(life)},
        "kernel_spans": len(inside),
        "images": sum(int(a.get("n_images", 0)) for _, _, a in inside),
        "slots": sum(int(a.get("bucket", 0)) for _, _, a in inside),
        "host_gap_s": {"n": len(host_gaps), "sum": sum(host_gaps)},
    }


def host_activity(events: Sequence[dict]) -> List[Tuple[str, float, float]]:
    """The host spans that idle gaps are labelled with: the engine's
    stages, not the per-request lifetimes, the apportioned per-layer
    shares or the transport spans (which also hold a keep-alive
    connection's idle wait for its next request)."""
    out = []
    for ev in events:
        if ev.get("ph") == "X" and ev.get("cat") not in (
                "request", "layer", "transport") \
                and not ev.get("args", {}).get("apportioned"):
            s = ev["ts"] * 1e-6
            out.append((f"host:{ev['name']}", s,
                        s + ev.get("dur", 0.0) * 1e-6))
    return out
