"""Streaming telemetry (DESIGN.md §11): bounded histograms and the
metrics registry, the span tracer's determinism and Chrome trace-event
schema, and the serving integration —
every submitted request visible in the trace with a terminal outcome.
"""
import json
import math
import re

import jax
import numpy as np
import pytest

from repro.obs.metrics import (Counter, LogHistogram, MetricsRegistry,
                               validate_metrics_snapshot)
from repro.obs.trace import (NULL_TRACER, NullTracer, Tracer,
                             span_tree, validate_trace)

IMG, WIDTH, CLASSES = 32, 0.0625, 10


@pytest.fixture(scope="module")
def vgg_params():
    from repro.models import vgg
    return vgg.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                           img=IMG, classes=CLASSES)


class FakeClock:
    """Deterministic injectable clock: each call advances a fixed step."""

    def __init__(self, step=0.001):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# --------------------------------------------------------------------------
# LogHistogram: bounded memory, bounded quantile error
# --------------------------------------------------------------------------

def test_histogram_quantiles_vs_numpy():
    """Quantile estimates stay within the advertised relative error of
    np.percentile on an adversarial mixture (lognormal bulk + uniform
    shelf + far outliers)."""
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        rng.lognormal(math.log(0.02), 1.0, 20_000),
        rng.uniform(0.5, 1.5, 2_000),
        np.array([50.0, 120.0, 300.0]),
    ])
    h = LogHistogram()
    h.record_many(vals)
    assert h.count == vals.size
    assert h.total == pytest.approx(vals.sum())
    assert h.min == vals.min() and h.max == vals.max()
    for p in (1, 25, 50, 90, 95, 99, 99.9):
        want = float(np.percentile(vals, p, method="inverted_cdf"))
        got = h.percentile(p)
        assert abs(got - want) / want <= h.rel_error, \
            f"p{p}: {got} vs numpy {want}"
    # the endpoints are exact thanks to the min/max clamp
    assert h.quantile(0.0) == vals.min()
    assert h.quantile(1.0) == vals.max()


def test_histogram_memory_fixed_after_100k():
    """The OOM-proofing claim: 100k recordings change no allocation."""
    h = LogHistogram()
    before = h.nbytes
    nbuckets = h.counts.size
    rng = np.random.default_rng(1)
    h.record_many(rng.lognormal(-3.0, 2.0, 100_000))
    assert h.count == 100_000
    assert h.nbytes == before
    assert h.counts.size == nbuckets


def test_histogram_underflow_overflow_and_nan():
    h = LogHistogram(lo=1e-3, hi=10.0, buckets_per_decade=8)
    h.record(0.0)            # underflow bucket
    h.record(-1.0)           # negative -> underflow too
    h.record(100.0)          # overflow bucket
    h.record(float("nan"))   # dropped entirely
    assert h.count == 3
    assert h.counts[0] == 2 and h.counts[-1] == 1
    # estimates clamp to the observed range even from the edge buckets
    assert h.quantile(0.0) == -1.0
    assert h.quantile(1.0) == 100.0
    snap = h.snapshot()
    assert snap["count"] == 3
    assert sum(snap["buckets"].values()) == 3


def test_histogram_empty_and_bad_args():
    h = LogHistogram()
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=0.5)


# --------------------------------------------------------------------------
# MetricsRegistry: cardinality cap, Prometheus exposition, JSON snapshot
# --------------------------------------------------------------------------

def test_registry_get_or_create_and_type_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", outcome="ok")
    c.inc(3)
    assert reg.counter("requests_total", outcome="ok").value == 3
    assert reg.counter("requests_total", outcome="failed").value == 0
    with pytest.raises(ValueError):
        reg.gauge("requests_total")          # one name, one type
    with pytest.raises(ValueError):
        reg.counter("bad name!")
    c2 = Counter()
    c2.set_total(5)
    with pytest.raises(ValueError):
        c2.set_total(4)                      # counters never decrease


def test_registry_label_cardinality_cap():
    reg = MetricsRegistry(max_series=4)
    for i in range(4):
        reg.counter("c_total", shard=str(i)).inc()
    with pytest.raises(ValueError, match="label cardinality"):
        reg.counter("c_total", shard="4")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "Requests", outcome="ok").inc(7)
    reg.gauge("serve_deadline_hit_rate", "SLO hit fraction").set(0.5)
    h = reg.histogram("serve_latency_seconds", "Latency")
    h.record_many([0.01, 0.02, 0.02, 5.0])
    text = reg.to_prometheus()
    assert '# TYPE serve_requests_total counter' in text
    assert 'serve_requests_total{outcome="ok"} 7' in text
    assert '# TYPE serve_deadline_hit_rate gauge' in text
    # histogram: cumulative buckets, closed by +Inf == count, plus
    # _sum/_count — the format scrapers actually parse
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    for ln in lines:
        assert re.match(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$', ln)
    bucket_vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                   if ln.startswith("serve_latency_seconds_bucket")]
    assert bucket_vals == sorted(bucket_vals)          # cumulative
    assert bucket_vals[-1] == 4
    assert "serve_latency_seconds_count 4" in text
    inf_lines = [ln for ln in lines if 'le="+Inf"' in ln]
    assert len(inf_lines) == 1 and inf_lines[0].endswith(" 4")


def test_snapshot_schema():
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    reg.gauge("b").set(1.5)
    reg.histogram("h_seconds").record_many([0.1, 0.2])
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) == []
    # the snapshot round-trips through JSON unchanged
    assert json.loads(json.dumps(snap)) == snap
    # and the validator actually rejects malformed artifacts
    assert validate_metrics_snapshot({"counters": {"x": -1},
                                      "gauges": {}, "histograms": {}})
    assert validate_metrics_snapshot([]) != []


# --------------------------------------------------------------------------
# Tracer: determinism, schema, span trees
# --------------------------------------------------------------------------

def _drive(tracer):
    with tracer.span("outer", tid=0, k=1):
        with tracer.span("inner", tid=0):
            tracer.instant("tick", cat="error", tid=0, request_id=3)
    h = tracer.begin("solo", "serve", 1)
    tracer.end(h, outcome="ok")


def test_trace_deterministic_under_fake_clock():
    """Same fake clock, same calls -> byte-identical event lists, so
    span trees are assertable exactly."""
    t1, t2 = Tracer(FakeClock()), Tracer(FakeClock())
    _drive(t1)
    _drive(t2)
    assert t1.events == t2.events
    assert validate_trace(t1.to_json()) == []
    tree = span_tree(t1.to_json())
    roots = [e["name"] for e in tree[None]]
    assert roots == ["outer", "solo"]
    outer_id = next(e["args"]["span_id"] for e in tree[None]
                    if e["name"] == "outer")
    assert [e["name"] for e in tree[outer_id]] == ["inner"]


def test_trace_event_schema_fields():
    t = Tracer(FakeClock(), pid=7)
    _drive(t)
    t.metadata(0, "engine")
    trace = t.to_json()
    assert validate_trace(trace) == []
    for ev in trace["traceEvents"]:
        for k in ("name", "cat", "ph", "ts", "pid", "tid"):
            assert k in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {7}
    # ts/dur are microseconds: the fake clock steps 1ms = 1000us
    inner = next(e for e in xs if e["name"] == "inner")
    assert inner["dur"] == pytest.approx(2000.0)     # instant consumed 1 tick
    # crash-path tagging: the ctx manager records the exception
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    ev = t.events[-1]
    assert ev["name"] == "boom" and "RuntimeError" in ev["args"]["error"]


def test_trace_end_closes_dangling_children_and_discard():
    t = Tracer(FakeClock())
    outer = t.begin("outer")
    t.begin("child")                 # never explicitly ended
    t.end(outer)                     # must close the child first
    names = [e["name"] for e in t.events]
    assert names == ["child", "outer"]
    assert validate_trace(t.to_json()) == []
    t2 = Tracer(FakeClock())
    t2.end(t2.begin("idle"), discard=True)
    assert t2.events == []


def test_span_ids_and_parents_stay_per_thread_under_contention():
    """Two threads record into one Tracer at once, on the same track:
    ids never repeat, and each thread's inner span keeps its own outer
    span as parent (open-span stacks are per thread)."""
    import sys
    import threading
    t = Tracer(FakeClock(step=1e-6))
    n = 400

    def nest(tag):
        for _ in range(n):
            outer = t.begin(f"outer-{tag}", tid=0)
            t.end(t.begin(f"inner-{tag}", tid=0))
            t.add_span(f"wire-{tag}", "transport", 4, 0.0, 1e-6)
            t.end(outer)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=nest, args=(tag,))
                   for tag in "ab"]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    trace = t.to_json()
    assert validate_trace(trace) == []
    assert len(trace["traceEvents"]) == 2 * 3 * n
    by_id = {e["args"]["span_id"]: e for e in trace["traceEvents"]}
    for e in trace["traceEvents"]:
        if e["name"].startswith("inner-"):
            parent = by_id[e["args"]["parent_id"]]
            assert parent["name"] == "outer-" + e["name"][-1]


def test_inline_spans_mirror_into_the_profiler(vgg_params, tmp_path):
    """A reference-mode engine traced by ``jax.profiler`` on the CPU:
    the Tracer's inline stage spans come back as annotations on a
    ``/host:`` plane (the device ops' clock); request lifetimes do not."""
    import glob
    from jax.profiler import ProfileData
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG,
                       policy="reference", buckets=(2,),
                       tracer=Tracer(FakeClock()))
    eng.warmup()
    rng = np.random.default_rng(4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            eng.submit(rng.standard_normal((1, 3, IMG, IMG))
                       .astype(np.float32))
        eng.step()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(path[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"submit", "form", "stage", "dispatch", "readback",
            "complete"} <= host
    assert not [n for n in host if n.startswith("request-")]


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("anything"):
        NULL_TRACER.instant("x")
    NULL_TRACER.end(NULL_TRACER.begin("y"))
    assert NULL_TRACER.to_json()["traceEvents"] == []
    with pytest.raises(RuntimeError):
        NULL_TRACER.save("/tmp/never.json")
    assert isinstance(NULL_TRACER, NullTracer)


def test_validate_trace_rejects_bad_events():
    bad = {"traceEvents": [
        {"name": "a", "cat": "c", "ph": "X", "ts": 1.0, "pid": 0,
         "tid": 0},                               # X without dur
        {"cat": "c", "ph": "i", "ts": -1, "pid": 0, "tid": 0},
        {"name": "b", "cat": "c", "ph": "X", "ts": 0, "dur": 1,
         "pid": 0, "tid": 0, "args": {"parent_id": 99}},
    ]}
    probs = "\n".join(validate_trace(bad))
    assert "missing 'dur'" in probs
    assert "missing 'name'" in probs
    assert "not a non-negative number" in probs
    assert "parent_id 99" in probs


# --------------------------------------------------------------------------
# Serving integration: lifecycle spans + bounded metrics end to end
# --------------------------------------------------------------------------

def test_serving_trace_zero_loss_and_metrics(vgg_params, tmp_path):
    """One engine run with the tracer and registry on: every submitted
    request appears as a lifetime span with a terminal outcome, the
    trace and metrics artifacts validate."""
    from repro.models import vgg
    from repro.obs.trace import check_trace_outcomes
    from repro.serve.vision import VisionEngine
    clock = FakeClock(step=0.0005)
    tracer = Tracer(clock)
    reg = MetricsRegistry()
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG,
                       policy="reference", buckets=(1, 2, 4),
                       tracer=tracer, registry=reg)
    rng = np.random.default_rng(2)
    sizes = (2, 1, 4, 1, 3)
    reqs = [eng.submit(rng.standard_normal((n, 3, IMG, IMG))
                       .astype(np.float32)) for n in sizes]
    eng.run()
    assert all(r.done for r in reqs)
    trace = tracer.to_json()
    assert validate_trace(trace) == []
    assert check_trace_outcomes(trace, expect_requests=len(sizes)) == []
    names = {e["name"] for e in trace["traceEvents"]}
    for stage in ("submit", "admit", "form", "stage", "dispatch",
                  "kernel", "readback", "epilogue", "complete"):
        assert stage in names, f"lifecycle stage {stage!r} missing"
    # measured stage spans only: no modelled per-layer children
    assert not [e for e in trace["traceEvents"] if e.get("cat") == "layer"]
    kernels = [e for e in trace["traceEvents"] if e["name"] == "kernel"]
    assert all(0 <= e["args"]["cpu_start_s"] <= e["args"]["cpu_end_s"]
               for e in kernels)
    # a direct caller hands over at submit: no inbox wait, then the queue
    lives = [e["args"] for e in trace["traceEvents"]
             if e.get("cat") == "request"]
    assert all(a["inbox_ms"] == 0 and a["queued_ms"] >= 0 for a in lives)
    # registry snapshot: bounded histograms in, schema-valid out
    eng.snapshot_registry(reg)
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) == []
    assert snap["counters"]['serve_requests_total{outcome="ok"}'] \
        == len(sizes)
    assert snap["histograms"]["serve_latency_seconds"]["count"] \
        == len(sizes)
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    assert validate_trace(json.loads(path.read_text())) == []


def test_serving_metrics_bounded_after_many_completions():
    """Satellite (a): ServingMetrics no longer grows per completion —
    100k recorded latencies/occupancies leave the footprint constant
    while the JSON keys (and rounding) survive."""
    from repro.serve.vision import ServingMetrics
    m = ServingMetrics()
    before = m.latency_hist.nbytes + m.occupancy_hist.nbytes
    rng = np.random.default_rng(3)
    m.latency_hist.record_many(rng.lognormal(-2.5, 0.8, 100_000))
    m.occupancy_hist.record_many(rng.uniform(0.25, 1.0, 100_000))
    assert m.latency_hist.count == 100_000
    assert m.latency_hist.nbytes + m.occupancy_hist.nbytes == before
    pct = m.latency_percentiles()
    assert set(pct) == {"p50_s", "p95_s", "p99_s", "mean_s"}
    for k, v in pct.items():
        assert v == round(v, 6), f"{k} not rounded to 6 places"
    assert 0.0 < m.slot_occupancy <= 1.0


def test_no_op_instrumentation_overhead(vgg_params):
    """The default NullTracer path must not measurably slow serving:
    same tiny workload with and without instrumentation enabled."""
    import time as _time
    from repro.models import vgg
    from repro.serve.vision import VisionEngine

    def run(tracer):
        eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG,
                           policy="reference", buckets=(1, 2),
                           tracer=tracer)
        rng = np.random.default_rng(5)
        for n in (1, 2, 1, 2):
            eng.submit(rng.standard_normal((n, 3, IMG, IMG))
                       .astype(np.float32))
        t0 = _time.perf_counter()
        eng.run()
        return _time.perf_counter() - t0

    run(None)                    # warm compile caches out of the timing
    base = min(run(None) for _ in range(3))
    traced = min(run(Tracer(FakeClock())) for _ in range(3))
    # generous bound: the claim is "near-zero", the gate is "not 2x" —
    # a tight % bound would be flaky on shared CI runners
    assert traced < base * 2.0 + 0.05
