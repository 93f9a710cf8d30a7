"""Wire-level tests for the HTTP serving front-end (DESIGN.md §13).

One module-scoped server — two in-process reference-policy workers over
a shared ScheduleCache — backs every wire test; the router/scoring
tests run against fake workers with no engine at all.  The invariants
under test are the transport versions of the serving contracts:

* the status map IS the outcome map (400/429/504/500/200), and a
  malformed body is refused before anything touches an engine;
* logits served over the wire are bitwise the engine's logits — the
  JSON hop (float32 -> float64 repr -> float32) loses nothing;
* SIGTERM is a drain, not a drop: accepted work completes, new work
  gets 503, and the zero-loss ledger stays balanced through shutdown;
* failover reroutes only on transport errors, and quarantine heals
  through the healthz probe.
"""
import asyncio
import base64
import json
import threading
import time

import numpy as np
import pytest

from repro.launch.server import start_server
from repro.serve.admission import BadRequestError
from repro.serve.router import (NoWorkersAvailable, Router,
                                WorkerUnavailable)
from repro.serve.transport import (InferResult, decode_infer_body,
                                   encode_images_payload, http_json)

IMG = 32
BUCKETS = (1, 2, 4)


class FakeGuard:
    requested = False


@pytest.fixture(scope="module")
def served():
    guard = FakeGuard()
    handle = start_server("vgg16", n_workers=2, policy="reference",
                          img=IMG, width_mult=0.0625, buckets=BUCKETS,
                          guard=guard)
    handle.test_guard = guard
    yield handle
    handle.stop()


def http(handle, method, path, payload=None, headers=None):
    return asyncio.run(http_json(handle.host, handle.port, method, path,
                                 payload, headers))


def images(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, 3, IMG, IMG)).astype(np.float32)


def engines(handle):
    return [w.worker.engine for w in handle.workers]


def submitted_total(handle):
    return sum(e.metrics.submitted for e in engines(handle))


# ---------------------------------------------------------------------------
# payload codec
# ---------------------------------------------------------------------------

def test_b64_payload_roundtrips_bitwise():
    x = images(3, seed=7)
    arr, deadline = decode_infer_body(
        json.dumps(encode_images_payload(x, 2.5)).encode())
    assert deadline == 2.5
    assert arr.dtype == np.float32
    np.testing.assert_array_equal(arr, x)


@pytest.mark.parametrize("body", [
    b"{not json",                                   # malformed JSON
    b"[1, 2, 3]",                                   # not an object
    b'{"deadline_s": "soon", "images": [1]}',       # non-numeric deadline
    b'{"shape": [1], "data_b64": "!!!"}',           # undecodable base64
    b'{"images": [["a"]]}',                         # non-numeric images
    b'{"nothing": 1}',                              # no payload at all
])
def test_decode_rejects_malformed_bodies(body):
    with pytest.raises(BadRequestError):
        decode_infer_body(body)


# ---------------------------------------------------------------------------
# the wire contract
# ---------------------------------------------------------------------------

def test_served_logits_bitwise_equal_direct_engine(served):
    """The tentpole invariant: HTTP serving is the engine, observed
    through a lossless wire — logits match a direct ``VisionEngine``
    submission bit for bit."""
    x = images(2, seed=3)
    status, obj = http(served, "POST", "/v1/infer",
                       encode_images_payload(x))
    assert status == 200 and obj["outcome"] == "ok"
    assert obj["served_by"] == "primary"
    wire = np.asarray(obj["logits"], np.float32)
    # direct submission to the very worker that served the wire request
    worker = {w.name: w for w in served.workers}[obj["worker"]].worker
    direct = worker.submit(x).result(60.0)
    assert direct.outcome.value == "ok"
    np.testing.assert_array_equal(wire, direct.logits)


def test_nested_list_images_accepted(served):
    x = images(1, seed=4)
    status, obj = http(served, "POST", "/v1/infer",
                       {"images": x.tolist()})
    assert status == 200 and obj["outcome"] == "ok"
    assert np.asarray(obj["logits"], np.float32).shape == (1, 10)


def test_malformed_body_400_without_engine_submit(served):
    before = submitted_total(served)
    status, obj = http(served, "POST", "/v1/infer", None)  # empty body
    assert status == 400 and obj["outcome"] == "bad_request"

    async def raw_garbage():
        reader, writer = await asyncio.open_connection(served.host,
                                                       served.port)
        body = b"{definitely not json"
        writer.write(b"POST /v1/infer HTTP/1.1\r\n"
                     b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        await writer.drain()
        line = await reader.readline()
        writer.close()
        return int(line.split()[1])

    assert asyncio.run(raw_garbage()) == 400
    # a garbage body never became a request: no engine saw a submit
    assert submitted_total(served) == before


def test_oversized_payload_413_before_body_read(served):
    """A huge declared Content-Length is answered from the headers
    alone — the server never reads (or allocates for) the body."""

    async def oversized():
        reader, writer = await asyncio.open_connection(served.host,
                                                       served.port)
        writer.write(b"POST /v1/infer HTTP/1.1\r\n"
                     b"Content-Length: 999999999\r\n\r\n")
        await writer.drain()
        line = await reader.readline()
        writer.close()
        return int(line.split()[1])

    before = submitted_total(served)
    assert asyncio.run(oversized()) == 413
    assert submitted_total(served) == before


def test_deadline_header_propagates_to_engine_submit(served):
    """``X-Deadline-S`` reaches ``engine.submit(deadline_s=...)`` and
    wins over the body's ``deadline_s``."""
    seen = []
    originals = [(e, e.submit) for e in engines(served)]
    for eng, orig in originals:
        def recorder(images, deadline_s=None, _orig=orig, **kw):
            seen.append(deadline_s)
            return _orig(images, deadline_s=deadline_s, **kw)
        eng.submit = recorder
    try:
        payload = encode_images_payload(images(1, seed=5), deadline_s=1.0)
        status, obj = http(served, "POST", "/v1/infer", payload,
                           headers={"X-Deadline-S": "30.0"})
    finally:
        for eng, orig in originals:
            eng.submit = orig
    assert status == 200 and obj["outcome"] == "ok"
    assert seen == [30.0]

    status, obj = http(served, "POST", "/v1/infer",
                       encode_images_payload(images(1, seed=5)),
                       headers={"X-Deadline-S": "not-a-number"})
    assert status == 400 and obj["outcome"] == "bad_request"


def test_sigterm_drain_completes_inflight_refuses_new(served):
    """The preemption discipline over the wire: once the guard trips,
    new requests get 503 and healthz reports draining, while a request
    accepted *before* the trip still completes 200."""
    gates = []
    for w in served.workers:
        gate = threading.Event()        # unset: the worker loop idles
        w.worker.gate = gate
        gates.append(gate)
    results = []
    t = threading.Thread(target=lambda: results.append(
        http(served, "POST", "/v1/infer",
             encode_images_payload(images(1, seed=6)))))
    try:
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                sum(w.worker.inflight for w in served.workers) == 0:
            time.sleep(0.005)
        assert sum(w.worker.inflight for w in served.workers) == 1
        served.test_guard.requested = True
        status, obj = http(served, "POST", "/v1/infer",
                           encode_images_payload(images(1, seed=6)))
        assert status == 503 and obj["outcome"] == "draining"
        status, obj = http(served, "GET", "/healthz")
        assert status == 503 and obj["status"] == "draining"
    finally:
        for gate in gates:
            gate.set()                  # release the drain
        t.join(60.0)
        served.test_guard.requested = False
        for w in served.workers:
            w.worker.gate = None
    assert not t.is_alive()
    status, obj = results[0]
    assert status == 200 and obj["outcome"] == "ok"


def test_metrics_and_stats_endpoints(served):
    status, text = http(served, "GET", "/metrics")
    assert status == 200
    assert "transport_requests_total" in text
    assert 'worker="w0"' in text        # per-worker engine series

    from repro.obs.metrics import validate_metrics_snapshot
    status, snap = http(served, "GET", "/metrics.json")
    assert status == 200 and validate_metrics_snapshot(snap) == []

    status, stats = http(served, "GET", "/stats")
    assert status == 200
    assert stats["totals"]["lost_requests"] == 0
    assert set(stats["workers"]) == {"w0", "w1"}
    assert status == 200


def test_unknown_route_404_and_method_405(served):
    assert http(served, "GET", "/nope")[0] == 404
    assert http(served, "GET", "/v1/infer")[0] == 405


# ---------------------------------------------------------------------------
# router: dispatch, failover, quarantine
# ---------------------------------------------------------------------------

class FakeWorker:
    remote = False

    def __init__(self, name, fail=False, healthy_after=False,
                 service_s=0.0):
        self.name = name
        self.fail = fail
        self.healthy_after = healthy_after
        self.service_s = service_s
        self.inflight = 0
        self.served = 0

    async def infer(self, images, deadline_s):
        if self.fail:
            raise WorkerUnavailable(f"{self.name} is down")
        self.served += 1
        return InferResult(outcome="ok", status=200,
                           logits=np.zeros((1, 10), np.float32),
                           worker=self.name)

    async def stats(self):
        return {"robustness": {"lost_requests": 0}}

    async def sync_registry(self, registry):
        pass

    async def healthy(self):
        return self.healthy_after


def test_router_failover_on_transport_error_only():
    bad = FakeWorker("bad", fail=True)
    good = FakeWorker("good")
    router = Router([bad, good], BUCKETS, quarantine_after=2)
    for b in BUCKETS:                   # make the dead worker the pick
        router._note_success("good", b, 1.0)
    res = asyncio.run(router.infer(np.zeros((1, 3, IMG, IMG),
                                            np.float32)))
    assert res.worker == "good" and res.status == 200
    assert router._failures["bad"] == 1 and not router.quarantined()
    assert router._failovers == 1


def test_router_quarantine_and_probe_revival():
    bad = FakeWorker("bad", fail=True, healthy_after=True)
    good = FakeWorker("good")
    router = Router([bad, good], BUCKETS, quarantine_after=2)
    x = np.zeros((1, 3, IMG, IMG), np.float32)
    for _ in range(4):
        assert asyncio.run(router.infer(x)).worker == "good"
    # two consecutive transport failures benched the bad worker: it no
    # longer even gets picked (failures stop accumulating)
    assert router.quarantined() == ["bad"]
    fails_frozen = router._failures["bad"]
    asyncio.run(router.infer(x))
    assert router._failures["bad"] == fails_frozen
    # a passing healthz probe un-benches it
    bad.fail = False
    assert asyncio.run(router.probe()) == ["bad"]
    assert router.quarantined() == []


def test_router_all_down_raises_no_workers():
    bad = FakeWorker("bad", fail=True)
    router = Router([bad], BUCKETS, quarantine_after=1)
    x = np.zeros((1, 3, IMG, IMG), np.float32)
    with pytest.raises(NoWorkersAvailable):
        asyncio.run(router.infer(x))
    with pytest.raises(NoWorkersAvailable):
        asyncio.run(router.infer(x))    # quarantined: refused immediately


def test_router_pick_prefers_fast_idle_worker():
    slow = FakeWorker("slow")
    fast = FakeWorker("fast")
    router = Router([slow, fast], BUCKETS)
    for bucket in BUCKETS:
        router._note_success("slow", bucket, 0.1)
        router._note_success("fast", bucket, 0.01)
    assert router._pick(1, frozenset()).name == "fast"
    # queue depth overrides raw speed once the fast worker backs up:
    # 64 queued images = 16 widest-bucket batches ahead of us, so the
    # predicted wait (16 * 0.01 + 0.01) now exceeds slow's idle 0.1
    fast.inflight = 64
    assert router._pick(1, frozenset()).name == "slow"


def test_router_failed_outcome_does_not_failover():
    """An engine-level ``failed`` outcome is terminal — rerouting it
    would double-serve a poison request through another replica."""

    class FailedOutcomeWorker(FakeWorker):
        async def infer(self, images, deadline_s):
            self.served += 1
            return InferResult(outcome="failed", status=500,
                               error="quarantined by the ladder",
                               worker=self.name)

    poison = FailedOutcomeWorker("poison")
    spare = FakeWorker("spare")
    router = Router([poison, spare], BUCKETS)
    for b in BUCKETS:                   # make poison the pick
        router._note_success("spare", b, 1.0)
    res = asyncio.run(router.infer(np.zeros((1, 3, IMG, IMG),
                                            np.float32)))
    assert res.status == 500 and res.worker == "poison"
    assert spare.served == 0 and router._failovers == 0


# ---------------------------------------------------------------------------
# load generator + perf gate
# ---------------------------------------------------------------------------

def test_load_generator_smoke_against_live_server(served, tmp_path):
    from benchmarks.run_async_requests import main
    bench = tmp_path / "BENCH_test.json"
    metrics = tmp_path / "metrics_scrape.json"
    rc = main(["--host", served.host, "--port", str(served.port),
               "--requests", "8", "--concurrency", "4",
               "--buckets", ",".join(str(b) for b in BUCKETS),
               "--bench-json", str(bench),
               "--metrics-out", str(metrics)])
    assert rc == 0
    tr = json.loads(bench.read_text())["transport"]
    assert tr["requests"] == 8 and tr["ok"] == 8
    assert tr["lost_requests"] == 0 and tr["kips"] > 0
    from repro.obs.metrics import validate_metrics_snapshot
    assert validate_metrics_snapshot(json.loads(metrics.read_text())) == []


def test_check_bench_transport_scope(tmp_path):
    from benchmarks.check_bench import compare, extract, scope_filter
    bench = {"transport": {"lost_requests": 0, "kips": 1.0,
                           "shed_rate": 0.05}}
    fresh = extract(bench)
    assert fresh["exact"]["transport.lost_requests"] == 0
    assert fresh["throughput"]["transport.kips"] == 1.0
    assert fresh["transport"]["transport.shed_rate"] == 0.05
    # scope core drops every transport.* metric; scope transport keeps
    # nothing else
    assert scope_filter(fresh, "core")["exact"] == {}
    assert scope_filter(fresh, "transport") == fresh
    # shed_rate gates as a ceiling: shedding less than baseline passes,
    # more fails; a lost request fails exactly
    base = {"exact": {"transport.lost_requests": 0},
            "latency": {}, "throughput": {"transport.kips": 1.0},
            "robustness": {}, "observability": {}, "quantization": {},
            "transport": {"transport.shed_rate": 0.1}}
    assert compare(fresh, base, tol=0.2) == []
    worse = extract({"transport": {"lost_requests": 1, "kips": 1.0,
                                   "shed_rate": 0.5}})
    kinds = {(k, m) for k, m, _ in compare(worse, base, tol=0.2)}
    assert ("exact", "transport.lost_requests") in kinds
    assert ("transport", "transport.shed_rate") in kinds


def test_check_bench_scoped_update_preserves_other_scope(tmp_path):
    from benchmarks.check_bench import main as gate_main
    core_bench = tmp_path / "core.json"
    core_bench.write_text(json.dumps({
        "latency": {"auto_per_img_s": 0.01,
                    "pallas_unfused_per_img_s": 0.02,
                    "pallas_fused_per_img_s": 0.015},
        "fold_reuse": {"hits": 5, "misses": 8, "replans": 0,
                       "conv_layers": 13, "distinct_schedules": 8},
        "pallas_calls": 13}))
    tr_bench = tmp_path / "transport.json"
    tr_bench.write_text(json.dumps({
        "transport": {"lost_requests": 0, "kips": 2.0,
                      "shed_rate": 0.0}}))
    baseline = tmp_path / "baseline.json"
    assert gate_main(["--bench", str(core_bench), "--scope", "core",
                      "--baseline", str(baseline), "--update"]) == 0
    assert gate_main(["--bench", str(tr_bench), "--scope", "transport",
                      "--baseline", str(baseline), "--update"]) == 0
    merged = json.loads(baseline.read_text())
    # the transport-scoped update kept the core metrics and vice versa
    assert merged["latency"]["vgg16.latency.auto_per_img_s"] == 0.01
    assert merged["exact"]["transport.lost_requests"] == 0
    assert merged["throughput"]["transport.kips"] == 2.0
    # each job gates only its own scope against the shared baseline
    assert gate_main(["--bench", str(core_bench), "--scope", "core",
                      "--baseline", str(baseline)]) == 0
    assert gate_main(["--bench", str(tr_bench), "--scope", "transport",
                      "--baseline", str(baseline)]) == 0
