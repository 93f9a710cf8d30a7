"""The double-buffered ``EngineWorker`` step (DESIGN.md §13): while batch
k computes, the worker forms, stages and dispatches batch k+1 once the
queue holds a widest bucket of images, and completes k after.

A stand-in wraps the engine's compiled forwards so that chosen
dispatches are read back only when the test releases them — a batch
is held "on the device" deterministically, on the CPU.

* two widest buckets queued: batch k+1's ``dispatch`` starts before
  batch k's ``readback`` ends, completion stays FIFO, at most one batch
  waits behind the one computing, and ``overlapped_batches`` counts it;
* fewer than a widest bucket queued behind a computing batch: nothing
  is formed, and batches and occupancy equal the synchronous ``step()``
  loop's on the same arrivals;
* with nothing computing, batch k is answered before k+1 forms;
* admission counts the rest of a computing batch's forward;
* a fault on batch k while k+1 is in flight degrades only k's
  requests; ``stop`` completes a dispatched batch; the service times
  given to admission and the watchdog, and the ``kernel`` spans, run
  from when the device could start a batch, not from its dispatch.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.engine import ScheduleCache
from repro.models.zoo import get_conv_model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TID_READBACK, Tracer
from repro.serve.chaos import ChaosInjector, Fault
from repro.serve.transport import EngineWorker
from repro.serve.vision import VisionEngine

IMG = 32
WIDE = 4            # the one bucket: a widest bucket is 4 images
HOLD_S = 0.3


@pytest.fixture(scope="module")
def model():
    spec = get_conv_model("vgg16")
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                              img=IMG, classes=10)
    return params, spec.to_graph(), ScheduleCache()


def make_engine(model, **kw):
    params, graph, cache = model
    eng = VisionEngine(params, graph, img=IMG, policy="reference",
                       buckets=(WIDE,), cache=cache, **kw)
    eng.warmup()
    return eng


def images(n, seed):
    return np.random.default_rng(seed).standard_normal(
        (n, 3, IMG, IMG)).astype(np.float32)


class _Held:
    """A forward's output whose readback waits for ``release``."""

    def __init__(self, out, release: threading.Event):
        self.out, self.release = out, release
        self.shape = out.shape

    def __array__(self, dtype=None, copy=None):
        self.release.wait(30.0)
        return np.asarray(self.out, dtype)


class Holds:
    """Wraps ``engine``'s compiled forwards; the outputs of the calls
    numbered in ``held`` (0-based, every forward counts) are read back
    only once ``release[i]`` is set."""

    def __init__(self, engine, held=()):
        self.calls = 0
        self.release = {i: threading.Event() for i in held}
        real = engine.compiler.network_for
        holds = self

        class Net:
            def __init__(self, net):
                self.net = net
                self.layer_schedules = net.layer_schedules

            def __call__(self, params, x):
                i = holds.calls
                holds.calls += 1
                out = self.net(params, x)
                ev = holds.release.get(i)
                return out if ev is None else _Held(out, ev)

        engine.compiler.network_for = lambda bucket: Net(real(bucket))

    def wait_calls(self, n, timeout=30.0):
        deadline = time.monotonic() + timeout
        while self.calls < n and time.monotonic() < deadline:
            time.sleep(0.002)
        assert self.calls >= n, f"{self.calls} forwards, waited for {n}"


def gated_submit(worker, sizes):
    """Submit every request before the worker looks at its inbox."""
    gate = threading.Event()
    worker.gate = gate
    futs = [worker.submit(images(n, seed=i)) for i, n in enumerate(sizes)]
    worker.gate = None
    gate.set()
    return futs


def results(futs):
    return [f.result(timeout=60.0) for f in futs]


def lost(worker):
    return worker.call(lambda e: e.metrics_dict()["robustness"]
                       ["lost_requests"]).result(timeout=60.0)


def test_full_buckets_overlap_and_complete_fifo(model):
    tracer = Tracer(time.monotonic)
    eng = make_engine(model, tracer=tracer)
    holds = Holds(eng, held=[0, 1])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        futs = gated_submit(w, [WIDE, WIDE, WIDE])
        holds.wait_calls(2)
        time.sleep(0.1)
        # batch 1 waits behind batch 0; batch 2 is not formed yet
        assert holds.calls == 2
        assert not any(f.done() for f in futs)
        holds.release[0].set()
        # batch 0 done: batch 2 goes behind batch 1, still computing
        holds.wait_calls(3)
        holds.release[1].set()
        reqs = results(futs)
        assert lost(w) == 0
    finally:
        w.stop()
    assert not w.alive
    assert holds.calls == 3
    assert [r.outcome.value for r in reqs] == ["ok"] * 3
    assert [r.served_by for r in reqs] == ["primary"] * 3
    t_done = [r.t_done for r in reqs]
    assert t_done == sorted(t_done)                     # FIFO completion
    m = eng.metrics
    assert (m.batches, m.overlapped_batches) == (3, 2)
    assert eng.metrics_dict()["overlapped_batches"] == 2
    assert m.elapsed_s >= 0.1 and m.kips > 0       # one busy period
    reg = eng.snapshot_registry(MetricsRegistry())
    assert "engine_overlapped_batches_total 2" in reg.to_prometheus()

    trace = tracer.to_json()["traceEvents"]

    def spans(name, **kw):
        return sorted((e for e in trace if e["ph"] == "X"
                       and e["name"] == name
                       and all(e.get(k) == v for k, v in kw.items())),
                      key=lambda e: e["ts"])
    disp, reads = spans("dispatch"), spans("readback", tid=TID_READBACK)
    assert [d["args"]["overlapped"] for d in disp] == [False, True, True]
    # batch 1 went to the device while batch 0 was still being read back
    assert disp[1]["ts"] < reads[0]["ts"] + reads[0]["dur"]
    kern = spans("kernel")
    assert len(kern) == 3
    for a, b in zip(kern, kern[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-3     # never overlap
    # stamps paired with the span's start: the off-CPU gap is >= 0
    for a, b in zip(kern, kern[1:]):
        wall = (b["ts"] - a["ts"] - a["dur"]) * 1e-6
        cpu = b["args"]["cpu_start_s"] - a["args"]["cpu_end_s"]
        assert wall - cpu >= -1e-5


def test_service_time_runs_from_when_the_device_could_start(model):
    eng = make_engine(model)
    seen = {"admission": [], "watchdog": []}
    adm, dog = eng.admission.observe, eng.watchdog.observe
    eng.admission.observe = lambda b, s: (
        seen["admission"].append(s), adm(b, s))[1]
    eng.watchdog.observe = lambda b, s: (
        seen["watchdog"].append(s), dog(b, s))[1]
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        futs = gated_submit(w, [WIDE, WIDE])
        holds.wait_calls(2)
        time.sleep(HOLD_S)
        holds.release[0].set()
        results(futs)
    finally:
        w.stop()
    assert eng.metrics.overlapped_batches == 1
    for durations in seen.values():
        first, second = durations
        assert first >= HOLD_S
        # batch 1 waited HOLD_S behind batch 0 and is not charged for it
        assert second < HOLD_S / 2


def test_partial_queue_behind_a_computing_batch_forms_nothing(model):
    sizes = [WIDE, 1, 2]                # 3 < WIDE images behind batch 0
    eng = make_engine(model)
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        futs = [w.submit(images(sizes[0], seed=0))]
        holds.wait_calls(1)
        futs += [w.submit(images(n, seed=i))
                 for i, n in enumerate(sizes[1:], 1)]
        deadline = time.monotonic() + 30.0
        while eng.metrics.submitted < 3 and time.monotonic() < deadline:
            time.sleep(0.002)
        time.sleep(0.1)
        assert eng.metrics.submitted == 3 and holds.calls == 1
        holds.release[0].set()
        results(futs)
    finally:
        w.stop()
    # the synchronous loop on the same arrivals
    ref = make_engine(model)
    ref.submit(images(sizes[0], seed=0))
    ref.step()
    for i, n in enumerate(sizes[1:], 1):
        ref.submit(images(n, seed=i))
    ref.step()
    got, want = eng.metrics, ref.metrics
    assert got.batches == want.batches == 2
    assert got.per_bucket == want.per_bucket
    assert got.occupancy_hist.count == want.occupancy_hist.count
    assert got.occupancy_hist.mean == pytest.approx(
        want.occupancy_hist.mean)
    assert got.overlapped_batches == want.overlapped_batches == 0


def test_with_nothing_computing_k_is_answered_before_the_next_forms(model):
    """The device idle, a synchronous step's order: batch k completes,
    its callers are answered and the inbox drained before k+1 forms."""
    tracer = Tracer(time.monotonic)
    eng = make_engine(model, tracer=tracer)
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        futs = [w.submit(images(WIDE, seed=0))]
        holds.wait_calls(1)
        futs.append(w.submit(images(1, seed=1)))
        deadline = time.monotonic() + 30.0
        while eng.metrics.submitted < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        holds.release[0].set()
        results(futs)
    finally:
        w.stop()
    assert (eng.metrics.batches, eng.metrics.overlapped_batches) == (2, 0)
    trace = tracer.to_json()["traceEvents"]
    forms = sorted((e for e in trace if e["ph"] == "X"
                    and e["name"] == "form"), key=lambda e: e["ts"])
    first = min((e for e in trace if e["ph"] == "X"
                 and e["name"] == "resolve"), key=lambda e: e["ts"])
    assert len(forms) == 2
    assert first["ts"] + first["dur"] <= forms[1]["ts"] + 1e-3


def test_admission_counts_the_batch_on_the_device(model):
    """A request arriving while a batch computes waits for the rest of
    that forward too: a deadline shorter than its own forward plus the
    held one is shed."""
    eng = make_engine(model)
    eng.admission.observe(WIDE, 1.0)        # the EWMA: 1 s a forward
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        first = w.submit(images(WIDE, seed=0))
        holds.wait_calls(1)
        late = w.submit(images(1, seed=1), deadline_s=1.5).result(60.0)
        holds.release[0].set()
        assert first.result(timeout=60.0).outcome.value == "ok"
    finally:
        w.stop()
    assert late.outcome.value == "rejected"
    # its own forward (1 s) alone fits the deadline; the held one's rest
    # (most of 1 s) does not
    assert late.predicted_wait_s > 1.5
    assert eng.metrics.shed == 1 and holds.calls == 1


@pytest.mark.parametrize("kind", ["nan", "kernel"])
def test_fault_on_batch_k_degrades_only_k(model, kind):
    eng = make_engine(model, chaos=ChaosInjector({0: Fault(kind)}))
    # batch 1's forward is held; a kernel fault raises before batch 0's
    # forward runs, so batch 1's is then the first
    held = 1 if kind == "nan" else 0
    holds = Holds(eng, held=[held])
    w = EngineWorker("w", eng).start(warmup=False)
    try:
        futs = gated_submit(w, [WIDE, WIDE])
        first = futs[0].result(timeout=60.0)
        # batch 0 went down the ladder while batch 1 was still in flight
        assert not futs[1].done()
        holds.release[held].set()
        second = futs[1].result(timeout=60.0)
        assert lost(w) == 0
    finally:
        w.stop()
    assert (first.outcome.value, first.served_by) == ("ok", "reference")
    assert (second.outcome.value, second.served_by) == ("ok", "primary")
    m = eng.metrics
    assert (m.batches, m.degraded_batches) == (2, 1)
    assert m.nonfinite_batches == (kind == "nan")


def test_stop_with_drain_completes_the_inflight_batch(model):
    eng = make_engine(model)
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    fut = w.submit(images(2, seed=0))
    holds.wait_calls(1)
    stopper = threading.Thread(target=w.stop, kwargs={"drain": True})
    stopper.start()
    time.sleep(0.1)
    assert w.alive and not fut.done()
    holds.release[0].set()
    stopper.join(60.0)
    assert not stopper.is_alive() and not w.alive
    assert fut.result(timeout=1.0).outcome.value == "ok"
    assert eng.metrics_dict()["robustness"]["lost_requests"] == 0


def test_stop_under_a_closed_gate_completes_the_dispatched_batch(model):
    eng = make_engine(model)
    holds = Holds(eng, held=[0])
    w = EngineWorker("w", eng).start(warmup=False)
    fut = w.submit(images(2, seed=0))
    holds.wait_calls(1)
    w.gate = threading.Event()          # the loop idles from here on
    stopper = threading.Thread(target=w.stop, kwargs={"drain": False})
    stopper.start()
    time.sleep(0.1)
    holds.release[0].set()
    stopper.join(60.0)
    assert not stopper.is_alive() and not w.alive
    assert fut.result(timeout=1.0).outcome.value == "ok"
    assert eng.metrics_dict()["robustness"]["lost_requests"] == 0


def test_run_feeder_counts_overlap_and_step_does_not(model):
    eng = make_engine(model)
    for i in range(3):
        eng.submit(images(WIDE, seed=i))
    eng.run()
    assert (eng.metrics.batches, eng.metrics.overlapped_batches) == (3, 2)
    for i in range(2):
        eng.submit(images(WIDE, seed=i))
    while eng.step():
        pass
    assert (eng.metrics.batches, eng.metrics.overlapped_batches) == (5, 2)


def test_offcpu_gap_is_not_negative_when_completion_lags_dispatch(model):
    """Batch 0 is read back on another thread, batch 1 is dispatched
    after that, and the engine thread then sleeps (off the CPU) before
    it completes batch 0: the stamps still give the gap between the two
    ``kernel`` spans an off-CPU share in [0, wall gap]."""
    tracer = Tracer(time.monotonic)
    eng = make_engine(model, tracer=tracer)
    holds = Holds(eng, held=[0])
    for i in range(2):
        eng.submit(images(WIDE, seed=i))
    first = eng.launch()
    reader = threading.Thread(target=eng.readback, args=(first,))
    reader.start()
    holds.release[0].set()
    reader.join(30.0)
    assert not reader.is_alive() and first.t_done is not None
    second = eng.launch()
    time.sleep(0.05)
    assert eng.complete() is first
    assert eng.complete() is second
    assert not second.overlapped
    a, b = sorted((e for e in tracer.to_json()["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "kernel"),
                  key=lambda e: e["ts"])
    wall = (b["ts"] - a["ts"] - a["dur"]) * 1e-6
    cpu = b["args"]["cpu_start_s"] - a["args"]["cpu_end_s"]
    assert wall > 0
    assert -1e-5 <= wall - cpu <= wall + 1e-5
