"""Fault-tolerant serving runtime (DESIGN.md §10): request lifecycle
state machine, strict bucket validation, typed bad-request rejection,
deadline expiry and SLO-aware admission, the degradation ladder
(reference fallback + quarantine bisection), watchdog hang flagging,
deterministic chaos injection, and the preemption drain."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft.fault_tolerance import PreemptionGuard
from repro.serve.admission import (AdmissionController, BadRequestError,
                                   DispatchWatchdog, RequestOutcome,
                                   validate_images)
from repro.serve.batcher import BucketPolicy, ImageBatcher, ImageRequest
from repro.serve.chaos import (PROFILE_EXPECTATIONS, PROFILES,
                               ChaosInjector, ChaosKernelFault, Fault,
                               chaos_summary)

IMG, WIDTH, CLASSES = 32, 0.0625, 10


@pytest.fixture(scope="module")
def vgg_params():
    from repro.models import vgg
    return vgg.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                           img=IMG, classes=CLASSES)


def _engine(vgg_params, **kw):
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    kw.setdefault("policy", "auto")
    kw.setdefault("buckets", (1, 2, 4))
    return VisionEngine(vgg_params, vgg.to_graph(), img=IMG, **kw)


def _imgs(rng, n):
    return rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)


# --------------------------------------------------------------------------
# satellite: strict BucketPolicy validation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("widths,msg", [
    ((), "at least one width"),
    ((0, 1), "must be >= 1"),
    ((-2, 4), "must be >= 1"),
    ((1, 2, 2, 4), "duplicate"),
    ((4, 2, 1), "ascending"),
])
def test_bucket_policy_rejects_bad_widths(widths, msg):
    with pytest.raises(ValueError, match=msg):
        BucketPolicy(widths)


def test_bucket_policy_aligned_still_dedups():
    # rounding widths up to the mesh data-axis size may collide them; the
    # derived policy dedups/sorts — only *user-supplied* widths are strict
    assert BucketPolicy((1, 2, 4, 6)).aligned(4).widths == (4, 8)
    assert BucketPolicy((1, 2, 4)).aligned(1).widths == (1, 2, 4)


# --------------------------------------------------------------------------
# satellite: typed BadRequestError at submit
# --------------------------------------------------------------------------

def test_submit_rejects_malformed_payloads():
    b = ImageBatcher(BucketPolicy((1, 2)), IMG)
    with pytest.raises(BadRequestError, match="must be"):
        b.submit(np.zeros((1, 3, IMG), np.float32))          # wrong rank
    with pytest.raises(BadRequestError, match="must be"):
        b.submit(np.zeros((1, 1, IMG, IMG), np.float32))     # wrong chans
    with pytest.raises(BadRequestError, match="not castable"):
        b.submit(np.array([["a"]], dtype=object))
    with pytest.raises(BadRequestError, match="zero images"):
        b.submit(np.zeros((0, 3, IMG, IMG), np.float32))
    with pytest.raises(BadRequestError, match="split it client-side"):
        b.submit(np.zeros((3, 3, IMG, IMG), np.float32))
    bad = np.zeros((1, 3, IMG, IMG), np.float32)
    bad[0, 0, 0, 0] = np.nan
    with pytest.raises(BadRequestError, match="non-finite"):
        b.submit(bad)
    assert len(b) == 0                      # nothing slipped into the queue
    # BadRequestError IS a ValueError: pre-existing callers keep working
    assert issubclass(BadRequestError, ValueError)


def test_validate_images_canonicalizes():
    one = validate_images(np.zeros((3, IMG, IMG)), chan=3, img=IMG,
                          max_images=4)
    assert one.shape == (1, 3, IMG, IMG) and one.dtype == np.float32
    lst = validate_images([np.zeros((3, IMG, IMG), np.float64)] * 2,
                          chan=3, img=IMG, max_images=4)
    assert lst.shape == (2, 3, IMG, IMG) and lst.dtype == np.float32


# --------------------------------------------------------------------------
# request lifecycle state machine
# --------------------------------------------------------------------------

def test_finish_is_single_transition():
    req = ImageRequest(rid=0, images=np.zeros((1, 3, IMG, IMG), np.float32))
    assert req.outcome is RequestOutcome.PENDING
    assert req.deadline_met is None
    with pytest.raises(ValueError, match="non-terminal"):
        req.finish(RequestOutcome.PENDING)
    req.finish(RequestOutcome.OK, t=1.0)
    assert req.done and req.outcome is RequestOutcome.OK
    with pytest.raises(ValueError, match="already"):
        req.finish(RequestOutcome.FAILED)


def test_deadline_met_semantics():
    kw = dict(images=np.zeros((1, 3, IMG, IMG), np.float32),
              t_submit=0.0, t_deadline=1.0)
    hit = ImageRequest(rid=0, **kw)
    hit.finish(RequestOutcome.OK, t=0.5)
    assert hit.deadline_met is True
    late = ImageRequest(rid=1, **kw)
    late.finish(RequestOutcome.OK, t=2.0)
    assert late.deadline_met is False
    shed = ImageRequest(rid=2, **kw)
    shed.finish(RequestOutcome.REJECTED, t=0.1)
    assert shed.deadline_met is False       # a shed SLO is a missed SLO
    free = ImageRequest(rid=3, images=kw["images"])
    free.finish(RequestOutcome.OK, t=9.0)
    assert free.deadline_met is None        # no SLO attached


def test_form_expires_past_deadline_requests():
    clk = {"t": 0.0}
    b = ImageBatcher(BucketPolicy((1, 2, 4)), IMG,
                     clock=lambda: clk["t"])
    rng = np.random.default_rng(0)
    r_slo = b.submit(_imgs(rng, 1), deadline_s=5.0)
    r_free = b.submit(_imgs(rng, 1))
    clk["t"] = 6.0                          # past r_slo's deadline
    fb = b.form()
    assert r_slo.outcome is RequestOutcome.EXPIRED
    assert r_slo in b.expired and not r_slo.done
    assert [r.rid for r in fb.requests] == [r_free.rid]  # FIFO, minus it
    assert b.form() is None


# --------------------------------------------------------------------------
# admission controller (unit math, no engine)
# --------------------------------------------------------------------------

def test_admission_cold_start_admits_everything():
    ac = AdmissionController((1, 2, 4))
    ok, predicted = ac.admit(1, pending_images=100, deadline_s=1e-9)
    assert ok and predicted == 0.0          # no evidence -> no shedding


def test_admission_sheds_on_measured_queue_delay():
    ac = AdmissionController((1, 2, 4), alpha=1.0)
    ac.observe(4, 0.1)                      # widest bucket: 0.1 s/batch
    # 8 pending images = 2 full batches ahead + its own 0.1 -> 0.3 s
    assert ac.predicted_wait_s(8, 4) == pytest.approx(0.3)
    ok, _ = ac.admit(4, 8, deadline_s=0.25)
    assert not ok
    ok, _ = ac.admit(4, 8, deadline_s=0.35)
    assert ok
    ok, _ = ac.admit(4, 8, deadline_s=None)  # no SLO: always admitted
    assert ok


def test_admission_counts_the_rest_of_dispatched_batches():
    ac = AdmissionController((1, 2, 4), alpha=1.0)
    ac.observe(4, 0.1)
    ac.observe(2, 0.05)
    # a bucket-4 batch 0.03 s into its forward, a bucket-2 one behind it,
    # 4 images queued, then its own bucket-4 batch
    backlog = [(4, 0.03), (2, 0.0)]
    assert ac.predicted_wait_s(4, 4, backlog) == pytest.approx(
        0.07 + 0.05 + 0.1 + 0.1)
    # a forward past its estimate holds nothing more
    assert ac.predicted_wait_s(0, 4, [(4, 0.5)]) == pytest.approx(0.1)
    ok, _ = ac.admit(4, 0, deadline_s=0.15, backlog=[(4, 0.0)])
    assert not ok
    ok, _ = ac.admit(4, 0, deadline_s=0.15)
    assert ok


def test_admission_estimates_fall_back_to_nearest_bucket():
    ac = AdmissionController((1, 2, 4), alpha=1.0)
    ac.observe(2, 0.05)
    assert ac.estimate_s(1) == pytest.approx(0.05)   # nearest wider
    assert ac.estimate_s(4) == pytest.approx(0.05)   # widest known
    ac.observe(2, 0.15)                              # EWMA moves
    assert ac.estimate_s(2) == pytest.approx(0.15)


# --------------------------------------------------------------------------
# watchdog
# --------------------------------------------------------------------------

def test_watchdog_flags_hung_dispatch_and_liveness():
    clk = {"t": 0.0}
    wd = DispatchWatchdog((1, 2, 4), hang_timeout_s=0.5,
                          clock=lambda: clk["t"])
    v = wd.observe(2, 0.1)
    assert not v.hung and wd.hung == 0
    v = wd.observe(2, 0.9)                  # outlived the timeout
    assert v.hung and wd.hung == 1
    assert wd.healthy()                     # it *completed*; loop is live
    clk["t"] += 10.0                        # nothing completes for 10 s
    assert not wd.healthy()                 # wedged engine, live signal


def test_watchdog_flags_straggling_bucket_lane():
    # three lanes: the median needs a majority of healthy lanes to
    # anchor against (with two lanes the slow one IS the median)
    wd = DispatchWatchdog((1, 2, 4), hang_timeout_s=30.0, window=10,
                          threshold=3.0)
    for _ in range(10):
        wd.observe(1, 0.01)                 # 0.01 s/img
        wd.observe(2, 0.02)                 # 0.01 s/img
        v = wd.observe(4, 0.2)              # 0.05 s/img -> 5x the median
    assert v.straggler and wd.straggler_events > 0


# --------------------------------------------------------------------------
# chaos injector determinism
# --------------------------------------------------------------------------

def test_chaos_schedule_is_deterministic_and_seeded():
    a = ChaosInjector.from_profile("mixed", 7)
    b = ChaosInjector.from_profile("mixed", 7)
    assert a.schedule == b.schedule
    assert 0 not in a.schedule              # dispatch 0 is always clean
    # the seed phase-shifts the schedule (offset in [1, period]); across
    # a handful of seeds more than one distinct schedule must appear
    offsets = {min(ChaosInjector.from_profile("mixed", s).schedule)
               for s in range(8)}
    assert len(offsets) > 1 and offsets <= {1, 2, 3}
    kinds = [f.kind for _, f in sorted(a.schedule.items())]
    assert kinds[:3] == ["kernel", "nan", "slow"]     # mixed cycles
    with pytest.raises(ValueError, match="unknown chaos profile"):
        ChaosInjector.from_profile("nope", 0)
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor")


def test_chaos_faults_fire_on_primary_stream_only():
    chaos = ChaosInjector({1: Fault("kernel")})
    x = np.ones(2, np.float32)
    assert chaos.call(np.sum, x) == 2.0               # dispatch 0: clean
    with pytest.raises(ChaosKernelFault):
        chaos.call(np.sum, x)                         # dispatch 1: fault
    # recovery stream never consumes schedule indices
    chaos2 = ChaosInjector({0: Fault("kernel")})
    assert chaos2.call(np.sum, x, stream="recovery") == 2.0
    with pytest.raises(ChaosKernelFault):
        chaos2.call(np.sum, x)                        # still pending
    assert chaos2.injected["kernel"] == 1


def test_chaos_poison_input_fires_on_both_streams():
    chaos = ChaosInjector(fault_on_nan_input=True)
    bad = np.array([1.0, np.nan], np.float32)
    for stream in ("primary", "recovery"):
        with pytest.raises(ChaosKernelFault, match="poisoned"):
            chaos.call(np.sum, bad, stream=stream)
    assert chaos.injected["poison"] == 2


def test_chaos_nan_fault_corrupts_output_shape_preserving():
    chaos = ChaosInjector({0: Fault("nan")})
    out = chaos.call(lambda a: a * 2, np.ones((2, 3), np.float32))
    assert out.shape == (2, 3) and np.isnan(out).all()


def test_chaos_slow_fault_sleeps_then_runs():
    slept = []
    chaos = ChaosInjector({0: Fault("slow", slow_s=0.25)},
                          sleep=slept.append)
    assert chaos.call(np.sum, np.ones(3, np.float32)) == 3.0
    assert slept == [0.25]


# --------------------------------------------------------------------------
# degradation ladder through the engine
# --------------------------------------------------------------------------

def test_kernel_fault_degrades_batch_to_reference_bitwise(vgg_params):
    """An injected kernel fault on batch k: the whole batch is re-served
    by the reference forward, bitwise-equal to a direct reference
    ``compile_network`` run; healthy batches stay on the primary path."""
    from repro.models import vgg
    eng = _engine(vgg_params, policy="pallas", buckets=(2,),
                  chaos=ChaosInjector({1: Fault("kernel")}))
    rng = np.random.default_rng(2)
    imgs = [_imgs(rng, 2), _imgs(rng, 2), _imgs(rng, 2)]
    reqs = [eng.submit(im) for im in imgs]  # one batch per request
    m = eng.run()
    assert all(r.outcome is RequestOutcome.OK for r in reqs)
    assert [r.served_by for r in reqs] == ["primary", "reference",
                                           "primary"]
    assert m.degraded_batches == 1 and m.failed == 0
    for req, im, policy in zip(reqs, imgs,
                               ("pallas", "reference", "pallas")):
        direct = vgg.compile_forward(vgg_params, img=IMG,
                                     batch=im.shape[0], policy=policy,
                                     cache=eng.compiler.cache)
        want = np.asarray(direct(vgg_params, jnp.asarray(im)))
        np.testing.assert_array_equal(req.logits, want)


def test_nan_output_detected_and_degraded(vgg_params):
    eng = _engine(vgg_params, buckets=(2,),
                  chaos=ChaosInjector({0: Fault("nan")}))
    rng = np.random.default_rng(3)
    req = eng.submit(_imgs(rng, 2))
    m = eng.run()
    assert req.outcome is RequestOutcome.OK
    assert req.served_by == "reference"
    assert np.isfinite(req.logits).all()
    assert m.nonfinite_batches == 1 and m.degraded_batches == 1


def test_quarantine_bisection_isolates_exactly_the_poison(vgg_params):
    """A request whose data crashes the kernel (on every ladder rung)
    fails alone; every batchmate is served, bitwise-correct."""
    from repro.models import vgg
    eng = _engine(vgg_params, policy="pallas", buckets=(1, 2, 4),
                  chaos=ChaosInjector(fault_on_nan_input=True))
    rng = np.random.default_rng(4)
    good = [_imgs(rng, 1), _imgs(rng, 1), _imgs(rng, 1)]
    poison = _imgs(rng, 1)
    poison[0, 0, 0, 0] = np.inf
    # slip the poison past submit validation straight into the queue —
    # modeling data that *becomes* bad after the front door (the chaos
    # injector's kernel then crashes on it, everywhere)
    reqs = [eng.submit(good[0]), eng.submit(good[1])]
    bad_req = ImageRequest(rid=999, images=poison)
    eng.batcher.queue.append(bad_req)
    eng.metrics.submitted += 1
    reqs.append(eng.submit(good[2]))
    m = eng.run()
    assert bad_req.outcome is RequestOutcome.FAILED
    assert "quarantined" in bad_req.error
    assert all(r.outcome is RequestOutcome.OK for r in reqs)
    assert m.failed == 1 and m.degraded_batches >= 1
    assert m.outcomes == {"ok": 3, "failed": 1}
    ref = vgg.compile_forward(vgg_params, img=IMG, batch=1,
                              policy="reference",
                              cache=eng.compiler.cache)
    for req, im in zip(reqs, good):
        want = np.asarray(ref(vgg_params, jnp.asarray(im)))
        np.testing.assert_array_equal(req.logits, want)


def test_slow_batch_flagged_hung_but_served(vgg_params):
    eng = _engine(vgg_params, buckets=(2,), hang_timeout_s=0.05,
                  chaos=ChaosInjector({0: Fault("slow", slow_s=0.2)}))
    rng = np.random.default_rng(5)
    req = eng.submit(_imgs(rng, 2))
    m = eng.run()
    assert req.outcome is RequestOutcome.OK     # slow, not broken
    assert req.served_by == "primary"
    assert m.hung_batches == 1 and m.degraded_batches == 0


def test_admission_shed_through_engine(vgg_params):
    eng = _engine(vgg_params, buckets=(1, 2))
    eng.warmup()
    rng = np.random.default_rng(6)
    eng.submit(_imgs(rng, 1))
    eng.step()                                  # EWMA goes live
    assert eng.admission.observations >= 1
    # a real batch can never finish in 1 ns: deterministically shed
    shed = eng.submit(_imgs(rng, 1), deadline_s=1e-9)
    assert shed.outcome is RequestOutcome.REJECTED
    assert "admission" in shed.error
    assert eng.pending == 0                     # never queued
    m = eng.metrics
    assert m.shed == 1 and m.deadline_total == 1 and m.deadline_hits == 0
    assert m.deadline_hit_rate == 0.0


# --------------------------------------------------------------------------
# the acceptance criteria, end to end
# --------------------------------------------------------------------------

@pytest.mark.parametrize("profile,buckets", [
    pytest.param("mixed", (1, 2, 4, 8), id="mixed"),
    # a single-fault profile still fires its fault several times in the
    # ten-request stream at two bucket widths, for half the compiles
    pytest.param("kernel-fault", (1, 2), id="kernel-fault"),
    pytest.param("nan", (1, 2), id="nan"),
    pytest.param("slow-batch", (1, 2), id="slow-batch"),
])
def test_chaos_run_zero_lost_requests_all_invariants(profile, buckets):
    """Under each deterministic chaos profile every
    submitted request reaches a terminal outcome (zero lost), quarantine
    isolates the poison, degraded logits are bitwise reference, healthy
    logits bitwise primary — ``chaos_summary`` raises on any violation —
    and the recovery path the profile targets actually ran."""
    assert profile in PROFILES
    d = chaos_summary("vgg16", profile=profile, seed=7, requests=10,
                      img=IMG, width_mult=WIDTH, policy="pallas",
                      buckets=buckets)
    rb = d["robustness"]
    assert rb["lost_requests"] == 0
    assert rb["submitted"] == 10 == sum(rb["outcomes"].values())
    for counter in PROFILE_EXPECTATIONS[profile]:
        assert rb[counter] > 0, counter
    assert rb["shed"] + rb["expired"] > 0
    assert d["chaos"]["profile"] == profile
    # deterministic: the same (profile, seed) schedules identically ...
    again = ChaosInjector.from_profile(profile, 7).describe()
    assert again["schedule"] == d["chaos"]["schedule"]
    if profile == "mixed":              # ... and replays identically
        d2 = chaos_summary("vgg16", profile=profile, seed=7, requests=10,
                           img=IMG, width_mult=WIDTH, policy="pallas",
                           buckets=buckets)
        assert d2["chaos"]["schedule"] == d["chaos"]["schedule"]
        assert d2["robustness"]["outcomes"] == rb["outcomes"]


def test_preemption_drain_flushes_admitted_requests(vgg_params):
    """A SIGTERM mid-stream stops admission, but everything already
    queued is flushed and metrics still emit — the clean drain."""
    eng = _engine(vgg_params, buckets=(1, 2))
    eng.warmup()
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 3, 8)
    admitted = []
    with PreemptionGuard() as guard:
        for i, n in enumerate(sizes):
            if i == 3:
                os.kill(os.getpid(), signal.SIGTERM)
            if guard.requested:          # stop admitting, keep draining
                break
            admitted.append(eng.submit(_imgs(rng, int(n))))
        eng.run()
    assert len(sizes) - len(admitted) == 5      # 3 admitted, 5 never
    assert all(r.outcome is RequestOutcome.OK for r in admitted)
    rb = eng.metrics_dict()["robustness"]
    assert rb["submitted"] == 3
    assert rb["lost_requests"] == 0
    assert sum(rb["outcomes"].values()) == 3


def test_metrics_dict_has_robustness_section(vgg_params):
    eng = _engine(vgg_params, buckets=(2,))
    rng = np.random.default_rng(7)
    eng.submit(_imgs(rng, 2))
    eng.run()
    rb = eng.metrics_dict()["robustness"]
    for k in ("submitted", "shed", "expired", "failed", "degraded_batches",
              "nonfinite_batches", "hung_batches", "straggler_events",
              "deadline_total", "deadline_hits", "deadline_hit_rate",
              "outcomes", "lost_requests"):
        assert k in rb, k
    assert rb["submitted"] == 1 and rb["outcomes"] == {"ok": 1}
    assert rb["deadline_hit_rate"] == 1.0       # no SLOs -> none missed
    assert rb["lost_requests"] == 0
