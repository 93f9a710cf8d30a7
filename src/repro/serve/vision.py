"""Continuous-batching image-inference engine over the compiled
fold-schedule engine (DESIGN.md §6), hardened into a fault-tolerant
serving runtime (DESIGN.md §10).

Mirrors the slot/queue design of ``serve/engine.py`` (the token engine)
but drives ``core/engine.py:CompiledNetwork`` forwards instead of decode
steps:

* batches form from a FIFO queue with **bucketed** widths
  (``serve/batcher.py``) — one jitted forward per bucket, all buckets
  sharing one ``ScheduleCache`` via ``BucketCompiler`` so fold planning
  and (optional) measured autotuning are pay-once across buckets;
* execution **shards across a mesh** by binding the batch (image-fold)
  axis and the N_F (filter-fold) axis to mesh axes through
  ``core/mapping.py:serving_conv_plan``'s ``partition_spec``
  (``distributed/sharding.py:vision_shardings``) — the identical engine
  code runs a 1-device CPU CI and a multi-device mesh;
* host→device staging **overlaps compute** with a double-buffered
  feeder: while the device runs batch k, batch k+1 is formed and
  ``device_put`` (the ``data/pipeline.py`` idiom of keeping the host one
  step ahead of the device) — ``run`` over a fixed queue, and the
  served route's ``EngineWorker`` over its inbox, through ``feed``
  (which forms behind a computing batch only a full widest bucket),
  ``readback`` and ``complete_ready``;
* the **fault-tolerant runtime** wraps the dispatch path: per-request
  deadlines with measured-EWMA admission control and form-time expiry
  (``serve/admission.py``), a degradation ladder that retries a failed
  or non-finite primary batch on the reference forward and bisects a
  still-failing batch to quarantine exactly the poisoned request, a
  watchdog (built on ``ft/fault_tolerance.py``) flagging hung and
  straggling dispatches, and an optional deterministic fault injector
  (``serve/chaos.py``).  The static fold schedules are never touched —
  all dynamism lives in this host runtime;
* serving metrics — measured KIPS, p50/p95/p99 request latency, slot
  occupancy, schedule-cache / fold-reuse hit rates, plus the robustness
  counters (shed / expired / failed / degraded / hung / deadline hit
  rate) — snapshot into the bench JSON via ``benchmarks/run.py`` and
  ``launch/serve.py --vision``.

The engine is model-agnostic: it serves any ``StreamGraph`` registered in
``models/zoo.py`` (``serving_summary`` looks models up by name), and the
per-conv fold schedules come from the shared graph lowering.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import BucketCompiler, ScheduleCache
from repro.core.mapping import serving_conv_plan
from repro.obs.folds import FoldStreamCounters
from repro.obs.metrics import LogHistogram, MetricsRegistry
from repro.obs.trace import (NULL_TRACER, REQ_TID0, TID_COMPLETE,
                             TID_DISPATCH, TID_ENGINE)
from repro.serve.admission import (AdmissionController, DispatchWatchdog,
                                   RequestOutcome)
from repro.serve.batcher import (BucketPolicy, FormedBatch, ImageBatcher,
                                 ImageRequest)

__all__ = ["InflightBatch", "ServingMetrics", "VisionEngine",
           "serving_summary"]


def _latency_hist() -> LogHistogram:
    """1µs .. 10ks range — any serving latency this host can produce."""
    return LogHistogram(lo=1e-6, hi=1e4, buckets_per_decade=48)


def _occupancy_hist() -> LogHistogram:
    """Slot occupancy lives in (0, 1]; a tight range keeps the relative
    bucket error well under the rounding the JSON applies."""
    return LogHistogram(lo=1e-3, hi=2.0, buckets_per_decade=48)


@dataclasses.dataclass
class ServingMetrics:
    """Accumulated over ``VisionEngine.run`` calls (warmup excluded).

    The original throughput/latency fields count *served* work; the
    robustness counters below track the request lifecycle — every
    submitted request ends in exactly one of the ``outcomes`` buckets, so
    ``submitted == sum(outcomes) + still-queued`` is the zero-loss
    invariant the chaos smoke asserts."""
    images: int = 0
    requests: int = 0
    batches: int = 0
    elapsed_s: float = 0.0
    # bounded log-bucketed histograms (``obs/metrics.py``), not lists: a
    # long-lived serving process records millions of completions and the
    # metrics footprint must not grow with traffic.  Exact count/sum/min/
    # max ride along, so means are exact and only the percentiles carry
    # the (≤ one bucket width, ~4.9%) quantization error.
    latency_hist: LogHistogram = dataclasses.field(
        default_factory=_latency_hist)
    occupancy_hist: LogHistogram = dataclasses.field(
        default_factory=_occupancy_hist)
    per_bucket: Dict[int, int] = dataclasses.field(default_factory=dict)
    # -- robustness (DESIGN.md §10) ---------------------------------------
    submitted: int = 0            # requests entering the engine (any fate)
    shed: int = 0                 # admission-rejected at submit
    expired: int = 0              # deadline passed before batch formation
    failed: int = 0               # quarantined by the degradation ladder
    degraded_batches: int = 0     # primary batch fell back to reference
    nonfinite_batches: int = 0    # primary output failed the finite check
    hung_batches: int = 0         # dispatch outlived the hang timeout
    straggler_events: int = 0     # bucket lane flagged by the detector
    deadline_total: int = 0       # terminal requests that carried an SLO
    deadline_hits: int = 0        # ... that completed OK in time
    # primary batches formed while another was still on the device
    overlapped_batches: int = 0
    outcomes: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def kips(self) -> float:
        """Measured kilo-images-per-second — the paper's eq (13) unit,
        here from wall clock rather than the cycle model."""
        return self.images / self.elapsed_s / 1e3 if self.elapsed_s else 0.0

    @property
    def slot_occupancy(self) -> float:
        return self.occupancy_hist.mean

    @property
    def deadline_hit_rate(self) -> float:
        """Fraction of SLO-carrying requests that completed in time (1.0
        when nothing carried a deadline — an SLO-free run misses none)."""
        return (self.deadline_hits / self.deadline_total
                if self.deadline_total else 1.0)

    def latency_percentiles(self) -> Dict[str, float]:
        """Same keys and rounding as the original list-backed version
        (the ``check_bench`` baselines compare these); percentiles now
        come from the bounded histogram, the mean stays exact."""
        h = self.latency_hist
        if not h.count:
            return {"p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0, "mean_s": 0.0}
        return {"p50_s": round(h.percentile(50), 6),
                "p95_s": round(h.percentile(95), 6),
                "p99_s": round(h.percentile(99), 6),
                "mean_s": round(h.mean, 6)}

    def as_dict(self) -> dict:
        return {
            "images": self.images,
            "requests": self.requests,
            "batches": self.batches,
            "overlapped_batches": self.overlapped_batches,
            "elapsed_s": round(self.elapsed_s, 4),
            "kips": round(self.kips, 6),
            "images_per_s": round(self.images / self.elapsed_s, 3)
                            if self.elapsed_s else 0.0,
            "latency": self.latency_percentiles(),
            "slot_occupancy": round(self.slot_occupancy, 4),
            "per_bucket_batches": {str(k): v for k, v
                                   in sorted(self.per_bucket.items())},
            "robustness": {
                "submitted": self.submitted,
                "shed": self.shed,
                "expired": self.expired,
                "failed": self.failed,
                "degraded_batches": self.degraded_batches,
                "nonfinite_batches": self.nonfinite_batches,
                "hung_batches": self.hung_batches,
                "straggler_events": self.straggler_events,
                "deadline_total": self.deadline_total,
                "deadline_hits": self.deadline_hits,
                "deadline_hit_rate": round(self.deadline_hit_rate, 4),
                "outcomes": {k: self.outcomes[k]
                             for k in sorted(self.outcomes)},
            },
        }


class _NonFiniteOutput(RuntimeError):
    """A primary forward completed but produced NaN/Inf in active rows."""


@dataclasses.dataclass
class InflightBatch:
    """A primary batch from its dispatch to its completion: ``readback``
    fills ``logits`` (or ``exc``) and ``t_done``, ``complete`` consumes
    it.  Clocks are ``time.monotonic``; ``cpu0`` is the engine thread's
    CPU clock at ``t0``, read only with tracing on."""
    fb: FormedBatch
    out: Any
    t0: float
    cpu0: Optional[float]
    exc: Optional[Exception]
    overlapped: bool              # formed while another was on the device
    logits: Optional[np.ndarray] = None
    t_done: Optional[float] = None


class VisionEngine:
    """Serve a stream of image requests through bucketed compiled forwards.

    ``submit`` then ``run`` (or ``step`` one batch at a time).  Outputs
    land on each request's ``logits`` and are bitwise-equal, per request,
    to a direct ``compile_network`` forward of the same images — padding
    and packing are pure batching concerns, invisible to the numerics.

    With ``mesh``, bucket widths round up to the data-axis size, params
    are placed by ``vision_shardings`` (conv weights and biases on the
    N_F filter-fold axis, everything else replicated) and every staged
    batch carries the ``serving_conv_plan`` batch sharding — GSPMD then
    runs the same jitted forwards data+model parallel, each fold kernel
    under ``shard_map`` on its device's shard.

    **Degradation ladder** (DESIGN.md §10): a primary dispatch that
    raises, or whose active rows come back non-finite, is retried on the
    bucket's *reference* compiled forward (counted ``degraded_batches``;
    the fold schedules stay untouched — only the executing kernel set
    changes).  If the reference batch also fails, it is bisected —
    halves retried recursively — until the poisoned request fails alone
    (``failed``, quarantined) and every batchmate is served.  Requests
    carry ``served_by`` (primary/reference) so callers can audit which
    rung produced each response.
    """

    def __init__(self, params: Dict[str, Any], graph, *,
                 img: int, chan: int = 3, policy: str = "auto",
                 buckets: Sequence[int] = (1, 2, 4, 8),
                 mesh=None, data_axis: str = "data",
                 model_axis: str = "model",
                 cache: Optional[ScheduleCache] = None,
                 head: Optional[Callable] = None,
                 fuse_epilogues: bool = True, autotune: bool = False,
                 tuning_path: Optional[str] = None,
                 autotune_timer: Optional[Callable] = None,
                 chaos=None, hang_timeout_s: float = 30.0,
                 admission: Optional[AdmissionController] = None,
                 tracer=None, registry: Optional[MetricsRegistry] = None,
                 fold_pe=None, precision: str = "fp32"):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.registry = registry
        bucket_policy = BucketPolicy(buckets)
        self.mesh = mesh
        self._x_sharding = None
        self.plan = None
        if mesh is not None:
            from repro.distributed.sharding import (vision_batch_sharding,
                                                    vision_shardings)
            data = mesh.shape.get(data_axis, 1)
            bucket_policy = bucket_policy.aligned(data)
            nf_max = max((int(leaf["w"].shape[0])
                          for leaf in params.values()
                          if isinstance(leaf, dict) and "w" in leaf
                          and getattr(leaf["w"], "ndim", 0) == 4),
                         default=1)
            self.plan = serving_conv_plan(bucket_policy.max_width, nf_max,
                                          data_axis=data_axis,
                                          model_axis=model_axis)
            params = jax.device_put(params,
                                    vision_shardings(params, mesh, self.plan))
            self._x_sharding = vision_batch_sharding(mesh, self.plan)
        self.params = params
        self.batcher = ImageBatcher(bucket_policy, img, chan,
                                    tracer=self.tracer)
        self.compiler = BucketCompiler(
            params, graph, img, chan=chan, policy=policy, cache=cache,
            head=head, fuse_epilogues=fuse_epilogues, autotune=autotune,
            tuning_path=tuning_path, autotune_timer=autotune_timer,
            tracer=self.tracer if self.tracer.enabled else None,
            precision=precision, mesh=mesh, mesh_plan=self.plan)
        self.metrics = ServingMetrics()
        self.chaos = chaos
        if chaos is not None and getattr(chaos, "tracer", None) in \
                (None, NULL_TRACER):
            chaos.tracer = self.tracer   # injected faults land in the trace
        self.admission = admission if admission is not None else \
            AdmissionController(bucket_policy.widths, registry=registry)
        self.watchdog = DispatchWatchdog(bucket_policy.widths,
                                         hang_timeout_s=hang_timeout_s)
        self._ref_compiler: Optional[BucketCompiler] = None
        # per-ScheduleKey streaming counters (obs/folds.py).  Always on:
        # the per-batch cost is O(conv layers) float ops, noise next to a
        # forward; tracing alone stays behind the NULL_TRACER check.
        self.folds = FoldStreamCounters(pe=fold_pe)
        self._req_spans: Dict[int, Any] = {}   # rid -> open lifetime span
        # dispatched, not yet completed, in dispatch order; one busy
        # period runs from the first of them formed to the last completed
        self._flight: collections.deque = collections.deque()
        self._busy_since = 0.0
        # readback done (and the engine thread's CPU clock then) of the
        # last completed batch: the device starts the next one no earlier
        self._last_done: Tuple[float, Optional[float]] = (-np.inf, None)
        self.warmup_s: Dict[int, float] = {}   # bucket -> warmup seconds

    # -- request side ------------------------------------------------------
    def submit(self, images: np.ndarray,
               deadline_s: Optional[float] = None,
               t_handoff: Optional[float] = None) -> ImageRequest:
        """Validate, admission-check, and enqueue one request.

        Malformed payloads raise ``BadRequestError`` (they never get a
        request object).  A well-formed request whose ``deadline_s`` the
        measured queue already blows is *returned un-queued* with
        ``outcome == REJECTED`` (counted ``shed``) — load shedding is a
        terminal outcome the caller observes, not an exception.
        ``t_handoff`` (``time.monotonic``) is when a caller on another
        thread handed the request over; it defaults to now."""
        tr = self.tracer
        sub = tr.begin("submit", tid=TID_ENGINE)
        try:
            req = self.batcher.make_request(images, deadline_s)
        except Exception as e:
            # malformed payload: no request object, no lifetime span
            tr.end(sub, error=repr(e))
            raise
        req.t_handoff = req.t_submit if t_handoff is None else t_handoff
        self.metrics.submitted += 1
        if tr.enabled:
            # the request's lifetime span, on its own track; closed with
            # the terminal outcome in ``_account`` — the zero-loss
            # invariant, visible in the trace
            self._req_spans[req.rid] = tr.begin(
                f"request-{req.rid}", cat="request",
                tid=REQ_TID0 + req.rid, request_id=req.rid,
                n_images=req.n, deadline_s=deadline_s)
        # ``submit`` closes where ``admit`` opens: engine spans stay flat
        tr.end(sub, request_id=req.rid)
        adm = tr.begin("admit", tid=TID_ENGINE)
        ok, predicted = self.admission.admit(
            req.n, self.batcher.pending_images, deadline_s,
            backlog=self._device_backlog())
        req.predicted_wait_s = predicted
        tr.end(adm, admitted=ok, predicted_wait_s=predicted)
        if not ok:
            req.finish(RequestOutcome.REJECTED,
                       error=f"admission: predicted wait {predicted:.4f}s "
                             f"exceeds deadline {deadline_s:.4f}s")
            self.metrics.shed += 1
            self._account(req)
            return req
        self.batcher.queue.append(req)
        return req

    @property
    def pending(self) -> int:
        return len(self.batcher)

    @property
    def dispatched(self) -> int:
        """Batches dispatched and not yet completed."""
        return len(self._flight)

    @property
    def computing(self) -> int:
        """Batches dispatched and not yet read back: the one on the
        device and any queued behind it."""
        return sum(f.t_done is None and f.exc is None for f in self._flight)

    def _device_backlog(self) -> List[Tuple[int, float]]:
        """(bucket, seconds it has run) of each computing batch, oldest
        first: the device's work ahead of a request besides the queue.
        The oldest has run since the device could start it, the one
        behind it not at all."""
        backlog: List[Tuple[int, float]] = []
        now, free = time.monotonic(), self._last_done[0]
        for f in self._flight:
            if f.t_done is not None:
                free = f.t_done
            elif f.exc is None:
                ran = 0.0 if backlog else max(now - max(f.t0, free), 0.0)
                backlog.append((f.fb.bucket, ran))
        return backlog

    # -- lifecycle accounting ---------------------------------------------
    def _account(self, req: ImageRequest) -> None:
        """Fold one terminal request into the outcome/deadline counters —
        called exactly once per request, at its terminal transition."""
        m = self.metrics
        key = req.outcome.value
        m.outcomes[key] = m.outcomes.get(key, 0) + 1
        if req.t_deadline is not None:
            m.deadline_total += 1
            if req.deadline_met:
                m.deadline_hits += 1
        span = self._req_spans.pop(req.rid, None)
        if span is not None:
            # queue waits from the hand-off: to the engine's submit (the
            # worker's inbox) and to the batch that took the request
            waits = {"inbox_ms": (req.t_submit - req.t_handoff) * 1e3}
            if req.t_formed is not None:
                waits["queued_ms"] = (req.t_formed - req.t_handoff) * 1e3
            self.tracer.end(span, outcome=key, served_by=req.served_by,
                            **waits,
                            **({"error": req.error} if req.error else {}))

    def _drain_expired(self) -> None:
        for req in self.batcher.expired:
            self.metrics.expired += 1
            self._account(req)
        self.batcher.expired.clear()

    # -- device side -------------------------------------------------------
    def _stage(self) -> Optional[Tuple[FormedBatch, jnp.ndarray]]:
        """Form the next batch and start its host→device transfer (an
        async ``device_put`` — the front half of the double buffer).
        Form-time deadline expiries are accounted here."""
        span = self.tracer.begin("form", tid=TID_ENGINE)
        fb = self.batcher.form()
        self._drain_expired()
        if fb is None:
            self.tracer.end(span, discard=True)   # idle poll: no noise
            return None
        self.tracer.end(span, bucket=fb.bucket, n_images=fb.n_images,
                        n_requests=len(fb.requests),
                        occupancy=fb.occupancy)
        # one transfer, straight to the (possibly sharded) device layout —
        # never commit to the default device first and reshard
        span = self.tracer.begin("stage", tid=TID_ENGINE, bucket=fb.bucket)
        if self._x_sharding is not None:
            x = jax.device_put(fb.x, self._x_sharding)
        else:
            x = jnp.asarray(fb.x)
        self.tracer.end(span)
        return fb, x

    def _dispatch(self, staged: Tuple[FormedBatch, jnp.ndarray],
                  overlapped: bool) -> InflightBatch:
        """Launch the bucket's compiled forward; returns without waiting
        (jit dispatch is async — the device computes while the host forms
        and stages the next batch, and queues a second forward behind
        the first).  A dispatch-time fault is carried in the
        ``InflightBatch`` instead of raised, so the feeder keeps feeding
        and recovery happens at completion time.  With tracing on, the
        engine thread's CPU clock is read beside ``t0``."""
        fb, x = staged
        net = self.compiler.network_for(fb.bucket)
        span = self.tracer.begin("dispatch", tid=TID_DISPATCH,
                                 bucket=fb.bucket, n_images=fb.n_images,
                                 overlapped=overlapped)
        t0 = time.monotonic()
        cpu0 = time.thread_time() if self.tracer.enabled else None
        try:
            if self.chaos is not None:
                out = self.chaos.call(lambda a: net(self.params, a), x)
            else:
                out = net(self.params, x)
            self.tracer.end(span)
            inflight = InflightBatch(fb, out, t0, cpu0, None, overlapped)
        except Exception as e:
            self.tracer.end(span, error=repr(e))
            inflight = InflightBatch(fb, None, t0, cpu0, e, overlapped)
        self._flight.append(inflight)
        return inflight

    def launch(self) -> Optional[InflightBatch]:
        """Form, stage and dispatch the next batch without waiting for
        it; ``None`` when nothing could be formed.  The batch is
        ``overlapped`` when another was computing as its forming
        began."""
        if not self._flight:
            self._busy_since = time.monotonic()
        overlapped = self.computing > 0
        staged = self._stage()
        return None if staged is None else self._dispatch(staged,
                                                          overlapped)

    def feed(self) -> List[InflightBatch]:
        """Dispatch, without waiting, what the full-bucket rule allows:
        with nothing computing, whatever is queued; behind one computing
        batch, one more once the queue holds a widest bucket of images;
        never a second one behind.  The served route's step
        (``EngineWorker``); returns the batches dispatched."""
        fed: List[InflightBatch] = []
        widest = self.batcher.policy.max_width
        while self.pending:
            computing = self.computing
            if computing > 1 or (computing and
                                 self.batcher.pending_images < widest):
                break
            inflight = self.launch()
            if inflight is None:
                break
            fed.append(inflight)
        return fed

    def readback(self, inflight: InflightBatch, *,
                 tid: int = TID_COMPLETE) -> None:
        """Block until the batch's logits are on the host (a device fault
        surfaces here, into ``exc``) and stamp when.  The wait releases
        the interpreter lock, so a worker runs this on a thread of its
        own."""
        tr = self.tracer
        if inflight.exc is None:
            span = tr.begin("readback", tid=tid, bucket=inflight.fb.bucket)
            try:
                inflight.logits = np.asarray(inflight.out)
            except Exception as e:
                inflight.exc = e
            inflight.out = None
            tr.end(span)
        inflight.t_done = time.monotonic()

    def complete(self, record: bool = True) -> InflightBatch:
        """Finish the oldest dispatched batch on the engine thread: read
        it back unless ``readback`` already has, then account, scatter
        or degrade.  ``metrics.elapsed_s`` gains each busy period, from
        its first batch's form to its last batch's completion."""
        inflight = self._flight[0]
        if inflight.t_done is None:
            self.readback(inflight)
        self._flight.popleft()
        self._finish(inflight, record)
        if not self._flight:
            self.metrics.elapsed_s += time.monotonic() - self._busy_since
        return inflight

    def complete_ready(self) -> int:
        """Complete, oldest first, the dispatched batches already read
        back; returns how many."""
        n = 0
        while self._flight and self._flight[0].t_done is not None:
            self.complete()
            n += 1
        return n

    def _finish(self, inflight: InflightBatch, record: bool) -> None:
        """Account, scatter or degrade a read-back batch.  Its service
        time runs from when the device could start it — its dispatch,
        or the previous batch's readback if that ended later — so a
        batch queued behind another is not charged for the wait."""
        fb, exc, logits = inflight.fb, inflight.exc, inflight.logits
        t_done, cpu_done = inflight.t_done, None
        tr = self.tracer
        if tr.enabled:
            # the engine thread's CPU clock at t_done, from its first own
            # reading after it — now, or the next batch's dispatch — less
            # the wall time since: the thread's CPU clock read from
            # another thread can lag, and this bound keeps every off-CPU
            # gap between two kernel spans >= 0
            marks = [(time.monotonic(), time.thread_time())]
            if self._flight and self._flight[0].t0 >= t_done:
                marks.append((self._flight[0].t0, self._flight[0].cpu0))
            t_mark, cpu_mark = min(marks)
            cpu_done = cpu_mark - (t_mark - t_done)
        t_start, cpu_start = inflight.t0, inflight.cpu0
        if self._last_done[0] > t_start:
            t_start, cpu_start = self._last_done
        self._last_done = (t_done, cpu_done)
        duration = t_done - t_start
        verdict = self.watchdog.observe(fb.bucket, duration)
        self.admission.observe(fb.bucket, duration)
        m = self.metrics
        if record:
            m.hung_batches += verdict.hung
            m.straggler_events += verdict.straggler
            m.batches += 1
            m.overlapped_batches += inflight.overlapped
            m.occupancy_hist.record(fb.occupancy)
            m.per_bucket[fb.bucket] = m.per_bucket.get(fb.bucket, 0) + 1
        # the measured device interval: device free to start -> readback
        # done, with the engine thread's CPU clock at both ends — what of
        # the host gap between two kernels was off the CPU (GIL or
        # blocking); consecutive kernel spans never overlap
        if tr.enabled:
            tr.add_span(
                "kernel", "device", TID_DISPATCH, t_start, duration,
                bucket=fb.bucket, n_images=fb.n_images,
                cpu_start_s=cpu_start, cpu_end_s=cpu_done,
                **({"error": repr(exc)} if exc is not None else {}))
        if record and exc is None:
            net = self.compiler.network_for(fb.bucket)
            self.folds.observe_dispatch(
                net.layer_schedules, fb.n_images, duration)
        if exc is None and not np.isfinite(logits[:fb.n_images]).all():
            if record:
                m.nonfinite_batches += 1
            tr.instant("nonfinite", cat="error", tid=TID_DISPATCH,
                       bucket=fb.bucket)
            exc = _NonFiniteOutput(
                f"primary batch (bucket {fb.bucket}) produced non-finite "
                "logits")
        if exc is not None:
            if record:
                m.degraded_batches += 1
            self._serve_degraded(list(fb.requests), record=record)
            return
        epi = tr.begin("epilogue", tid=TID_COMPLETE, bucket=fb.bucket)
        ImageBatcher.scatter(fb, logits, t_done)
        if record:
            m.images += fb.n_images
            m.requests += len(fb.requests)
            for r in fb.requests:
                m.latency_hist.record(r.latency_s)
        tr.end(epi)
        comp = tr.begin("complete", tid=TID_COMPLETE,
                        n_requests=len(fb.requests))
        for req in fb.requests:
            self._account(req)
        tr.end(comp)

    # -- degradation ladder ------------------------------------------------
    @property
    def reference_compiler(self) -> BucketCompiler:
        """The fallback rung: reference-mode compiled forwards per bucket,
        built lazily on first degradation, sharing the primary compiler's
        ``ScheduleCache`` (planning stays pay-once; only the executing
        kernels differ).  When the primary policy already *is* reference,
        the primary compiler is reused outright."""
        if self.compiler.policy == "reference":
            return self.compiler
        if self._ref_compiler is None:
            # the same precision AND the same calibrated recipe: a request
            # retried on the reference rung must see bitwise-identical
            # scales, or degradation would change its numerics
            self._ref_compiler = BucketCompiler(
                self.params, self.compiler.graph, self.batcher.img,
                chan=self.batcher.chan, policy="reference",
                cache=self.compiler.cache, head=self.compiler.head,
                precision=self.compiler.precision, quant=self.compiler.quant)
        return self._ref_compiler

    def _reference_forward(self, reqs: List[ImageRequest]) -> np.ndarray:
        """One reference-mode batch over ``reqs`` (re-packed and re-padded
        to a bucket width).  Chaos wraps this too, on the ``recovery``
        stream — scheduled faults never fire here, but a poisoned input
        still does (see ``serve/chaos.py``)."""
        total = sum(r.n for r in reqs)
        bucket = self.batcher.policy.bucket_for(total)
        x = np.zeros((bucket, self.batcher.chan, self.batcher.img,
                      self.batcher.img), np.float32)
        off = 0
        for r in reqs:
            x[off:off + r.n] = r.images
            off += r.n
        if self._x_sharding is not None:
            xd = jax.device_put(x, self._x_sharding)
        else:
            xd = jnp.asarray(x)
        net = self.reference_compiler.network_for(bucket)
        if self.chaos is not None:
            out = self.chaos.call(lambda a: net(self.params, a), xd,
                                  stream="recovery")
        else:
            out = net(self.params, xd)
        return np.asarray(out)

    def _serve_degraded(self, reqs: List[ImageRequest],
                        record: bool = True) -> None:
        """The ladder below a failed primary batch: reference retry, then
        recursive bisection, then single-request quarantine.  Every
        request in ``reqs`` is terminal when this returns."""
        tr = self.tracer
        span = tr.begin("degrade", tid=TID_COMPLETE, n_requests=len(reqs))
        try:
            logits = self._reference_forward(reqs)
        except Exception as e:
            if len(reqs) == 1:
                req = reqs[0]
                req.finish(RequestOutcome.FAILED,
                           error=f"quarantined: {type(e).__name__}: {e}")
                if record:
                    self.metrics.failed += 1
                tr.instant("quarantine", cat="error", tid=TID_COMPLETE,
                           request_id=req.rid, error=repr(e))
                self._account(req)
                tr.end(span, error=repr(e), quarantined=req.rid)
                return
            # the failed attempt's span closes before the halves open
            # theirs: engine spans stay flat
            tr.end(span, error=repr(e), bisected=True)
            mid = (len(reqs) + 1) // 2     # bisect: isolate the poison
            self._serve_degraded(reqs[:mid], record=record)
            self._serve_degraded(reqs[mid:], record=record)
            return
        t_done = time.monotonic()
        m = self.metrics
        off = 0
        for req in reqs:
            rows = logits[off:off + req.n]
            off += req.n
            if np.isfinite(rows).all():
                req.logits = rows
                req.served_by = "reference"
                req.finish(RequestOutcome.OK, t=t_done)
                if record:
                    m.images += req.n
                    m.requests += 1
                    m.latency_hist.record(req.latency_s)
            else:
                req.finish(RequestOutcome.FAILED, t=t_done,
                           error="quarantined: non-finite reference output")
                if record:
                    m.failed += 1
                tr.instant("quarantine", cat="error", tid=TID_COMPLETE,
                           request_id=req.rid,
                           error="non-finite reference output")
            self._account(req)
        tr.end(span, served_by="reference")

    def warmup(self) -> List[int]:
        """Compile and run every bucket width once on zeros, so serving
        latencies measure steady-state forwards, not XLA traces.  Returns
        the widths warmed; ``warmup_s`` keeps each width's seconds
        (schedule build + trace + compile + one run).  Chaos never wraps
        warmup — the injector's dispatch indices count served batches
        only."""
        widths = list(self.batcher.policy.widths)
        for w in widths:
            t0 = time.monotonic()
            net = self.compiler.network_for(w)
            zeros = np.zeros((w, self.batcher.chan, self.batcher.img,
                              self.batcher.img), np.float32)
            if self._x_sharding is not None:
                x = jax.device_put(zeros, self._x_sharding)
            else:
                x = jnp.asarray(zeros)
            np.asarray(net(self.params, x))
            self.warmup_s[w] = time.monotonic() - t0
        return widths

    def step(self) -> int:
        """Serve one batch synchronously; returns #images served (0 when
        the queue is empty)."""
        if self.launch() is None:
            return 0
        return self.complete().fb.n_images

    def run(self, max_batches: int = 1_000_000) -> ServingMetrics:
        """Drain the queue with the double-buffered feeder: batch k+1 is
        formed, staged host→device and dispatched while the device
        computes batch k, and k completes (the blocking readback) only
        after that.  Recovery (the degradation ladder) runs inside
        completion — the feeder never stalls on a fault."""
        batches = 0
        while True:
            # a batch is only formed (popping its requests) while the
            # budget allows dispatching it, so no request is dropped
            nxt = self.launch() if batches < max_batches else None
            batches += nxt is not None
            if self.dispatched > (nxt is not None):
                self.complete()
            if nxt is None:
                return self.metrics

    # -- reporting ---------------------------------------------------------
    def metrics_dict(self) -> dict:
        d = self.metrics.as_dict()
        d["compile"] = self.compiler.stats()    # buckets + fold-reuse rates
        d["buckets"] = list(self.batcher.policy.widths)
        d["mesh"] = (dict(self.mesh.shape) if self.mesh is not None else None)
        # zero-loss invariant: submitted == terminal + still-queued
        # + in dispatched batches
        terminal = sum(self.metrics.outcomes.values())
        d["robustness"]["lost_requests"] = (
            self.metrics.submitted - terminal - self.pending
            - sum(len(f.fb.requests) for f in self._flight))
        if self.chaos is not None:
            d["robustness"]["chaos_injected"] = dict(self.chaos.injected)
        # the live per-ScheduleKey table (obs/folds.py): model-side eq-10
        # utilization + modeled bytes joined with measured dispatch time
        d["observability"] = self.folds.as_dict()
        return d

    def snapshot_registry(self, registry: Optional[MetricsRegistry] = None,
                          labels: Optional[Dict[str, str]] = None
                          ) -> MetricsRegistry:
        """Sync every serving counter into a metrics registry
        (``obs/metrics.py``) — one snapshot carrying perf + robustness +
        fold-reuse + chaos health.  Sync happens here, at snapshot time,
        so the serving hot path never touches the registry.

        ``labels`` (e.g. ``{"worker": "w0"}``) is stamped onto every
        synced series, so several engines — the HTTP router's worker
        pool — can share one registry without clobbering each other."""
        reg = registry if registry is not None else \
            (self.registry or MetricsRegistry())
        lb = dict(labels or {})
        m = self.metrics

        def c(name: str, help_: str = "", **kw):
            return reg.counter(name, help_, **lb, **kw)

        def g(name: str, help_: str = "", **kw):
            return reg.gauge(name, help_, **lb, **kw)
        c("serve_requests_submitted_total",
          "Requests entering the engine (any fate)").set_total(m.submitted)
        for outcome, n in sorted(m.outcomes.items()):
            c("serve_requests_total", "Terminal requests by outcome",
              outcome=outcome).set_total(n)
        c("serve_images_total", "Images served OK").set_total(m.images)
        c("serve_batches_total", "Primary batches completed"
          ).set_total(m.batches)
        c("engine_overlapped_batches_total",
          "Primary batches formed while another was on the device"
          ).set_total(m.overlapped_batches)
        for name, help_ in (("shed", "Admission-rejected at submit"),
                            ("expired", "Deadline passed before forming"),
                            ("failed", "Quarantined requests"),
                            ("degraded_batches", "Primary -> reference"),
                            ("nonfinite_batches", "Non-finite primary out"),
                            ("hung_batches", "Dispatch over hang timeout"),
                            ("straggler_events", "Straggling bucket lanes"),
                            ("deadline_total", "Terminal with an SLO"),
                            ("deadline_hits", "SLO met")):
            c(f"serve_{name}_total", help_).set_total(getattr(m, name))
        g("serve_kips", "Measured kilo-images per second").set(m.kips)
        g("serve_deadline_hit_rate", "SLO hit fraction"
          ).set(m.deadline_hit_rate)
        g("serve_pending_requests", "Still queued").set(self.pending)
        cs = self.compiler.cache.stats
        c("schedule_cache_hits_total", "Fold-reuse hits").set_total(cs.hits)
        c("schedule_cache_misses_total", "Schedules planned"
          ).set_total(cs.misses)
        c("schedule_cache_replans_total", "Geometry replans"
          ).set_total(cs.replans)
        g("schedule_cache_hit_rate", "Fold-reuse rate").set(cs.hit_rate)
        reg.register_histogram("serve_latency_seconds", m.latency_hist,
                               "End-to-end request latency", **lb)
        reg.register_histogram("serve_slot_occupancy", m.occupancy_hist,
                               "Real rows / bucket width per batch", **lb)
        if self.chaos is not None:
            for kind, n in sorted(self.chaos.injected.items()):
                c("chaos_injected_total", "Faults fired by the injector",
                  kind=kind).set_total(n)
        for row in self.folds.rows():
            g("fold_util_model_pct", "eq-10 model PE utilization",
              schedule=row["key"]).set(row["util_model_pct"])
            g("fold_achieved_vs_model_pct",
              "Measured GFLOP/s over eq-12 model GFLOP/s",
              schedule=row["key"]).set(row["achieved_vs_model_pct"])
        c("admission_observations_total", "Batch service-time samples"
          ).set_total(self.admission.observations)
        return reg


def serving_summary(model: str, *, requests: int = 32, img: int = 32,
                    width_mult: float = 0.0625, classes: int = 10,
                    policy: str = "auto", buckets: Sequence[int] = (1, 2, 4, 8),
                    mesh=None, seed: int = 0, autotune: bool = False,
                    tuning_path: Optional[str] = None,
                    deadline_s: Optional[float] = None,
                    deadline_every: int = 1,
                    guard=None,
                    tracer=None,
                    registry: Optional[MetricsRegistry] = None,
                    precision: str = "fp32",
                    verbose: bool = False) -> dict:
    """Serve a deterministic mixed-size random request stream through a
    reduced-width registered model (``models/zoo.py``) and return the
    metrics dict (the per-model serving section of the bench JSON).
    Shared by ``launch/serve.py --vision`` and ``benchmarks/run.py``.

    ``deadline_s`` attaches an SLO to every ``deadline_every``-th request.
    ``guard`` is a ``ft/fault_tolerance.py:PreemptionGuard`` (or anything
    with a ``requested`` attribute): once it trips, admission stops —
    remaining requests are never submitted — while everything already
    queued is flushed and the metrics still emit (the clean SIGTERM
    drain)."""
    from repro.models.zoo import get_conv_model
    spec = get_conv_model(model)
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=width_mult,
                              img=img, classes=classes)
    engine = VisionEngine(params, spec.to_graph(), img=img, policy=policy,
                          buckets=buckets, mesh=mesh, autotune=autotune,
                          tuning_path=tuning_path, tracer=tracer,
                          registry=registry, precision=precision)
    engine.warmup()
    rng = np.random.default_rng(seed)
    max_n = engine.batcher.policy.max_width
    sizes = rng.integers(1, max_n + 1, requests)
    preempted = 0
    for i, n in enumerate(sizes):
        if guard is not None and getattr(guard, "requested", False):
            preempted = len(sizes) - i      # stop admitting, keep draining
            break
        dl = (deadline_s if deadline_s is not None
              and (deadline_every <= 1 or i % deadline_every == 0) else None)
        engine.submit(rng.standard_normal((int(n), 3, img, img))
                      .astype(np.float32), deadline_s=dl)
    engine.run()                            # flush everything in flight
    if registry is not None:
        engine.snapshot_registry(registry)
    d = engine.metrics_dict()
    d["workload"] = {"model": model, "width_mult": width_mult, "img": img,
                     "requests": int(requests), "policy": policy,
                     "precision": precision,
                     "seed": seed, "backend": jax.default_backend(),
                     "deadline_s": deadline_s, "preempted": preempted}
    if verbose:
        lat = d["latency"]
        print(f"served {d['requests']} requests / {d['images']} images in "
              f"{d['elapsed_s']}s: {d['kips']} KIPS "
              f"({d['images_per_s']} img/s)")
        print(f"latency p50={lat['p50_s']}s p95={lat['p95_s']}s "
              f"p99={lat['p99_s']}s; slot occupancy "
              f"{d['slot_occupancy']}; batches/bucket "
              f"{d['per_bucket_batches']}")
        rb = d["robustness"]
        print(f"robustness: outcomes {rb['outcomes']}, "
              f"shed={rb['shed']} expired={rb['expired']} "
              f"failed={rb['failed']} degraded={rb['degraded_batches']} "
              f"deadline_hit_rate={rb['deadline_hit_rate']} "
              f"lost={rb['lost_requests']}")
        if preempted:
            print(f"preemption drain: {preempted} request(s) never "
                  "admitted; queue flushed cleanly")
        c = d["compile"]
        print(f"buckets compiled {c['buckets']}, "
              f"{c['distinct_schedules']} distinct schedules, "
              f"schedule-cache hit_rate={c['hit_rate']}")
        print(engine.folds.table())
    return d
