"""Async HTTP request transport over the vision serving runtime
(DESIGN.md §13).

The serving stack so far ends at a Python API: callers hand
``VisionEngine.submit`` a numpy array and poll ``run``/``step``.  This
module puts the engine behind a wire — a small asyncio HTTP/1.1
front-end (stdlib only, no new runtime deps) speaking a JSON protocol —
so the request-lifecycle machinery from DESIGN.md §10 is observable by
real clients as HTTP semantics:

    outcome   (serve/admission.py)        HTTP
    --------------------------------------------------------------
    BadRequestError at submit             400  (never reaches a batch)
    rejected  (admission shed)            429  + Retry-After from the
                                               predicted queue wait
    expired   (deadline passed queued)    504
    failed    (quarantined by the ladder) 500
    ok                                    200  + logits, served_by
    draining  (PreemptionGuard tripped)   503  (new work refused)

Every submitted request still reaches exactly one terminal outcome and
every wire request receives exactly one response carrying it — the
zero-loss invariant now holds across the transport, which is what the
load generator (``benchmarks/run_async_requests.py``) and the CI
``transport`` job assert.

Threading model: jit dispatch and the batcher are synchronous, so each
``VisionEngine`` is owned by one dedicated ``EngineWorker`` thread; the
asyncio side enqueues ``(payload, Future)`` pairs and awaits the future
(``asyncio.wrap_future``).  The worker drains its inbox before every
batch it forms, so concurrent wire requests pack into wide device
batches — the continuous-batching discipline survives the wire
unchanged — and keeps serving the inbox while a batch computes, forming
and dispatching the next full one behind it (a readback thread per
worker waits for each batch and wakes the worker when it is done).

Endpoints:

* ``POST /v1/infer``  — images (nested JSON lists, or base64 raw
  float32 via ``{"shape", "dtype", "data_b64"}``) + optional deadline
  (``X-Deadline-S`` header, or ``deadline_s`` in the body).
* ``GET /healthz``    — liveness; 503 once draining.
* ``GET /metrics``    — Prometheus text exposition of the shared
  ``MetricsRegistry`` (engines synced per scrape under a ``worker``
  label); ``GET /metrics.json`` is the JSON snapshot
  ``obs.report --validate-metrics`` checks.
* ``GET /stats``      — router dispatch state + per-worker engine
  metrics (the load generator reads ``lost_requests`` here).

Observability: per-endpoint request counters
(``transport_requests_total{endpoint,status}``), a handling-time
histogram (``transport_request_seconds``) and a per-request transport
span on ``TID_TRANSPORT``, all from the request's first byte (not from
the keep-alive wait before it).  The span's children are the request's
stages — ``read`` (first byte → body complete), ``decode`` (body →
images, deadline header included), ``wait`` (the router and engine) and
``encode`` (result → response bytes) — each carrying the engine's
``request_id``, so a request's wire and engine spans join.
"""
from __future__ import annotations

import asyncio
import base64
import dataclasses
import json
import math
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.obs.trace import (NULL_TRACER, TID_COMPLETE, TID_ENGINE,
                             TID_READBACK, TID_TRANSPORT)
from repro.serve.admission import BadRequestError
from repro.serve.batcher import ImageRequest

__all__ = ["EngineWorker", "InferResult", "TransportServer",
           "HttpClient", "http_json", "PayloadTooLarge",
           "encode_images_payload", "decode_infer_body",
           "result_from_request", "result_from_response",
           "OUTCOME_STATUS"]

# terminal RequestOutcome value -> HTTP status (the wire contract)
OUTCOME_STATUS = {"ok": 200, "rejected": 429, "expired": 504,
                  "failed": 500}
_REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
            405: "Method Not Allowed", 413: "Payload Too Large",
            429: "Too Many Requests", 500: "Internal Server Error",
            503: "Service Unavailable", 504: "Gateway Timeout"}

MAX_BODY_BYTES = 8 << 20        # oversized payloads are capped, not read
MAX_HEADERS = 100


class PayloadTooLarge(Exception):
    """Declared Content-Length exceeds the body cap — answered 413
    before a single body byte is read."""


# ---------------------------------------------------------------------------
# wire payloads
# ---------------------------------------------------------------------------

def encode_images_payload(images: np.ndarray,
                          deadline_s: Optional[float] = None) -> dict:
    """The compact client-side body: base64 of the raw float32 buffer
    (~3x smaller than nested JSON lists and no float-repr cost)."""
    arr = np.ascontiguousarray(np.asarray(images, np.float32))
    payload: Dict[str, Any] = {
        "shape": list(arr.shape), "dtype": "float32",
        "data_b64": base64.b64encode(arr.tobytes()).decode("ascii")}
    if deadline_s is not None:
        payload["deadline_s"] = float(deadline_s)
    return payload


def decode_infer_body(body: bytes) -> Tuple[np.ndarray, Optional[float]]:
    """Parse a ``POST /v1/infer`` body into (images, deadline_s).

    Raises ``BadRequestError`` for malformed JSON or an undecodable
    payload — before anything touches an engine, so a garbage body can
    never show up in ``metrics.submitted``."""
    try:
        obj = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as e:
        raise BadRequestError(f"request body is not valid JSON: {e}") from e
    if not isinstance(obj, dict):
        raise BadRequestError(
            f"request body must be a JSON object, got "
            f"{type(obj).__name__}")
    deadline = obj.get("deadline_s")
    if deadline is not None:
        try:
            deadline = float(deadline)
        except (TypeError, ValueError) as e:
            raise BadRequestError(
                f"deadline_s must be a number, got {deadline!r}") from e
    if "data_b64" in obj:
        try:
            raw = base64.b64decode(obj["data_b64"], validate=True)
            arr = np.frombuffer(raw, dtype=np.dtype(
                obj.get("dtype", "float32"))).reshape(obj["shape"])
        except (KeyError, TypeError, ValueError) as e:
            raise BadRequestError(
                f"undecodable b64 image payload: {e}") from e
        return np.asarray(arr, np.float32), deadline
    if "images" in obj:
        try:
            arr = np.asarray(obj["images"], np.float32)
        except (TypeError, ValueError) as e:
            raise BadRequestError(
                f"images field is not a numeric array: {e}") from e
        return arr, deadline
    raise BadRequestError(
        "request body needs an 'images' array or a "
        "'shape'/'dtype'/'data_b64' payload")


@dataclasses.dataclass
class InferResult:
    """One wire-level inference result — what the router returns and
    ``POST /v1/infer`` serializes, whichever worker produced it."""
    outcome: str
    status: int
    logits: Optional[np.ndarray] = None
    served_by: Optional[str] = None
    error: Optional[str] = None
    latency_s: Optional[float] = None
    predicted_wait_s: Optional[float] = None
    request_id: Optional[int] = None
    worker: Optional[str] = None

    def body(self) -> dict:
        d: Dict[str, Any] = {"outcome": self.outcome}
        for k in ("request_id", "worker", "served_by", "error"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.latency_s is not None:
            d["latency_s"] = round(self.latency_s, 6)
        if self.predicted_wait_s is not None:
            d["predicted_wait_s"] = round(self.predicted_wait_s, 6)
        if self.logits is not None:
            # float32 -> float64 -> repr round-trips bitwise, so served
            # logits survive the JSON hop exactly (tested)
            d["logits"] = np.asarray(self.logits, np.float64).tolist()
        return d

    def headers(self) -> Dict[str, str]:
        if self.status == 429:
            wait = max(self.predicted_wait_s or 0.0, 0.0)
            return {"Retry-After": str(max(1, math.ceil(wait)))}
        return {}


def result_from_request(req: ImageRequest,
                        worker: Optional[str] = None) -> InferResult:
    """Terminal ``ImageRequest`` -> wire result (the local-worker path)."""
    out = req.outcome.value
    return InferResult(
        outcome=out, status=OUTCOME_STATUS.get(out, 500),
        logits=req.logits if out == "ok" else None,
        served_by=req.served_by, error=req.error,
        latency_s=req.latency_s if req.done else None,
        predicted_wait_s=req.predicted_wait_s,
        request_id=req.rid, worker=worker)


def result_from_response(status: int, obj: dict,
                         worker: Optional[str] = None) -> InferResult:
    """HTTP response from a remote worker -> wire result (the
    subprocess-worker path)."""
    if not isinstance(obj, dict):
        obj = {"error": f"non-JSON worker response: {obj!r}"}
    logits = obj.get("logits")
    return InferResult(
        outcome=obj.get("outcome", "failed"), status=int(status),
        logits=(np.asarray(logits, np.float32)
                if logits is not None else None),
        served_by=obj.get("served_by"), error=obj.get("error"),
        latency_s=obj.get("latency_s"),
        predicted_wait_s=obj.get("predicted_wait_s"),
        request_id=obj.get("request_id"), worker=worker)


# ---------------------------------------------------------------------------
# the engine worker thread
# ---------------------------------------------------------------------------

class EngineWorker:
    """One serving worker: a dedicated thread owning a ``VisionEngine``.

    The transport enqueues ``(payload, Future)`` pairs; the thread
    drains its whole inbox before it forms a batch, so concurrent wire
    requests pack into the same device batch, then resolves each future
    the moment its request reaches a terminal outcome (including
    submit-time admission rejects and form-time expiries).  ``call``
    runs an arbitrary function against the engine *on the worker
    thread* — stats and metrics snapshots serialize with serving work
    instead of racing it.

    The step is double-buffered: while batch k computes, the thread
    keeps serving its inbox, and once the queue holds a widest bucket of
    images it forms, stages and dispatches batch k+1, which the device
    runs straight after k (the rule is ``VisionEngine.feed``).  With
    fewer queued, nothing is formed until k is done; then k completes,
    its callers are answered, the inbox is drained and whatever is
    queued goes, in a synchronous step's order.  At most one batch waits
    behind the one computing.  A readback thread blocks on each batch's
    logits in dispatch order and posts a wake-up into the inbox, so the
    thread wakes on whichever comes first, a request or a finished
    batch; completion (scatter, accounting, the degradation ladder)
    stays on the engine thread, in dispatch order, after the next batch
    is on its way.
    """

    def __init__(self, name: str, engine, *, poll_s: float = 0.002):
        self.name = name
        self.engine = engine
        self.poll_s = float(poll_s)
        self._inbox: "queue.Queue" = queue.Queue()
        self._waiting: Dict[int, Tuple[ImageRequest, Future]] = {}
        self._stop = threading.Event()
        self._drain = True
        self._thread = threading.Thread(
            target=self._loop, name=f"engine-worker-{name}", daemon=True)
        # dispatched batches, in order, for the readback thread
        self._readbacks: "queue.Queue" = queue.Queue()
        # test hook: when set to an (unset) Event the loop idles until
        # it is set — lets tests hold a request in flight deterministically
        self.gate: Optional[threading.Event] = None

    def start(self, warmup: bool = True) -> "EngineWorker":
        if warmup:
            self.engine.warmup()
        self._thread.start()
        return self

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    @property
    def inflight(self) -> int:
        """Requests accepted but not yet terminal (inbox + queued)."""
        return self._inbox.qsize() + len(self._waiting)

    def submit(self, images: np.ndarray,
               deadline_s: Optional[float] = None) -> Future:
        """Thread-safe: resolves to the terminal ``ImageRequest`` (or
        raises ``BadRequestError`` for malformed payloads).  The hand-off
        is stamped here, so the request's wait in the inbox is seen."""
        fut: Future = Future()
        self._inbox.put(("infer", (images, deadline_s, time.monotonic()),
                         fut))
        return fut

    def call(self, fn: Callable) -> Future:
        """Run ``fn(engine)`` on the worker thread; resolves to its
        return value."""
        fut: Future = Future()
        self._inbox.put(("call", fn, fut))
        return fut

    def stop(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the worker; with ``drain`` (the default) everything
        already accepted completes first — the SIGTERM discipline.  A
        batch already dispatched completes either way."""
        self._drain = drain
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)

    # -- worker thread -----------------------------------------------------
    def _loop(self) -> None:
        reader = threading.Thread(target=self._read_back,
                                  name=f"engine-readback-{self.name}",
                                  daemon=True)
        reader.start()
        try:
            self._serve()
        finally:
            # the reader reads back every dispatched batch before it
            # stops; any not yet completed (a stop under a closed gate)
            # complete now
            self._readbacks.put(None)
            reader.join()
            while self.engine.dispatched:
                self.engine.complete()
            self._resolve_terminal()

    def _serve(self) -> None:
        engine = self.engine
        while True:
            gate = self.gate
            if gate is not None and not gate.wait(timeout=0.01):
                if self._stop.is_set() and not self._drain:
                    break
                continue
            drained = 0
            while True:
                try:
                    item = self._inbox.get_nowait()
                except queue.Empty:
                    break
                self._handle(item)
                drained += 1
            if not engine.computing and engine.complete_ready():
                # the device is idle: answer batch k's callers and take in
                # what came meanwhile before forming, as a synchronous
                # step orders it
                self._resolve_terminal()
                continue
            fed = engine.feed()
            for inflight in fed:
                self._readbacks.put(inflight)
            # behind a computing batch, k completes once k+1 is on its
            # way, so the host work overlaps k+1's forward
            completed = engine.complete_ready()
            self._resolve_terminal()
            if fed or completed:
                continue
            if self._stop.is_set() and not engine.pending \
                    and not engine.dispatched:
                if not self._drain:
                    self._fail_waiting("worker stopped without drain")
                    break
                if self._inbox.empty() and not self._waiting:
                    break
                continue
            if not drained:
                tr = engine.tracer
                t0 = tr.clock() if tr.enabled else None
                try:
                    item = self._inbox.get(timeout=self.poll_s)
                except queue.Empty:
                    continue
                if t0 is not None:   # only a wait that returned work
                    tr.add_span("idle", "serve", TID_ENGINE, t0,
                                tr.clock() - t0)
                self._handle(item)

    def _read_back(self) -> None:
        """The readback thread: wait for each dispatched batch, in order,
        and post a wake-up into the inbox (``None`` stops the thread)."""
        while True:
            inflight = self._readbacks.get()
            if inflight is None:
                return
            self.engine.readback(inflight, tid=TID_READBACK)
            self._inbox.put(("done", None, None))

    def _handle(self, item) -> None:
        kind, payload, fut = item
        if kind == "done":          # a batch is read back: a wake-up
            return
        if not fut.set_running_or_notify_cancel():
            return
        if kind == "call":
            try:
                fut.set_result(payload(self.engine))
            except Exception as e:
                fut.set_exception(e)
            return
        images, deadline_s, t_handoff = payload
        try:
            req = self.engine.submit(images, deadline_s=deadline_s,
                                     t_handoff=t_handoff)
        except Exception as e:
            fut.set_exception(e)
            return
        if req.outcome.terminal:
            fut.set_result(req)
        else:
            self._waiting[req.rid] = (req, fut)

    def _resolve_terminal(self) -> None:
        done = [rid for rid, (req, _) in self._waiting.items()
                if req.outcome.terminal]
        if not done:
            return
        tr = self.engine.tracer
        span = tr.begin("resolve", tid=TID_COMPLETE, n_requests=len(done))
        for rid in done:
            req, fut = self._waiting.pop(rid)
            fut.set_result(req)
        tr.end(span)

    def _fail_waiting(self, why: str) -> None:
        for _, fut in self._waiting.values():
            if not fut.done():
                fut.set_exception(RuntimeError(why))
        self._waiting.clear()


# ---------------------------------------------------------------------------
# HTTP/1.1 framing (stdlib asyncio streams; no new deps)
# ---------------------------------------------------------------------------

async def _read_http_message(reader: asyncio.StreamReader,
                             max_body: int):
    """One request or response off the stream:
    ``(start_line_parts, headers, body)``; ``None`` on clean EOF.
    Raises ``PayloadTooLarge`` *before* reading an oversized body."""
    line = await reader.readline()
    if not line:
        return None
    return await _read_http_rest(reader, line, max_body)


async def _read_http_rest(reader: asyncio.StreamReader, line: bytes,
                          max_body: int):
    """The headers and body of a message whose start ``line`` is read."""
    parts = line.decode("latin-1").split()
    if len(parts) < 2:
        raise ValueError(f"malformed HTTP start line: {line!r}")
    headers: Dict[str, str] = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        k, _, v = h.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
        if len(headers) > MAX_HEADERS:
            raise ValueError("too many HTTP headers")
    length = int(headers.get("content-length", "0") or "0")
    if length > max_body:
        raise PayloadTooLarge(
            f"declared body of {length} bytes exceeds the "
            f"{max_body}-byte cap")
    body = await reader.readexactly(length) if length > 0 else b""
    return parts, headers, body


def _http_response(status: int, payload,
                   content_type: str = "application/json",
                   extra_headers: Optional[Dict[str, str]] = None,
                   close: bool = False) -> bytes:
    if isinstance(payload, (dict, list)):
        body = json.dumps(payload).encode("utf-8")
    elif isinstance(payload, str):
        body = payload.encode("utf-8")
    else:
        body = bytes(payload)
    lines = [f"HTTP/1.1 {status} {_REASONS.get(status, 'Unknown')}",
             f"Content-Type: {content_type}",
             f"Content-Length: {len(body)}",
             f"Connection: {'close' if close else 'keep-alive'}"]
    for k, v in (extra_headers or {}).items():
        lines.append(f"{k}: {v}")
    return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1") + body


class HttpClient:
    """A keep-alive JSON client on one asyncio connection — the load
    generator runs one per virtual user, the router one per call."""

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = int(port)
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None

    async def _connect(self) -> None:
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)

    async def request(self, method: str, path: str, payload=None,
                      headers: Optional[Dict[str, str]] = None,
                      max_body: int = MAX_BODY_BYTES):
        """Returns ``(status, parsed_json_or_text)``; reconnects once on
        a dropped keep-alive connection."""
        body = (json.dumps(payload).encode("utf-8")
                if payload is not None else b"")
        head = [f"{method} {path} HTTP/1.1",
                f"Host: {self.host}:{self.port}",
                f"Content-Length: {len(body)}",
                "Content-Type: application/json"]
        for k, v in (headers or {}).items():
            head.append(f"{k}: {v}")
        raw = ("\r\n".join(head) + "\r\n\r\n").encode("latin-1") + body
        for attempt in (0, 1):
            if self._writer is None:
                await self._connect()
            try:
                assert self._writer is not None and self._reader is not None
                self._writer.write(raw)
                await self._writer.drain()
                msg = await _read_http_message(self._reader, max_body)
                if msg is None:
                    raise ConnectionError("server closed the connection")
                break
            except (ConnectionError, asyncio.IncompleteReadError, OSError):
                await self.close()
                if attempt:
                    raise
        parts, resp_headers, resp_body = msg
        status = int(parts[1])
        if resp_headers.get("connection", "").lower() == "close":
            await self.close()
        ctype = resp_headers.get("content-type", "")
        if ctype.startswith("application/json"):
            return status, json.loads(resp_body.decode("utf-8"))
        return status, resp_body.decode("utf-8", "replace")

    async def close(self) -> None:
        if self._writer is not None:
            try:
                self._writer.close()
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
        self._reader = self._writer = None


async def http_json(host: str, port: int, method: str, path: str,
                    payload=None, headers: Optional[Dict[str, str]] = None):
    """One-shot request on a fresh connection (the router's remote-worker
    calls and the launcher's probes)."""
    client = HttpClient(host, port)
    try:
        return await client.request(method, path, payload, headers)
    finally:
        await client.close()


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class TransportServer:
    """The asyncio HTTP front-end over a ``serve/router.py:Router``.

    One connection-handler coroutine per client with keep-alive, a
    body-size cap answered 413 before the body is read, per-endpoint
    request counters in ``registry``, one transport span per request in
    ``tracer``, and an optional append-only access log.  ``guard`` is a
    ``PreemptionGuard`` (anything with ``.requested``): once it trips,
    new ``/v1/infer`` requests are refused 503 and ``/healthz`` reports
    draining, while responses already in flight complete — the graceful
    SIGTERM drain, visible from the wire.
    """

    def __init__(self, router, *, host: str = "127.0.0.1", port: int = 0,
                 registry=None, tracer=None, guard=None,
                 max_body: int = MAX_BODY_BYTES,
                 access_log: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.router = router
        self.host = host
        self.port = int(port)          # rebound to the OS pick on start
        self.registry = registry
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.guard = guard
        self.max_body = int(max_body)
        self.clock = clock
        self._access_path = access_log
        self._access_fh = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._probe_task: Optional[asyncio.Task] = None

    # -- lifecycle ---------------------------------------------------------
    @property
    def draining(self) -> bool:
        return bool(self.guard is not None
                    and getattr(self.guard, "requested", False))

    async def start(self, probe_interval_s: float = 0.0) -> int:
        if self._access_path:
            self._access_fh = open(self._access_path, "a", buffering=1)
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        if probe_interval_s > 0:
            self._probe_task = asyncio.ensure_future(
                self._probe_loop(probe_interval_s))
        return self.port

    async def shutdown(self) -> None:
        """Stop accepting; in-flight handler coroutines finish on their
        own (worker drain is the caller's job — ``launch/server.py``)."""
        if self._probe_task is not None:
            self._probe_task.cancel()
            self._probe_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        if self._access_fh is not None:
            self._access_fh.close()
            self._access_fh = None

    async def _probe_loop(self, interval_s: float) -> None:
        while True:
            await asyncio.sleep(interval_s)
            try:
                await self.router.probe()
            except Exception:       # a failed probe must not kill serving
                pass

    # -- observability -----------------------------------------------------
    def _observe(self, endpoint: str, status: int, t0: float,
                 stages: Optional[Dict[str, Tuple[float, float]]] = None,
                 request_id: Optional[int] = None) -> None:
        """Count one wire request and record its span from the first
        byte (``t0``), with one child per ``stages`` entry (name -> start,
        end), each carrying the engine's ``request_id`` where it has
        one.  All use explicit timing: they cross ``await``s, so
        concurrent connections on the one transport track must not nest
        into each other."""
        dur = self.clock() - t0
        if self.registry is not None:
            self.registry.counter(
                "transport_requests_total",
                "Wire requests by endpoint and status",
                endpoint=endpoint, status=str(status)).inc()
            self.registry.histogram(
                "transport_request_seconds",
                "Wire request handling time",
                endpoint=endpoint).record(dur)
        if self.tracer.enabled:
            ids = {} if request_id is None else {"request_id": request_id}
            sid = self.tracer.add_span(endpoint, "transport", TID_TRANSPORT,
                                       t0, dur, status=status, **ids)
            for name, (a, b) in (stages or {}).items():
                self.tracer.add_span(name, "transport", TID_TRANSPORT, a,
                                     b - a, parent=sid, **ids)
        if self._access_fh is not None:
            self._access_fh.write(
                f"{time.time():.3f} {endpoint} {status} "
                f"{dur * 1e3:.2f}ms\n")

    # -- connection handling -----------------------------------------------
    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    # a keep-alive connection idles here, outside any span
                    line = await reader.readline()
                    if not line:
                        break        # client closed between requests
                    t0 = self.clock()            # the request's first byte
                    msg = await _read_http_rest(reader, line, self.max_body)
                except PayloadTooLarge as e:
                    # the body was never read: answer and drop the
                    # connection rather than resynchronize mid-stream
                    writer.write(_http_response(
                        413, {"outcome": "bad_request", "error": str(e)},
                        close=True))
                    await writer.drain()
                    self._observe("payload-too-large", 413, t0)
                    break
                except (ValueError, asyncio.IncompleteReadError):
                    break            # malformed framing: drop quietly
                stages = {"read": (t0, self.clock())}
                parts, headers, body = msg
                method, target = parts[0], parts[1]
                path = target.split("?", 1)[0]
                endpoint = f"{method} {path}"
                status, payload, extra, ctype = await self._route(
                    method, path, headers, body, stages)
                close = (headers.get("connection", "").lower() == "close"
                         or status in (413, 503))
                t_enc = self.clock()
                request_id = None
                if isinstance(payload, InferResult):
                    request_id = payload.request_id
                    payload = payload.body()
                raw = _http_response(status, payload, content_type=ctype,
                                     extra_headers=extra, close=close)
                stages["encode"] = (t_enc, self.clock())
                writer.write(raw)
                await writer.drain()
                self._observe(endpoint, status, t0, stages, request_id)
                if close:
                    break
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    # -- routing -----------------------------------------------------------
    async def _route(self, method: str, path: str,
                     headers: Dict[str, str], body: bytes,
                     stages: Dict[str, Tuple[float, float]]):
        """(status, payload, extra_headers, content_type) per endpoint;
        ``/v1/infer`` adds its ``decode`` and ``wait`` to ``stages`` and
        answers an ``InferResult``, encoded by the caller."""
        json_t = "application/json"
        if path == "/healthz":
            if self.draining:
                return 503, {"status": "draining"}, None, json_t
            return 200, {"status": "ok",
                         "workers": self.router.worker_names(),
                         "quarantined": self.router.quarantined()}, \
                None, json_t
        if path == "/metrics":
            text = await self._metrics_text()
            return 200, text, None, "text/plain; version=0.0.4"
        if path == "/metrics.json":
            return 200, await self._metrics_snapshot(), None, json_t
        if path == "/stats":
            return 200, await self.router.stats(), None, json_t
        if path == "/v1/infer":
            if method != "POST":
                return 405, {"error": f"{method} not allowed; POST"}, \
                    None, json_t
            return await self._infer(headers, body, stages) + (json_t,)
        return 404, {"error": f"no such endpoint {path!r}"}, None, json_t

    async def _infer(self, headers: Dict[str, str], body: bytes,
                     stages: Dict[str, Tuple[float, float]]):
        from repro.serve.router import NoWorkersAvailable
        if self.draining:
            return 503, {"outcome": "draining",
                         "error": "server is draining (preemption "
                                  "requested); refusing new requests"}, \
                None
        try:
            t_dec = self.clock()
            images, deadline_s = decode_infer_body(body)
            hdr = headers.get("x-deadline-s")
            if hdr is not None:        # the header wins over the body
                try:
                    deadline_s = float(hdr)
                except ValueError as e:
                    raise BadRequestError(
                        f"X-Deadline-S header {hdr!r} is not a "
                        "number") from e
            t_wait = self.clock()
            stages["decode"] = (t_dec, t_wait)
            res = await self.router.infer(images, deadline_s)
            stages["wait"] = (t_wait, self.clock())
        except BadRequestError as e:
            return 400, {"outcome": "bad_request", "error": str(e)}, None
        except NoWorkersAvailable as e:
            return 503, {"outcome": "unavailable", "error": str(e)}, None
        return res.status, res, res.headers()

    # -- metrics endpoints -------------------------------------------------
    async def _sync_engines(self):
        from repro.obs.metrics import MetricsRegistry
        reg = self.registry if self.registry is not None else \
            MetricsRegistry(max_series=2048)
        await self.router.sync_registry(reg)
        return reg

    async def _metrics_text(self) -> str:
        reg = await self._sync_engines()
        return reg.to_prometheus()

    async def _metrics_snapshot(self) -> dict:
        reg = await self._sync_engines()
        return reg.snapshot()
