"""Structured request-lifecycle tracing (DESIGN.md §11).

A ``Tracer`` records **spans** — named intervals with a category, a
track (``tid``), and key/value args — through an injectable clock, and
exports Chrome trace-event JSON that Perfetto / ``chrome://tracing``
load directly.  The serving stack records one flat span per stage of
the engine thread — ``idle`` (blocked on the inbox), ``submit``,
``admit``, ``form``, ``stage`` (host→device put), ``dispatch``,
``readback``, ``epilogue``, ``complete``, ``resolve`` (futures set),
``degrade`` — so no engine span encloses another and a device idle gap
names the stage that held the thread (a serving worker's ``readback``
runs on a thread of its own, on ``TID_READBACK``).  Beside them: the
``kernel`` span (cat ``device``, from when the device could start the
batch — its dispatch, or the previous batch's readback if that ended
later — to readback done, with the engine thread's CPU clock at both
ends when tracing), one *lifetime* span per
request on its own track, closed at the single terminal accounting
point with the outcome and the request's queue waits in ``args`` — so
the zero-loss invariant ("every submitted request reaches exactly one
of ok/rejected/expired/failed") is visible in the trace itself — and
the HTTP front's spans (``serve/transport.py``): one per wire request
from its first byte, with ``read``/``decode``/``wait``/``encode``
children carrying the engine's ``request_id``.

Same clock as the device.  An enabled ``Tracer`` mirrors every inline
(``begin``/``end``) span outside the ``request`` category into a
``jax.profiler.TraceAnnotation`` of the same name, opened and closed on
the recording thread, so a ``jax.profiler`` trace of the server shows
the host stages on its ``/host:`` plane beside the device ops.  Spans
recorded with explicit timing (``add_span``) and request lifetimes are
not mirrored.

Threads.  The engine, readback and HTTP threads record into one
``Tracer``: span ids come from one shared counter, and open-span
stacks are kept per (thread, track), so concurrent recorders neither
repeat an id nor adopt each other's spans as parents.

Determinism: span IDs are a plain sequence number, and all timestamps
come from the injected ``clock``, so a test driving a fake clock from
one thread gets a byte-identical event list and can assert exact trees
via ``span_tree``.

The no-op path is ``NULL_TRACER`` (a ``NullTracer``): every method is a
``pass``, so instrumented hot paths cost one method call when tracing
is off.  ``tracer.enabled`` lets a caller skip argument construction
entirely.

Chrome trace-event fields emitted (the subset ``validate_trace``
checks): ``name``/``cat``/``ph``/``ts``/``pid``/``tid`` on every event,
``dur`` on complete (``ph="X"``) events, ``s`` scope on instants
(``ph="i"``), ``args`` everywhere.  Timestamps are microseconds, as the
format requires.
"""
from __future__ import annotations

import itertools
import json
import threading
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "SpanHandle",
           "validate_trace", "check_trace_outcomes", "span_tree"]

# Well-known track ids: one per pipeline stage, requests above REQ_TID0.
TID_ENGINE = 0        # engine control: idle/submit/admit/form/stage
TID_DISPATCH = 1      # device dispatch + kernel
TID_COMPLETE = 2      # readback/epilogue/completion/resolve
TID_COMPILE = 3       # compile_network / schedule planning
TID_TRANSPORT = 4     # HTTP front-end: one span per wire request
TID_READBACK = 5      # a serving worker's readback thread
REQ_TID0 = 1000       # request r lives on track REQ_TID0 + r


class SpanHandle:
    """An open span: returned by ``begin``, closed by ``end``."""

    __slots__ = ("id", "name", "cat", "tid", "ts_s", "args", "parent",
                 "thread", "annotation")

    def __init__(self, sid: int, name: str, cat: str, tid: int,
                 ts_s: float, args: Dict[str, Any],
                 parent: Optional[int], thread: int,
                 annotation=None) -> None:
        self.id = sid
        self.name = name
        self.cat = cat
        self.tid = tid
        self.ts_s = ts_s
        self.args = args
        self.parent = parent
        self.thread = thread
        self.annotation = annotation


class Tracer:
    """Span recorder with an injectable clock and deterministic IDs.

    ``clock`` is any zero-arg callable returning seconds (monotonic by
    contract).  Pass a fake in tests; production uses
    ``time.monotonic`` supplied by the caller (this module never
    touches the wall clock on its own).
    """

    enabled = True

    def __init__(self, clock, pid: int = 0) -> None:
        from jax.profiler import TraceAnnotation
        self.clock = clock
        self.pid = int(pid)
        self.events: List[dict] = []
        self._ids = itertools.count(1)     # next() is atomic across threads
        # (thread, tid) -> open-span stack
        self._open: Dict[Tuple[int, int], List[SpanHandle]] = {}
        self._annotation = TraceAnnotation

    # -- span lifecycle ----------------------------------------------------
    def begin(self, name: str, cat: str = "serve", tid: int = TID_ENGINE,
              **args) -> SpanHandle:
        thread = threading.get_ident()
        stack = self._open.setdefault((thread, tid), [])
        parent = stack[-1].id if stack else None
        annotation = None
        if cat != "request":
            annotation = self._annotation(name)
            annotation.__enter__()
        h = SpanHandle(next(self._ids), name, cat, tid, float(self.clock()),
                       dict(args), parent, thread, annotation)
        stack.append(h)
        return h

    def end(self, handle: SpanHandle, discard: bool = False,
            **args) -> None:
        """Close ``handle``.  ``discard=True`` drops the span instead of
        recording it — used for no-work iterations (an idle ``form()``
        call) that would otherwise bury the trace in noise; its profiler
        annotation, already open, is still closed."""
        key = (handle.thread, handle.tid)
        stack = self._open.get(key, [])
        if handle in stack:
            # close any children left open (crash paths) along the way
            while stack and stack[-1] is not handle:
                self.end(stack[-1])
            stack.pop()
            if not stack:
                del self._open[key]
        end_s = None if discard else float(self.clock())
        if handle.annotation is not None:
            handle.annotation.__exit__(None, None, None)
            handle.annotation = None
        if discard:
            return
        handle.args.update(args)
        self.events.append(self._event(
            handle.name, handle.cat, "X", handle.tid, handle.ts_s,
            dur_s=max(0.0, end_s - handle.ts_s), args=handle.args,
            id=handle.id, parent=handle.parent))

    def span(self, name: str, cat: str = "serve", tid: int = TID_ENGINE,
             **args):
        """``with tracer.span(...):`` convenience wrapper."""
        return _SpanCtx(self, name, cat, tid, args)

    def instant(self, name: str, cat: str = "serve",
                tid: int = TID_ENGINE, **args) -> None:
        """A zero-duration event (e.g. a request expiring in the queue,
        an injected fault firing)."""
        self.events.append(self._event(
            name, cat, "i", tid, float(self.clock()), args=dict(args),
            id=next(self._ids), scope="t"))

    def add_span(self, name: str, cat: str, tid: int, ts_s: float,
                 dur_s: float, parent: Optional[int] = None,
                 **args) -> int:
        """Record a complete span with explicit timing — for intervals
        not measurable inline: the device's ``kernel`` interval, a wait
        recorded only once it returned work, and spans that cross an
        ``await`` (concurrent connections on one track would otherwise
        nest into each other).  Returns the span id for use as a later
        ``parent``."""
        sid = next(self._ids)
        self.events.append(self._event(
            name, cat, "X", tid, float(ts_s), dur_s=max(0.0, float(dur_s)),
            args=dict(args), id=sid, parent=parent))
        return sid

    def metadata(self, tid: int, name: str) -> None:
        """Name a track in the viewer (``thread_name`` metadata)."""
        self.events.append({
            "name": "thread_name", "cat": "__metadata", "ph": "M",
            "ts": 0, "pid": self.pid, "tid": int(tid),
            "args": {"name": name},
        })

    # -- export ------------------------------------------------------------
    def _event(self, name: str, cat: str, ph: str, tid: int, ts_s: float,
               dur_s: Optional[float] = None,
               args: Optional[Dict[str, Any]] = None,
               id: Optional[int] = None, parent: Optional[int] = None,
               scope: Optional[str] = None) -> dict:
        ev: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": ph,
            "ts": round(ts_s * 1e6, 3),        # µs, per the format
            "pid": self.pid, "tid": int(tid),
            "args": dict(args or {}),
        }
        if dur_s is not None:
            ev["dur"] = round(dur_s * 1e6, 3)
        if id is not None:
            ev["args"]["span_id"] = id
        if parent is not None:
            ev["args"]["parent_id"] = parent
        if scope is not None:
            ev["s"] = scope
        return ev

    def to_json(self) -> dict:
        """The Chrome trace-event JSON object format."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")


class _SpanCtx:
    __slots__ = ("t", "name", "cat", "tid", "args", "handle")

    def __init__(self, t: Tracer, name: str, cat: str, tid: int,
                 args: Dict[str, Any]) -> None:
        self.t, self.name, self.cat, self.tid = t, name, cat, tid
        self.args = args
        self.handle: Optional[SpanHandle] = None

    def __enter__(self) -> SpanHandle:
        self.handle = self.t.begin(self.name, self.cat, self.tid,
                                   **self.args)
        return self.handle

    def __exit__(self, exc_type, exc, tb) -> None:
        extra = {"error": repr(exc)} if exc is not None else {}
        self.t.end(self.handle, **extra)


class NullTracer:
    """The default recorder: every operation is a no-op, so the
    instrumented paths cost one method dispatch when tracing is off."""

    enabled = False
    events: List[dict] = []

    def begin(self, name, cat="serve", tid=0, **args):
        return None

    def end(self, handle, discard=False, **args):
        pass

    def span(self, name, cat="serve", tid=0, **args):
        return _NULL_CTX

    def instant(self, name, cat="serve", tid=0, **args):
        pass

    def add_span(self, name, cat, tid, ts_s, dur_s, parent=None, **args):
        return 0

    def metadata(self, tid, name):
        pass

    def to_json(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path):
        raise RuntimeError("NullTracer records nothing; construct a "
                           "Tracer to save a trace")


class _NullCtx:
    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        pass


_NULL_CTX = _NullCtx()
NULL_TRACER = NullTracer()


# -- analysis / validation ----------------------------------------------------
def span_tree(trace: dict) -> Dict[Optional[int], List[dict]]:
    """Parent-id -> children (complete spans only), children in
    recording order.  Roots are under key ``None``.  Tests assert exact
    trees against this under a fake clock."""
    tree: Dict[Optional[int], List[dict]] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        parent = ev.get("args", {}).get("parent_id")
        tree.setdefault(parent, []).append(ev)
    return tree


_PH_REQUIRED: Dict[str, tuple] = {
    "X": ("dur",),
    "i": (),
    "M": (),
}


def validate_trace(trace) -> List[str]:
    """Every schema problem in a Chrome trace-event JSON object (empty
    list = valid).  Checks the fields Perfetto requires plus this
    repo's own invariants (span ids unique, parents exist)."""
    problems: List[str] = []
    if not isinstance(trace, dict):
        return [f"trace must be a JSON object, got {type(trace).__name__}"]
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing or non-list 'traceEvents'"]
    seen_ids = set()
    for i, ev in enumerate(evs):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        for k in ("name", "cat", "ph", "ts", "pid", "tid"):
            if k not in ev:
                problems.append(f"{where}: missing {k!r}")
        ph = ev.get("ph")
        if ph not in _PH_REQUIRED:
            problems.append(f"{where}: unknown ph {ph!r}")
        else:
            for k in _PH_REQUIRED[ph]:
                if k not in ev:
                    problems.append(f"{where}: ph={ph} missing {k!r}")
        for k in ("ts", "dur"):
            if k in ev and (isinstance(ev[k], bool)
                            or not isinstance(ev[k], (int, float))
                            or ev[k] < 0):
                problems.append(f"{where}: {k}={ev[k]!r} is not a "
                                "non-negative number")
        args = ev.get("args", {})
        if not isinstance(args, dict):
            problems.append(f"{where}: args is not an object")
            continue
        sid = args.get("span_id")
        if sid is not None:
            if sid in seen_ids:
                problems.append(f"{where}: duplicate span_id {sid}")
            seen_ids.add(sid)
    # parent links must resolve to a recorded span
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            continue
        parent = ev.get("args", {}).get("parent_id") \
            if isinstance(ev.get("args"), dict) else None
        if parent is not None and parent not in seen_ids:
            problems.append(f"event[{i}]: parent_id {parent} does not "
                            "match any span_id")
    return problems


TERMINAL_OUTCOMES = ("ok", "rejected", "expired", "failed")


def check_trace_outcomes(trace: dict, expect_requests: int) -> List[str]:
    """The zero-loss invariant, read off the trace: every submitted
    request's lifetime span (``cat == "request"``) ends with exactly one
    terminal outcome in its args."""
    problems: List[str] = []
    seen = {}
    for ev in trace.get("traceEvents", []):
        if not isinstance(ev, dict) or ev.get("cat") != "request":
            continue
        if ev.get("ph") != "X":
            continue
        args = ev.get("args", {})
        rid = args.get("request_id")
        outcome = args.get("outcome")
        if rid is None:
            problems.append(f"request span {ev.get('name')!r} has no "
                            "request_id")
            continue
        if rid in seen:
            problems.append(f"request {rid}: more than one lifetime span")
        seen[rid] = outcome
        if outcome not in TERMINAL_OUTCOMES:
            problems.append(f"request {rid}: outcome {outcome!r} is not "
                            f"one of {TERMINAL_OUTCOMES}")
    if len(seen) != expect_requests:
        problems.append(f"trace has {len(seen)} request lifetime spans, "
                        f"expected {expect_requests}")
    return problems
