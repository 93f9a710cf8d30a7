"""Stage spans of the served path (DESIGN.md §11, §13): one traced HTTP
server, driven over the wire, and its ``Tracer`` read back.

* the engine thread's stage spans are flat — none encloses another — so
  a device idle gap takes the name of the stage that held the thread;
  the readback thread's spans are flat on their own track, and
  consecutive ``kernel`` spans never overlap;
* the transport span starts at a request's first byte, not at the
  keep-alive wait before it, and its ``read``/``decode``/``wait``/
  ``encode`` children carry the engine's ``request_id``;
* every served request's lifetime span carries its queue waits;
* the engine and transport threads record into one Tracer without
  repeating a span id.
"""
import asyncio
import time

import numpy as np
import pytest

from repro.launch.server import start_server
from repro.obs.trace import (TID_COMPLETE, TID_DISPATCH, TID_ENGINE,
                             TID_READBACK, Tracer, validate_trace)
from repro.serve.transport import HttpClient, encode_images_payload

IMG = 32
INFER = "POST /v1/infer"


@pytest.fixture(scope="module")
def traced():
    """A one-worker traced server after a burst of concurrent requests
    and a keep-alive client that pauses 50 ms between two requests."""
    tracer = Tracer(time.monotonic)
    handle = start_server("vgg16", n_workers=1, policy="reference",
                          img=IMG, width_mult=0.0625, buckets=(1, 2, 4),
                          tracer=tracer)
    rng = np.random.default_rng(0)

    def body(n):
        return encode_images_payload(
            rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32))

    async def one(payload):
        client = HttpClient(handle.host, handle.port)
        try:
            return await client.request("POST", "/v1/infer", payload)
        finally:
            await client.close()

    async def burst():
        return await asyncio.gather(*(one(body(1 + i % 3))
                                      for i in range(8)))

    async def paused():
        client = HttpClient(handle.host, handle.port)
        try:
            first = await client.request("POST", "/v1/infer", body(1))
            await asyncio.sleep(0.05)
            second = await client.request("POST", "/v1/infer", body(2))
        finally:
            await client.close()
        return first, second

    try:
        answers = asyncio.run(burst())
        pair = asyncio.run(paused())
    finally:
        handle.stop()
    assert all(status == 200 for status, _ in answers + list(pair))
    return tracer.to_json(), [obj for _, obj in pair]


def spans(trace, **match):
    return [e for e in trace["traceEvents"] if e["ph"] == "X"
            and all(e.get(k) == v for k, v in match.items())]


def test_trace_validates_with_two_threads_recording(traced):
    trace, _ = traced
    assert validate_trace(trace) == []
    assert spans(trace, cat="transport") and spans(trace, name="kernel")


def _assert_sequential(events):
    for a, b in zip(events, events[1:]):
        # each span ends before the next starts (1 ns of slack for the
        # microsecond rounding of ts and dur)
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-3, (a, b)


def test_engine_thread_spans_are_flat(traced):
    trace, _ = traced
    engine = sorted((e for e in trace["traceEvents"] if e["ph"] == "X"
                     and e["tid"] in (TID_ENGINE, TID_DISPATCH,
                                      TID_COMPLETE)
                     and e["cat"] != "device"), key=lambda e: e["ts"])
    names = {e["name"] for e in engine}
    assert {"idle", "submit", "admit", "form", "stage", "dispatch",
            "epilogue", "complete", "resolve"} <= names
    _assert_sequential(engine)          # one thread, no nesting
    assert not [e for e in engine if "parent_id" in e["args"]]
    # the blocking readback runs on the worker's readback thread
    reads = sorted(spans(trace, tid=TID_READBACK), key=lambda e: e["ts"])
    assert reads and {e["name"] for e in reads} == {"readback"}
    _assert_sequential(reads)
    assert not [e for e in reads if "parent_id" in e["args"]]
    _assert_sequential(sorted(spans(trace, name="kernel"),
                              key=lambda e: e["ts"]))


def test_transport_span_starts_at_first_byte(traced):
    trace, (first, second) = traced
    ends = {}
    for obj in (first, second):
        (ep,) = [e for e in spans(trace, name=INFER)
                 if e["args"].get("request_id") == obj["request_id"]]
        kids = {e["name"]: e for e in trace["traceEvents"]
                if e["args"].get("parent_id") == ep["args"]["span_id"]}
        assert set(kids) == {"read", "decode", "wait", "encode"}
        for k in kids.values():
            assert k["args"]["request_id"] == obj["request_id"]
            assert ep["ts"] - 1e-3 <= k["ts"]
            assert k["ts"] + k["dur"] <= ep["ts"] + ep["dur"] + 1e-3
        ends[obj["request_id"]] = (ep["ts"], ep["ts"] + ep["dur"])
    # the 50 ms the client paused lies between the two spans, in neither
    gap_us = ends[second["request_id"]][0] - ends[first["request_id"]][1]
    assert gap_us >= 40e3


def test_queue_waits_on_every_served_request(traced):
    trace, _ = traced
    lives = [e["args"] for e in spans(trace, cat="request")]
    assert len(lives) == 10
    for a in lives:
        assert a["outcome"] == "ok" and a["served_by"] == "primary"
        assert a["queued_ms"] >= a["inbox_ms"] >= 0
