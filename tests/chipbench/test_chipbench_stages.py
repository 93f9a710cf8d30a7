"""The reduction of the server's stage spans (``chipbench/stages.py``) on
a fixed event list, and ``devtrace.reduce_spans`` unmoved by them."""
import pytest

from chipbench import devtrace, stages

WINDOW = (0.5, 3.0)


def events(with_stages: bool):
    """Two forwards in the window and one after it, four requests, and,
    with ``with_stages``, the wire spans and stamps a traced server adds."""
    from repro.obs.trace import TID_DISPATCH, TID_TRANSPORT, Tracer
    tr = Tracer(lambda: 0.0)
    cpu = [(10.0, 10.01), (10.04, 10.05), (11.0, 11.01)]
    for (ts, n), (c0, c1) in zip(((1.0, 6), (1.6, 8), (5.0, 8)), cpu):
        stamps = {"cpu_start_s": c0, "cpu_end_s": c1} if with_stages \
            else {}
        tr.add_span("kernel", "device", TID_DISPATCH, ts, 0.5, bucket=8,
                    n_images=n, **stamps)
    # (start, served_by, inbox_ms, queued_ms)
    for rid, (ts, by, inbox, queued) in enumerate((
            (1.2, "primary", 100.0, 250.0),     # handed over at 1.1
            (1.3, "reference", 0.0, 50.0),      # not the primary path
            (0.9, "primary", 800.0, 900.0),     # handed over at 0.1
            (1.4, "primary", 5.0, None))):      # shed: never formed
        waits = {}
        if with_stages:
            waits = {"inbox_ms": inbox}
            if queued is not None:
                waits["queued_ms"] = queued
        tr.add_span(f"request-{rid}", "request", 1000 + rid, ts, 1.0,
                    request_id=rid, outcome="ok", served_by=by, **waits)
    if with_stages:
        tr.add_span("idle", "serve", 0, 2.1, 0.2)
        tr.add_span("stage", "serve", 0, 1.55, 0.01)
        for ts, endpoint, kids in (
                (1.0, "POST /v1/infer", {"read": 0.05, "decode": 0.02,
                                         "wait": 0.4, "encode": 0.01}),
                (5.0, "POST /v1/infer", {"decode": 0.1, "encode": 0.1}),
                (1.1, "GET /stats", {"read": 0.001, "encode": 0.002})):
            sid = tr.add_span(endpoint, "transport", TID_TRANSPORT, ts, 0.5,
                              status=200, request_id=7)
            for name, dur in kids.items():
                tr.add_span(name, "transport", TID_TRANSPORT, ts, dur,
                            parent=sid, request_id=7)
    return tr.events


def test_codec_queue_wait_and_offcpu_sums():
    got = stages.reduce(events(True), WINDOW)
    assert got["codec_s"] == {"n": 1, "sum": pytest.approx(0.03)}
    assert got["queue_wait_s"] == {"n": 1, "sum": pytest.approx(0.25)}
    # 0.1 s between the first two kernels, 0.03 s of it on the CPU
    assert got["host_offcpu_s"] == {"n": 1, "sum": pytest.approx(0.07)}


def test_a_program_without_stage_spans_gives_nothing():
    got = stages.reduce(events(False), WINDOW)
    assert all(v == {"n": 0, "sum": 0.0} for v in got.values())


def test_reduce_spans_is_unmoved_by_the_stage_spans():
    """The existing per-layer metrics read the same values from a trace
    that holds the stage spans, and ``stages`` adds only new keys."""
    plain = devtrace.reduce_spans(events(False), WINDOW)
    assert devtrace.reduce_spans(events(True), WINDOW) == plain
    assert plain["host_gap_s"] == {"n": 1, "sum": pytest.approx(0.1)}
    assert not set(plain) & set(stages.reduce(events(True), WINDOW))
