"""The reduction from a profiler trace and Tracer spans to metrics."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import devtrace


def test_union_clip_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert devtrace.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert devtrace.clip(iv, 1.5, 3.2) == [(1.5, 2.0), (3.0, 3.2)]
    assert devtrace.gaps(devtrace.merge(iv), -1.0, 5.0) == [
        (-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


HLO = ('%forward.22 = f32[32,512,14,14]{3,2,1,0:T(8,128)} custom-call('
       'f32[32,512,30,30]{3,2,1,0:T(8,128)} %pad.23), '
       'custom_call_target="tpu_custom_call", frontend_attributes={}')


def test_fold_kernels_are_the_mosaic_custom_calls():
    assert devtrace.is_fold(HLO)
    assert not devtrace.is_fold("%pad.2 = f32[32,64,226,226]{3,2,1,0} pad("
                                "f32[32,64,224,224]{3,2,1,0} %forward.13)")
    assert devtrace.short_name(HLO) == \
        "%forward.22 f32[32,512,14,14] custom-call"


def synthetic():
    """Two forwards of three fold kernels each plus one fusion, on a
    trace clock 100 s behind the monotonic one."""
    ops = []
    t = 1.0
    for _ in range(2):
        for k in range(3):
            ops.append(("/device:TPU:0", f"custom-call.{k}", t, t + 0.1,
                        True))
            t += 0.1
        ops.append(("/device:TPU:0", "fusion.1", t, t + 0.05, False))
        t += 0.25                      # 0.2 s idle after each forward
    syncs = [(0.5, 100.5), (3.0, 103.0)]
    return ops, syncs


def test_busy_idle_and_gaps_on_the_window():
    ops, syncs = synthetic()
    win = (101.0, 102.0)               # trace 1.0 .. 2.0
    host = [("host:form", 101.36, 101.55), ("host:complete", 101.35,
                                            101.37)]
    got = devtrace.reduce_device(ops, syncs, win, host)
    assert got["window_s"] == pytest.approx(1.0)
    busy = 2 * 0.35
    assert got["busy_s"] == pytest.approx(busy)
    assert got["clock_drift_s"] == pytest.approx(0.0)
    label, length = got["idle_gaps"][0]
    assert length == pytest.approx(0.2)
    assert label == "host:form 95%"
    names = dict(got["device_ops"])
    assert names["custom-call.0"] == pytest.approx(0.2)
    assert names["fusion.1"] == pytest.approx(0.1)


def test_gaps_stay_unattributed_when_clocks_drift():
    ops, _ = synthetic()
    got = devtrace.reduce_device(ops, [(0.5, 100.5), (3.0, 103.1)],
                                 (101.0, 102.2), [("host:form", 0, 1e9)])
    assert all(label == "unattributed" for label, _ in got["idle_gaps"])


def test_fold_roofline_maps_events_to_convs_in_order():
    ops, _ = synthetic()
    folds = [(s, e) for _, _, s, e, f in ops if f]
    got = devtrace.fold_roofline(folds, (0.0, 10.0), 3, [0.01, 0.02, 0.03])
    assert got["events"] == 6
    assert got["device_s"] == pytest.approx(0.6)
    assert got["roofline_min_s"] == pytest.approx(0.12)
    only_second = devtrace.fold_roofline(folds, (1.5, 10.0), 3,
                                         [0.01, 0.02, 0.03])
    assert only_second["roofline_min_s"] == pytest.approx(0.06)
    assert devtrace.fold_roofline(folds[:-1], (0.0, 10.0), 3,
                                  [0.01, 0.02, 0.03]) is None


def test_spans_give_host_gaps_images_and_slots():
    from repro.obs.trace import TID_DISPATCH, Tracer
    tr = Tracer(lambda: 0.0)
    tr.add_span("kernel", "device", TID_DISPATCH, 1.0, 0.5, bucket=8,
                n_images=6)
    tr.add_span("kernel", "device", TID_DISPATCH, 1.6, 0.5, bucket=8,
                n_images=8)
    tr.add_span("kernel", "device", TID_DISPATCH, 5.0, 0.5, bucket=8,
                n_images=8)
    tr.add_span("request-1", "request", 1001, 0.9, 1.0)
    tr.add_span("request-2", "request", 1002, 5.0, 1.0)
    got = devtrace.reduce_spans(tr.events, (0.5, 3.0))
    assert got["images"] == 14 and got["slots"] == 16
    assert got["host_gap_s"]["n"] == 1
    assert got["host_gap_s"]["sum"] == pytest.approx(0.1)
    assert got["kernel_spans"] == 2
    assert got["lifetimes_s"] == {"n": 1, "sum": pytest.approx(1.0)}


def test_a_recorded_trace_is_read_with_its_sync_stamps(tmp_path):
    """A real ``.xplane.pb`` from the CPU profiler: the host's sync
    annotations come back with their monotonic stamps (no device plane
    on the CPU, so no device ops)."""
    import glob
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    stamps = []
    for _ in range(2):
        stamps.append(time.monotonic_ns())
        with jax.profiler.TraceAnnotation(devtrace.SYNC,
                                          mono_ns=stamps[-1]):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ops, syncs = devtrace.read_xplane(path[0])
    assert [m for _, m in syncs] == [s * 1e-9 for s in stamps]
    off, drift = devtrace.clock_offset(syncs)
    assert drift < 1e-3
    assert not [o for o in ops if not o[0].startswith("/device:")]
