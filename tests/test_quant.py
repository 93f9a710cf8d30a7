"""Int8 quantized fold streaming (core/quant.py + the int8 kernel path):
roundtrip error bounds (property-based), the WS/OS/depthwise int8 kernels
against the dequantized-operand oracle, int32 accumulator safety (kernel
and static verifier), precision-keyed schedule caching and tuning-JSON
compatibility, end-to-end zoo agreement with the fp32 oracle, the jaxpr
audit, and the compression re-export."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.engine import (ScheduleCache, ScheduleKey, compile_network,
                               dataflow_traffic_bytes, stream_bytes_per_elem,
                               traffic_components)
from repro.core.epilogue import Epilogue
from repro.core.loopnest import ConvLoopNest
from repro.core.mapping import plan_conv_blocks
from repro.core.quant import (INT32_ACC_MAX, act_scale, check_precision,
                              default_calib_batch, dequantize_int8,
                              int32_accumulator_bound, quantize_act,
                              quantize_graph, quantize_int8, quantize_weight,
                              requant_affine, requant_epilogue, weight_scales)
from repro.kernels.ops import conv2d_int8


# --------------------------------------------------------------------------
# scheme: roundtrip bounds and scale granularity
# --------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                min_size=1, max_size=64))
def test_roundtrip_error_bounded_by_half_scale(vals):
    x = jnp.asarray(np.asarray(vals, np.float32))
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    # symmetric round-to-nearest: worst case half a quantization step
    assert float(err.max()) <= float(s) / 2 + 1e-9


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
def test_weight_roundtrip_bounded_per_channel(nf, c, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (nf, c, 3, 3))
    wq, scales = quantize_weight(w)
    assert wq.dtype == jnp.int8 and scales.shape == (nf,)
    deq = np.asarray(wq, np.float32) * np.asarray(scales)[:, None, None, None]
    err = np.abs(deq - np.asarray(w))
    for o in range(nf):
        assert float(err[o].max()) <= float(scales[o]) / 2 + 1e-9


def test_per_channel_beats_per_tensor_on_skewed_filters():
    # one loud output channel must not crush the quiet one's resolution
    w = jnp.stack([jnp.full((1, 3, 3), 100.0), jnp.full((1, 3, 3), 0.01)])
    _, scales = quantize_weight(w)
    assert float(scales[0]) > 100 * float(scales[1])


def test_act_scale_is_python_float_and_check_precision():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 8))
    s = act_scale(x)
    assert isinstance(s, float) and s > 0
    q = quantize_act(x, s)
    assert q.dtype == jnp.int8
    check_precision("fp32")
    check_precision("int8")
    with pytest.raises(ValueError):
        check_precision("int4")
    with pytest.raises(ValueError):
        stream_bytes_per_elem("bf16")
    assert stream_bytes_per_elem("int8") == 1
    assert stream_bytes_per_elem("fp32", 4) == 4


def test_requant_epilogue_and_affine_compose():
    epi = Epilogue(bias=True, relu=True, scale=True)
    q = requant_epilogue(epi)
    assert q.scale and not q.bias and q.relu == epi.relu
    dq = jnp.asarray([0.5, 2.0])
    bias = jnp.asarray([1.0, -1.0])
    bn_s = jnp.asarray([2.0, 3.0])
    bn_b = jnp.asarray([0.1, 0.2])
    sc, sh = requant_affine(dq, epi, bias, bn_s, bn_b)
    np.testing.assert_allclose(np.asarray(sc), [1.0, 6.0])
    np.testing.assert_allclose(np.asarray(sh), [2.1, -2.8])
    # bias-only epilogue: scale is the bare dequant, shift is the bias
    sc2, sh2 = requant_affine(dq, Epilogue(bias=True), bias, None, None)
    np.testing.assert_allclose(np.asarray(sc2), np.asarray(dq))
    np.testing.assert_allclose(np.asarray(sh2), np.asarray(bias))


# --------------------------------------------------------------------------
# int8 kernels vs the dequantized-operand oracle
# --------------------------------------------------------------------------

def _oracle(x, w, b, x_scale, stride, pad, epi, groups=1,
            scale=None, shift=None):
    """fp32 conv over the *dequantized* int8 operands + the fp32 epilogue:
    the only error left for the kernel path is arithmetic order."""
    from repro.core.epilogue import apply_epilogue
    wq, ws = quantize_weight(w)
    xq = quantize_act(x, x_scale)
    xd = xq.astype(jnp.float32) * x_scale
    wd = wq.astype(jnp.float32) * ws[:, None, None, None]
    y = jax.lax.conv_general_dilated(
        xd, wd, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)
    return apply_epilogue(y, b, epi, None, scale, shift)


@pytest.mark.parametrize("impl,groups", [
    ("fold_ws", 1), ("fold_os", 1), ("fold_ws", 2), ("fold_os", 2),
])
def test_int8_fold_kernels_match_oracle(impl, groups):
    cv = dict(nf=8, c=8, x=6, y=6, stride=1, pad=1)
    k = jax.random.PRNGKey(42)
    kx, kw, kb = jax.random.split(k, 3)
    x = jax.random.normal(kx, (2, cv["c"], cv["x"], cv["y"]))
    w = jax.random.normal(kw, (cv["nf"], cv["c"] // groups, 3, 3))
    b = jax.random.normal(kb, (cv["nf"],))
    epi = Epilogue(bias=True, relu=True)
    xs = act_scale(x)
    got = conv2d_int8(x, w, b, x_scale=xs, stride=1, pad=1, epilogue=epi,
                      impl=impl, interpret=True, groups=groups)
    want = _oracle(x, w, b, xs, 1, 1, epi, groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_int8_depthwise_matches_oracle():
    c = 8
    k = jax.random.PRNGKey(3)
    kx, kw = jax.random.split(k)
    x = jax.random.normal(kx, (1, c, 6, 6))
    w = jax.random.normal(kw, (c, 1, 3, 3))
    xs = act_scale(x)
    # depthwise always lowers through the dedicated fold_dw kernel (the
    # grouped WS/OS paths require C/G >= 2, same as fp32)
    got = conv2d_int8(x, w, x_scale=xs, stride=1, pad=1,
                      impl="fold_dw", interpret=True, groups=c)
    want = _oracle(x, w, None, xs, 1, 1, None, groups=c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_int8_reference_path_matches_fold_path():
    # the degradation ladder swaps kernels, never numerics: the lax
    # reference path shares the exact same quantization points
    k = jax.random.PRNGKey(5)
    x = jax.random.normal(k, (1, 4, 6, 6))
    w = jax.random.normal(jax.random.fold_in(k, 1), (8, 4, 3, 3))
    xs = act_scale(x)
    fold = conv2d_int8(x, w, x_scale=xs, stride=1, pad=1,
                       impl="fold_os", interpret=True)
    ref = conv2d_int8(x, w, x_scale=xs, stride=1, pad=1, impl="direct")
    np.testing.assert_allclose(np.asarray(fold), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_int8_accumulator_no_overflow_at_depth():
    # saturate every operand to the int8 extreme over a deep reduction:
    # 127*127*cg*r*s must accumulate exactly (int32), not wrap
    cg, r = 2048, 3
    x = jnp.full((1, cg, r, r), 1.0)
    w = jnp.full((4, cg, r, r), 1.0)
    xs = act_scale(x)
    bound = int32_accumulator_bound(cg, r, r)
    assert 0 < bound <= INT32_ACC_MAX
    y = conv2d_int8(x, w, x_scale=xs, stride=1, pad=0,
                    impl="fold_os", interpret=True)
    # dequant of the exact integer 127*127*cg*r*r at scale (1/127)^2
    want = float(bound) * (1.0 / 127.0) ** 2
    np.testing.assert_allclose(np.asarray(y).ravel(),
                               np.full(4, want), rtol=1e-6)


def test_plan_check_flags_accumulator_overflow():
    from repro.analysis.plan_check import check_plan
    cv = ConvLoopNest(n=1, nf=8, c=2 ** 18, r=3, s=3, x=3, y=3,
                      stride=1, pad=0)
    assert int32_accumulator_bound(cv.cg, cv.r, cv.s) > INT32_ACC_MAX
    plan = plan_conv_blocks(cv).clamped(cv.nf, cv.c, cv.p)
    rep = check_plan(cv, plan, precision="int8")
    assert any(f.code == "quant.acc-overflow" for f in rep.findings)
    # the same plan is clean at fp32 and at a safe depth
    assert not any(f.code == "quant.acc-overflow"
                   for f in check_plan(cv, plan).findings)
    safe = ConvLoopNest(n=1, nf=8, c=64, r=3, s=3, x=6, y=6,
                        stride=1, pad=1)
    srep = check_plan(safe, plan_conv_blocks(safe).clamped(
        safe.nf, safe.c, safe.p), precision="int8")
    assert not any(f.code == "quant.acc-overflow" for f in srep.findings)


# --------------------------------------------------------------------------
# precision-keyed schedules, dtype-aware traffic, tuning JSON
# --------------------------------------------------------------------------

def test_schedule_key_carries_precision():
    cv = ConvLoopNest(n=1, nf=16, c=8, r=3, s=3, x=12, y=12,
                      stride=1, pad=1)
    k_fp = ScheduleKey.from_loopnest(cv)
    k_q = ScheduleKey.from_loopnest(cv, "int8")
    assert k_fp != k_q and k_fp.precision == "fp32"
    assert str(k_q).endswith("/int8") and "/int8" not in str(k_fp)
    cache = ScheduleCache()
    a = cache.schedule_for(cv)
    b = cache.schedule_for(cv, precision="int8")
    assert a.key != b.key and cache.distinct == 2


def test_traffic_model_prices_streamed_dtype():
    cv = ConvLoopNest(n=1, nf=16, c=16, r=3, s=3, x=8, y=8,
                      stride=1, pad=1)
    plan = plan_conv_blocks(cv).clamped(cv.nf, cv.c, cv.p)
    fp = dataflow_traffic_bytes(cv, plan)
    q = dataflow_traffic_bytes(cv, plan, precision="int8")
    for df in ("weight_stationary", "output_stationary"):
        cf = traffic_components(cv, plan, df)
        cq = traffic_components(cv, plan, df, precision="int8")
        # weights/activations shrink 4x; the fp32 output does not
        assert cq["weights"] * 4 == cf["weights"]
        assert cq["input"] * 4 == cf["input"]
        assert cq["output"] == cf["output"]
        assert q[df] < fp[df]
    dw = ConvLoopNest(n=1, nf=8, c=8, r=3, s=3, x=8, y=8,
                      stride=1, pad=1, groups=8)
    dplan = plan_conv_blocks(dw).clamped(dw.nf, dw.c, dw.p)
    df_fp = traffic_components(dw, dplan, "depthwise")
    df_q = traffic_components(dw, dplan, "depthwise", precision="int8")
    assert df_q["weights"] * 4 == df_fp["weights"]
    assert df_q["input"] * 4 == df_fp["input"]
    assert df_q["output"] == df_fp["output"]
    # the psum formulation now costs its staging round-trip even at
    # g_c == 1: with one depth fold the partial is written, read back,
    # and the final written — 3x the plain WS output bytes
    g_c = plan.grid[1]
    comp = traffic_components(cv, plan, "weight_stationary_psum")
    base = traffic_components(cv, plan, "weight_stationary")
    assert comp["output"] == (2 * g_c + 1) * base["output"]
    assert fp["weight_stationary_psum"] > fp["weight_stationary"]


def _fake_tuned_cache():
    cache = ScheduleCache()
    cv = ConvLoopNest(n=1, nf=16, c=8, r=3, s=3, x=12, y=12,
                      stride=1, pad=1)
    fake = iter(range(1, 100))
    cache.autotune_for(cv, timer=lambda plan, df: float(next(fake)))
    cache.autotune_for(cv, timer=lambda plan, df: float(next(fake)),
                       precision="int8")
    return cache, cv


def test_tuning_json_roundtrips_precision(tmp_path):
    cache, cv = _fake_tuned_cache()
    path = str(tmp_path / "tune.json")
    assert cache.save_tuning(path) == 2
    fresh = ScheduleCache()
    assert fresh.load_tuning(path) == 2
    got = fresh.schedule_for(cv, precision="int8")
    assert got.source == "loaded" and got.key.precision == "int8"
    assert fresh.schedule_for(cv).key.precision == "fp32"


def test_tuning_json_backward_compat_pre_precision(tmp_path):
    """A cache written before the precision axis existed loads as fp32 —
    all a pre-int8 writer could have measured — instead of rotting."""
    cache, cv = _fake_tuned_cache()
    path = str(tmp_path / "tune.json")
    cache.save_tuning(path)
    with open(path) as f:
        payload = json.load(f)
    old = [e for e in payload["entries"]
           if e["key"].get("precision", "fp32") == "fp32"]
    for e in old:
        e["key"].pop("precision", None)
    payload["entries"] = old
    with open(path, "w") as f:
        json.dump(payload, f)
    fresh = ScheduleCache()
    assert fresh.load_tuning(path) == len(old) == 1
    got = fresh.schedule_for(cv)
    assert got.source == "loaded" and got.key.precision == "fp32"


# --------------------------------------------------------------------------
# graph calibration + end-to-end zoo agreement
# --------------------------------------------------------------------------

def test_quantize_graph_records_every_conv():
    from repro.models import vgg
    params = vgg.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                             img=32, classes=10)
    g = vgg.to_graph()
    recipe = quantize_graph(g, params, default_calib_batch((2, 3, 32, 32)))
    convs = [nd.name for nd in g.nodes if nd.op == "conv"]
    assert len(convs) == 13
    for name in convs:
        assert recipe.scale_for(name) > 0
    from repro.core.graph import GraphError
    with pytest.raises(GraphError):
        recipe.scale_for("not_a_conv")


@pytest.mark.parametrize("model,n_convs", [("vgg16", 13), ("resnet18", 20),
                                           ("mobilenetv2", 52)])
def test_zoo_int8_matches_fp32_oracle(model, n_convs):
    from repro.models.zoo import get_conv_model
    spec = get_conv_model(model)
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                              img=32, classes=10)
    shape = (4, 3, 32, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    net_fp = compile_network(params, spec.to_graph(), shape, policy="pallas")
    net_q = compile_network(params, spec.to_graph(), shape, policy="pallas",
                            precision="int8")
    assert net_q.precision == "int8"
    assert len(net_q.layer_schedules) == n_convs
    assert all(s.key.precision == "int8"
               for _, s in net_q.layer_schedules)
    yf = np.asarray(net_fp(params, x))
    yq = np.asarray(net_q(params, x))
    agree = (yf.argmax(-1) == yq.argmax(-1)).mean()
    assert agree >= 0.98
    # the int8 error is quantization, not divergence: small next to the
    # oracle's logit spread
    spread = float(yf.max() - yf.min())
    assert float(np.abs(yf - yq).max()) <= 0.15 * spread


@pytest.mark.parametrize("model", ["vgg16", "resnet18", "mobilenetv2"])
def test_zoo_int8_stream_bytes_ratio(model):
    """Int8 streams each compiled zoo network's weights and activations
    in at least 3.5x fewer modelled HBM bytes than fp32 (4x per element;
    the per-channel scales and the fp32 output stay wide)."""
    from repro.models.zoo import get_conv_model
    spec = get_conv_model(model)
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                              img=32, classes=10)

    def stream_bytes(precision):
        net = compile_network(params, spec.to_graph(), (2, 3, 32, 32),
                              policy="pallas", jit=False,
                              precision=precision)
        total = 0.0
        for _, sc in net.layer_schedules:
            comp = traffic_components(sc.nest, sc.plan, sc.dataflow,
                                      precision=sc.key.precision)
            total += comp["weights"] + comp["input"]
        return total

    assert stream_bytes("fp32") / stream_bytes("int8") >= 3.5


def test_zoo_int8_reference_policy_matches_pallas_policy():
    from repro.models.zoo import get_conv_model
    spec = get_conv_model("mobilenetv2")
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                              img=32, classes=10)
    shape = (2, 3, 32, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    pal = compile_network(params, spec.to_graph(), shape, policy="pallas",
                          precision="int8")
    ref = compile_network(params, spec.to_graph(), shape, policy="reference",
                          precision="int8")
    np.testing.assert_allclose(np.asarray(pal(params, x)),
                               np.asarray(ref(params, x)),
                               rtol=1e-4, atol=1e-4)


def test_int8_rejects_psum_dataflow():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 6, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 3, 3))
    with pytest.raises(ValueError, match="psum"):
        conv2d_int8(x, w, x_scale=act_scale(x), stride=1, pad=1,
                    impl="fold_ws_psum", interpret=True)


# --------------------------------------------------------------------------
# static verification + jaxpr audit of the int8 lowering
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["vgg16", "resnet18", "mobilenetv2"])
def test_foldlint_clean_on_int8_zoo(model):
    from repro.analysis.foldlint import lint_model
    summary = lint_model(model, precision="int8")
    assert summary["ok"], summary["report"]
    assert summary["precision"] == "int8"
    assert summary["pallas_calls"] == summary["conv_layers"] > 0


def test_jaxpr_audit_one_pallas_call_per_conv_int8():
    from repro.analysis import audit_compiled
    from repro.models import vgg
    params = vgg.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                             img=32, classes=10)
    shape = (1, 3, 32, 32)
    net = compile_network(params, vgg.to_graph(), shape, policy="pallas",
                          jit=False, precision="int8")
    rep = audit_compiled(net, params, shape)
    assert rep.pallas_calls == rep.conv_layers == 13
    assert rep.findings.ok
    # the quantize steps are jitted wrappers, visible but opaque — no
    # 4-D epilogue math escapes the fused kernels
    assert rep.top_counts.get("quantize_act") == 13
    assert rep.top_counts.get("quantize_weight") == 13


def test_compression_reexports_shared_scheme():
    from repro.core import quant
    from repro.distributed import compression
    assert compression.quantize_int8 is quant.quantize_int8
    assert compression.dequantize_int8 is quant.dequantize_int8
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 7))
    rt = compression.int8_roundtrip({"g": x})["g"]
    q, s = quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(rt),
                                  np.asarray(dequantize_int8(q, s)))
