"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import conv1d_causal, conv2d
from repro.kernels.ref import conv1d_causal_ref, conv2d_direct, conv2d_im2col

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _xla_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


CASES = [
    # (N, C, X, Y, NF, R, S, stride, pad)
    (1, 3, 8, 8, 4, 3, 3, 1, 1),
    (2, 4, 12, 10, 8, 3, 3, 1, 0),
    (1, 8, 9, 9, 16, 3, 3, 2, 1),
    (2, 2, 7, 7, 5, 1, 1, 1, 0),
    (1, 6, 14, 14, 4, 5, 5, 1, 2),
    (1, 4, 11, 13, 3, 3, 5, 2, 2),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("impl", ["fold_ws", "fold_os", "fold_ws_psum",
                                  "im2col", "direct"])
def test_conv2d_matches_xla(case, impl):
    n, c, x_, y_, nf, r, s, stride, pad = case
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (n, c, x_, y_), jnp.float32)
    w = _rand(k2, (nf, c, r, s), jnp.float32)
    ref = _xla_conv(x, w, stride, pad)
    out = conv2d(x, w, stride=stride, pad=pad, impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_dtypes(dtype):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (2, 4, 10, 10), dtype)
    w = _rand(k2, (8, 4, 3, 3), dtype)
    ref = _xla_conv(x, w, 1, 1)
    for impl in ("fold_ws", "fold_os"):
        out = conv2d(x, w, stride=1, pad=1, impl=impl)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("t,d,k", [(16, 8, 4), (33, 16, 4), (8, 5, 3),
                                   (64, 128, 4), (7, 1, 2)])
def test_conv1d_causal_fold_vs_ref(t, d, k):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (2, t, d), jnp.float32)
    w = _rand(k2, (k, d), jnp.float32)
    ref = conv1d_causal_ref(x, w)
    out = conv1d_causal(x, w, impl="fold")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_conv2d_gradients_match_xla():
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (2, 3, 8, 8), jnp.float32)
    w = _rand(k2, (4, 3, 3, 3), jnp.float32)

    def loss_ours(x, w):
        return jnp.sum(conv2d(x, w, stride=1, pad=1, impl="direct") ** 2)

    def loss_xla(x, w):
        return jnp.sum(_xla_conv(x, w, 1, 1) ** 2)

    gx, gw = jax.grad(loss_ours, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), rtol=1e-4,
                               atol=1e-4)


def test_conv2d_strided_gradient():
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (1, 2, 9, 9), jnp.float32)
    w = _rand(k2, (3, 2, 3, 3), jnp.float32)
    g = jax.grad(lambda xx: conv2d(xx, w, 2, 1, impl="direct").sum())(x)
    g_r = jax.grad(lambda xx: _xla_conv(xx, w, 2, 1).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_r), rtol=1e-4,
                               atol=1e-4)


def test_fold_kernel_uses_plan_geometry():
    """The Pallas block plan solves eq (2) under VMEM limits."""
    from repro.core.loopnest import ConvLoopNest
    from repro.core.mapping import VMEM_LIMIT_BYTES, plan_conv_blocks
    cv = ConvLoopNest(n=1, nf=512, c=512, r=3, s=3, x=56, y=56,
                      stride=1, pad=1)
    plan = plan_conv_blocks(cv)
    assert plan.vmem_bytes <= VMEM_LIMIT_BYTES // 2   # half of VMEM
    assert plan.c_block % 128 == 0                  # weight-fold lane tile
    assert plan.nf_block % 8 == 0                   # MXU lane alignment
    g_nf, g_c, g_p = plan.grid
    assert g_nf * plan.nf_block >= cv.nf
    assert g_c * plan.c_block >= cv.c
    assert g_p * plan.p_block >= cv.p


@pytest.mark.parametrize("x_shape,w_shape,kw,name", [
    ((1, 8, 10, 10), (16, 8, 3, 3), {}, "fold_ws_r3s3_st1"),
    ((1, 8, 10, 10), (16, 8, 3, 3), {"dataflow": "output_stationary"},
     "fold_os_r3s3_st1"),
    ((1, 8, 10, 10), (8, 1, 3, 3), {"dataflow": "depthwise", "groups": 8},
     "fold_dw_r3s3_st1"),
    ((1, 8, 9, 9), (16, 8, 1, 1), {"stride": 2}, "fold_ws_r1s1_st2"),
])
def test_fold_kernel_launch_carries_a_stable_name(x_shape, w_shape, kw,
                                                  name):
    """Each launch's ``pallas_call`` is named from its dataflow and
    window, the name a profiler trace finds the kernel by."""
    from repro.kernels.conv2d_ws import conv2d_folded
    x, w = jnp.ones(x_shape), jnp.ones(w_shape)
    jaxpr = jax.make_jaxpr(
        lambda x, w: conv2d_folded(x, w, interpret=True, **kw))(x, w)
    names = [str(e.params["name"]) for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(names) == 1 and names[0].startswith(name)


# --------------------------------------------------------------------------
# row packing: k output rows per tap dot on stride-1 WS/OS launches
# --------------------------------------------------------------------------

_BR = dict(bias=True, relu=True)

# (id, q, c, nf, dataflow, epilogue kwargs, c_block, p_block, groups, int8)
# q is the output row width (3x3 taps over a q+2 padded input; 1x1 where
# the id says so)
PACKED = [
    ("q4_os_bias_relu_cfolds", 4, 16, 16, "output_stationary", _BR, 8, None,
     1, False),
    ("q4_ws_residual", 4, 8, 16, "weight_stationary",
     dict(bias=True, relu=True, residual=True), None, None, 1, False),
    ("q8_ws_pool_cfolds", 8, 16, 8, "weight_stationary",
     dict(bias=True, relu=True, pool="max2"), 8, None, 1, False),
    ("q8_os_bn_relu6", 8, 8, 16, "output_stationary",
     dict(scale=True, relu6=True), None, None, 1, False),
    ("q8_ws_groups2", 8, 16, 16, "weight_stationary", _BR, None, None, 2,
     False),
    ("q8_os_int8", 8, 16, 16, "output_stationary",
     dict(scale=True, relu=True), 8, None, 1, True),
    ("q14_os_pool_pfolds", 14, 8, 16, "output_stationary",
     dict(bias=True, relu=True, pool="max2"), None, 6, 1, False),
    ("q14_ws_residual_pfolds", 14, 8, 8, "weight_stationary",
     dict(bias=True, relu=True, residual=True), None, 7, 1, False),
    # 17 rows of 30 lanes fit 128 channels' window, but do not divide the
    # 20-row P fold
    ("q28_ws_pblock20", 28, 128, 8, "weight_stationary", _BR, None, 20, 1,
     False),
    ("q28_os_bn", 28, 8, 8, "output_stationary", dict(scale=True), None,
     None, 1, False),
    ("q7_1x1_os_bn_relu6", 7, 160, 16, "output_stationary",
     dict(scale=True, relu6=True), None, None, 1, False),
]


def _packed_case(q, c, nf, groups, epi, int8, ksize):
    from repro.core.epilogue import Epilogue
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(q * 31 + c), 4)
    shape_x = (2, c, q + ksize - 1, q + ksize - 1)
    shape_w = (nf, c // groups, ksize, ksize)
    if int8:
        x = jax.random.randint(k1, shape_x, -8, 9).astype(jnp.int8)
        w = jax.random.randint(k2, shape_w, -8, 9).astype(jnp.int8)
    else:
        x, w = _rand(k1, shape_x, jnp.float32), _rand(k2, shape_w,
                                                       jnp.float32)
    epi = Epilogue(**epi)
    vec = {}
    if epi.bias:
        vec["bias"] = _rand(k3, (nf,), jnp.float32)
    if epi.scale:
        vec["scale"] = jnp.linspace(0.5, 1.0, nf) * (0.01 if int8 else 1.0)
        vec["shift"] = _rand(k3, (nf,), jnp.float32)
    if epi.residual:
        vec["residual"] = _rand(k4, (2, nf, q, q), jnp.float32)
    ref = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, precision=jax.lax.Precision.HIGHEST)
    col = (slice(None), None, None)
    if epi.bias:
        ref = ref + vec["bias"][col]
    if epi.scale:
        ref = ref * vec["scale"][col] + vec["shift"][col]
    if epi.residual:
        ref = ref + vec["residual"]
    if epi.relu:
        ref = jnp.maximum(ref, 0.0)
    if epi.relu6:
        ref = jnp.clip(ref, 0.0, 6.0)
    if epi.pool:
        ref = jax.lax.reduce_window(ref, -jnp.inf, jax.lax.max,
                                    (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    return x, w, epi, vec, ref


@pytest.mark.parametrize("case", PACKED, ids=[c[0] for c in PACKED])
def test_packed_rows_match_one_row_per_dot_and_xla(case, monkeypatch):
    """Packing k output rows into one dot per tap gives the one-row-per-dot
    kernel's output and lax.conv's at HIGHEST, through every epilogue,
    depth and P folds, groups and the int8 stream."""
    from repro.core.mapping import ConvBlockPlan
    from repro.kernels import conv2d_ws
    name, q, c, nf, dataflow, epi_kw, c_b, p_b, groups, int8 = case
    x, w, epi, vec, ref = _packed_case(q, c, nf, groups, epi_kw, int8,
                                       1 if "1x1" in name else 3)
    plan = None
    if c_b or p_b:
        plan = ConvBlockPlan(nf_block=nf // groups, c_block=c_b or c // groups,
                             p_block=p_b or q, grid=(1, 1, 1), vmem_bytes=0,
                             groups=groups)

    def run():
        spec = conv2d_ws.fold_kernel_spec(
            x.shape, w.shape, plan=plan, dataflow=dataflow, epilogue=epi,
            groups=groups)
        out = conv2d_ws.conv2d_folded(x, w, plan=plan, dataflow=dataflow,
                                      epilogue=epi, groups=groups,
                                      interpret=True, **vec)
        return spec, np.asarray(out)

    spec, packed = run()
    assert spec.dataflow == dataflow and spec.rows_per_dot > 1
    assert spec.p_block % spec.rows_per_dot == 0
    monkeypatch.setattr(conv2d_ws, "DOT_WINDOW", 0)
    one, rows = run()
    assert one.rows_per_dot == 1
    scale = float(np.abs(ref).max())
    # the same products in the same order; the host's dot may sum a wider
    # window's lanes in another association, so rounding may differ
    np.testing.assert_allclose(packed, rows, rtol=0, atol=2e-6 * scale)
    np.testing.assert_allclose(packed, np.asarray(ref), rtol=0,
                               atol=2e-6 * scale)


# (id, x shape, w shape, kwargs, expected rows per dot)
K_RULE = [
    ("stride2", (1, 8, 18, 18), (8, 8, 3, 3), dict(stride=2), 1),
    ("rows_past_budget", (1, 512, 4, 302), (8, 512, 3, 3), {}, 1),
    ("no_lane_tile_saved_224", (1, 8, 6, 226), (8, 8, 3, 3), {}, 1),
    ("psum", (1, 8, 6, 6), (8, 8, 3, 3),
     dict(dataflow="weight_stationary_psum"), 1),
    ("depthwise", (1, 8, 6, 6), (8, 1, 3, 3),
     dict(dataflow="depthwise", groups=8), 1),
    ("q4_whole_fold", (1, 8, 6, 6), (8, 8, 3, 3), {}, 4),
    ("q14_pooled_even", (1, 8, 16, 16), (8, 8, 3, 3), dict(pool=True), 14),
    ("q28_seven_row_fold", (1, 8, 9, 30), (8, 8, 3, 3), {}, 7),
    ("q56_odd_divisor", (1, 128, 16, 58), (8, 128, 3, 3), {}, 7),
    ("q56_odd_divisor_pooled", (1, 128, 16, 58), (8, 128, 3, 3),
     dict(pool=True), 2),
    ("q56_8_channels", (1, 8, 58, 58), (8, 8, 3, 3), {}, 16),
    ("q56_256_filters", (1, 8, 58, 58), (256, 8, 3, 3), {}, 4),
    ("1x1_one_mxu_pass", (1, 128, 8, 8), (8, 128, 1, 1), {}, 1),
    ("1x1_two_mxu_passes", (1, 160, 7, 7), (8, 160, 1, 1), {}, 7),
]


@pytest.mark.parametrize("case", K_RULE, ids=[c[0] for c in K_RULE])
def test_rows_per_dot_rule(case):
    """k is derived from the launch's shape: 1 where it cannot or need not
    pack; otherwise the most rows whose window fits ``DOT_WINDOW`` and
    that divide the P fold, even under a fused pool.  Every lane offset of a packed tap
    window is static and a row group's dots run over whole 128-lane
    tiles, which the packed accumulator holds one row per group."""
    from repro.analysis.index_check import check_kernel_spec
    from repro.core.epilogue import Epilogue
    from repro.kernels.conv2d_ws import DOT_WINDOW, fold_kernel_spec
    _, xs, ws, kw, want = case
    kw = dict(kw)
    epi = (Epilogue(bias=True, relu=True, pool="max2") if kw.pop("pool",
                                                                   False)
           else None)
    spec = fold_kernel_spec(xs, ws, epilogue=epi, **kw)
    k = spec.rows_per_dot
    assert k == want
    assert k <= spec.p_block and spec.p_block % k == 0
    if epi is not None:
        assert k % 2 == 0
    if k > 1:
        # the packed accumulator: one (nf_b, lanes) tile row per group
        groups, nf_b, lanes = spec.scratch
        assert lanes % 128 == 0 and lanes >= k * spec.wph
        widest = max(spec.plan.c_block, nf_b)
        assert -(-widest // 8) * 8 * lanes <= DOT_WINDOW
        rows = spec.p_pad if spec.dataflow == "weight_stationary" \
            else spec.p_block
        assert groups * k == rows
    assert check_kernel_spec(spec).ok
