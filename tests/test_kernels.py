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
