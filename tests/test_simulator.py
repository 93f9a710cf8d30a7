"""Fold-schedule execution == convolution semantics (the decomposition
computes the right thing, not just the right counts)."""
import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.folds import PEArray
from repro.core.loopnest import ConvLoopNest, vgg16_conv_layers
from repro.core.simulator import execute_conv_by_folds, simulate_cycles


def _ref(x, w, stride, pad):
    return np.asarray(jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))


@given(n=st.integers(1, 2), nf=st.integers(1, 6), c=st.integers(1, 6),
       rs=st.sampled_from([1, 3]), x=st.integers(5, 10),
       stride=st.sampled_from([1, 2]),
       pe_r=st.sampled_from([2, 4, 8]), pe_c=st.sampled_from([8, 16, 24]))
@settings(max_examples=25, deadline=None)
def test_fold_execution_matches_conv(n, nf, c, rs, x, stride, pe_r, pe_c):
    if pe_c < rs + 1:
        return
    cv = ConvLoopNest(n=n, nf=nf, c=c, r=rs, s=rs, x=x, y=x,
                      stride=stride, pad=rs // 2)
    rng = np.random.default_rng(0)
    xt = rng.standard_normal((n, c, x, x)).astype(np.float32)
    wt = rng.standard_normal((nf, c, rs, rs)).astype(np.float32)
    out = execute_conv_by_folds(xt, wt, cv, PEArray(pe_r, pe_c))
    ref = _ref(xt, wt, stride, rs // 2)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_cycle_report_components_positive():
    cv = vgg16_conv_layers()[3][1]
    rep = simulate_cycles(cv, PEArray(64, 64))
    assert rep.t_wl > 0 and rep.t_mt > 0 and rep.t_op > 0
    assert rep.total == rep.t_wl + rep.t_mt + rep.t_op + rep.t_wb


def test_message_transfer_significant_with_hops():
    """Store-and-forward multicast makes message transfer a major runtime
    component (the paper's §V.C quotes T_MT as dominant; our per-message
    cycle simulator puts it at the same order as compute, and the
    system-level model in perfmodel.system_cycles — which also counts
    injection bandwidth — reproduces the dominance; see test_perfmodel)."""
    total_mt = total_op = total_wl = 0
    for _, cv in vgg16_conv_layers():
        rep = simulate_cycles(cv, PEArray(64, 64), multicast_hops=True)
        total_mt += rep.t_mt
        total_op += rep.t_op
        total_wl += rep.t_wl
    assert total_mt > 0.3 * total_op
    assert total_mt > 5 * total_wl


def test_multicast_hops_flag_reduces_mt():
    cv = vgg16_conv_layers()[5][1]
    with_hops = simulate_cycles(cv, PEArray(32, 32), multicast_hops=True)
    without = simulate_cycles(cv, PEArray(32, 32), multicast_hops=False)
    assert with_hops.t_mt > without.t_mt
