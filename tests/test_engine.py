"""Fold-schedule execution engine: cache reuse, dataflow selection,
whole-network compilation equivalence (DESIGN.md §4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (ScheduleCache, ScheduleKey, dataflow_costs,
                               plan_and_dataflow, resolve_execution,
                               select_dataflow)
from repro.core.loopnest import ConvLoopNest, vgg16_conv_layers
from repro.core.mapping import plan_conv_blocks
from repro.models import vgg


# --------------------------------------------------------------------------
# schedule cache: the paper's fold reuse over VGG-16
# --------------------------------------------------------------------------

def test_vgg16_fold_reuse_geometry():
    """13 conv layers collapse to 8 filter-fold geometries: >= 5 hits."""
    cache = ScheduleCache()
    for _, cv in vgg16_conv_layers():
        cache.schedule_for(cv)
    assert cache.stats.hits + cache.stats.misses == 13
    assert cache.distinct <= 8
    assert cache.stats.hits >= 5
    assert cache.stats.replans == 0            # walk order shrinks spatially
    assert cache.stats.hit_rate == pytest.approx(5 / 13)


def test_schedule_key_is_spatial_and_batch_independent():
    cv28 = ConvLoopNest(n=1, nf=512, c=512, r=3, s=3, x=28, y=28,
                        stride=1, pad=1)
    cv14 = dataclasses.replace(cv28, x=14, y=14, n=4)
    assert ScheduleKey.from_loopnest(cv28) == ScheduleKey.from_loopnest(cv14)
    cache = ScheduleCache()
    s28 = cache.schedule_for(cv28)
    s14 = cache.schedule_for(cv14)
    assert s14 is s28                          # exact reuse, plan clamps
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_schedule_replans_when_spatial_grows():
    """Reuse shrinking spatially is exact; growing must re-solve the plan
    so the VMEM working-set bound stays honest."""
    cv14 = ConvLoopNest(n=1, nf=512, c=512, r=3, s=3, x=14, y=14,
                        stride=1, pad=1)
    cv28 = dataclasses.replace(cv14, x=28, y=28)
    cache = ScheduleCache()
    cache.schedule_for(cv14)
    s28 = cache.schedule_for(cv28)
    assert cache.stats.replans == 1
    assert cache.distinct == 1                 # same key, slot re-solved
    assert s28.nest.x == 28


def test_clamped_plan_covers_smaller_layer():
    cv28 = ConvLoopNest(n=1, nf=512, c=512, r=3, s=3, x=28, y=28,
                        stride=1, pad=1)
    plan28 = plan_conv_blocks(cv28)
    clamped = plan28.clamped(nf=512, c=512, p=14)
    g_nf, g_c, g_p = clamped.grid
    assert g_nf * clamped.nf_block >= 512
    assert g_c * clamped.c_block >= 512
    assert g_p * clamped.p_block >= 14
    assert clamped.p_block <= 14


# --------------------------------------------------------------------------
# dataflow selection from perfmodel cost estimates
# --------------------------------------------------------------------------

def test_dataflow_selection_deterministic_over_vgg():
    def select_all():
        cache = ScheduleCache()
        return [cache.schedule_for(cv).dataflow
                for _, cv in vgg16_conv_layers()]

    a, b = select_all(), select_all()
    assert a == b
    assert set(a) <= {"weight_stationary", "output_stationary"}


def test_dataflow_costs_shape_sensitivity():
    """Large spatial extents re-fetch the weight fold per P fold, so
    output-stationary must price in g_p weight reloads; small layers with a
    single P fold prefer output-stationary (single output write)."""
    big = ConvLoopNest(n=1, nf=64, c=64, r=3, s=3, x=224, y=224,
                       stride=1, pad=1)
    plan_big = plan_conv_blocks(big)
    costs = dataflow_costs(big, plan_big)
    assert costs["weight_stationary"] < costs["output_stationary"]
    assert select_dataflow(big, plan_big) == "weight_stationary"

    small = ConvLoopNest(n=1, nf=512, c=512, r=3, s=3, x=14, y=14,
                         stride=1, pad=1)
    plan_small, flow_small = plan_and_dataflow(small)
    g_p = plan_small.clamped(small.nf, small.c, small.p).grid[2]
    assert g_p == 1
    assert flow_small == "output_stationary"


# --------------------------------------------------------------------------
# execution / interpret policy
# --------------------------------------------------------------------------

def test_resolve_execution_policies():
    on_tpu = jax.default_backend() == "tpu"
    mode, interp = resolve_execution("auto")
    if on_tpu:
        assert (mode, interp) == ("pallas", False)
    else:
        assert (mode, interp) == ("reference", False)
    assert resolve_execution("pallas") == ("pallas", not on_tpu)
    assert resolve_execution("reference") == ("reference", False)
    with pytest.raises(ValueError):
        resolve_execution("nope")


# --------------------------------------------------------------------------
# whole-network compilation: numerics + fold reuse end to end
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_vgg():
    params = vgg.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                             img=32, classes=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32))
    ref = np.asarray(vgg.forward(params, x, impl="im2col"))
    return params, x, ref


@pytest.mark.parametrize("policy", ["reference", "pallas", "auto"])
def test_compile_network_matches_im2col_oracle(tiny_vgg, policy):
    params, x, ref = tiny_vgg
    net = vgg.compile_forward(params, img=32, batch=2, policy=policy)
    out = np.asarray(net(params, x))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)
    reuse = net.fold_reuse()
    assert reuse["conv_layers"] == 13
    assert net.distinct_schedules <= 8
    assert reuse["hits"] >= 5


def test_shared_cache_reuses_schedules_across_networks(tiny_vgg):
    """A caller-supplied (even empty) cache must actually be used: a
    second network compiled against it is built entirely from hits, and
    each network's fold_reuse() reports only its own build."""
    params, _, _ = tiny_vgg
    cache = ScheduleCache()
    net_a = vgg.compile_forward(params, img=32, batch=2,
                                policy="reference", cache=cache)
    net_b = vgg.compile_forward(params, img=32, batch=2,
                                policy="reference", cache=cache)
    assert net_a.build_stats.misses == 8 and net_a.build_stats.hits == 5
    assert net_b.build_stats.misses == 0 and net_b.build_stats.hits == 13
    assert cache.distinct == 8


def test_forward_with_schedule_cache_matches_oracle(tiny_vgg):
    """The per-layer forward with a ScheduleCache (cost-selected dataflows,
    cached plans) matches the oracle, reusing schedules across layers."""
    params, x, ref = tiny_vgg
    cache = ScheduleCache()
    out = np.asarray(vgg.forward(params, x, impl="fold_auto", cache=cache))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)
    assert cache.distinct <= 8
    assert cache.stats.hits >= 5


def test_kernel_for_is_memoized(tiny_vgg):
    cache = ScheduleCache()
    cv = ConvLoopNest(n=1, nf=8, c=4, r=3, s=3, x=16, y=16, stride=1, pad=1)
    sched = cache.schedule_for(cv)
    k1 = cache.kernel_for(sched, interpret=True)
    k2 = cache.kernel_for(sched, interpret=True)
    assert k1 is k2
    assert cache.kernel_for(sched, interpret=False) is not k1


def test_fold_auto_impl_matches_reference():
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (1, 4, 10, 10))
    w = jax.random.normal(k2, (6, 4, 3, 3))
    from repro.kernels import conv2d
    ref = np.asarray(conv2d(x, w, stride=1, pad=1, impl="direct"))
    out = np.asarray(conv2d(x, w, stride=1, pad=1, impl="fold_auto"))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# whole-zoo compile counts: fold reuse and one fused launch per conv
# --------------------------------------------------------------------------

# conv_layers, distinct_schedules, hits, misses, replans, pallas_calls of
# a fresh-cache compile at width 0.0625 / 32 px, the same at either
# precision: the precision is in every key, so neither adds a schedule
ZOO_COMPILE_COUNTS = {
    "vgg16": (13, 8, 5, 8, 0, 13),
    "resnet18": (20, 11, 9, 11, 0, 20),
    "mobilenetv2": (52, 27, 25, 27, 0, 52),
}


@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("model", sorted(ZOO_COMPILE_COUNTS))
def test_zoo_compile_counts(model, precision):
    """The counts are exact: a fusion regression (an epilogue op escaping
    its conv's kernel) or a planner change that splits or merges fold
    geometries moves one of them before it moves any latency."""
    from repro.analysis import audit_compiled
    from repro.core.engine import compile_network
    from repro.models.zoo import get_conv_model
    spec = get_conv_model(model)
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=0.0625,
                              img=32, classes=10)
    shape = (2, 3, 32, 32)
    net = compile_network(params, spec.to_graph(), shape, policy="pallas",
                          jit=False, precision=precision)
    assert all(s.key.precision == precision for _, s in net.layer_schedules)
    reuse = net.fold_reuse()
    got = (reuse["conv_layers"], reuse["distinct_schedules"], reuse["hits"],
           reuse["misses"], reuse["replans"],
           audit_compiled(net, params, shape).pallas_calls)
    assert got == ZOO_COMPILE_COUNTS[model]
