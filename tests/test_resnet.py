"""ResNet-18 through the streaming-graph lowering: oracle numerics,
gradients through the fused residual VJP, one pallas_call per conv
(jaxpr-asserted), stride-2 / 1x1 ScheduleKey coverage, per-model
fold-reuse stats, and serving equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit_compiled
from repro.core.engine import ScheduleCache
from repro.models import resnet

IMG, WIDTH, CLASSES = 32, 0.0625, 10


@pytest.fixture(scope="module")
def tiny_resnet():
    params = resnet.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                                img=IMG, classes=CLASSES)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, IMG, IMG))
    ref = np.asarray(resnet.forward(params, x, impl="im2col"))
    return params, x, ref


# --------------------------------------------------------------------------
# compiled forward vs the im2col/XLA reference oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["reference", "pallas", "auto"])
def test_compile_forward_matches_im2col_oracle(tiny_resnet, policy):
    params, x, ref = tiny_resnet
    net = resnet.compile_forward(params, img=IMG, batch=2, policy=policy)
    out = np.asarray(net(params, x))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)
    reuse = net.fold_reuse()
    assert reuse["conv_layers"] == resnet.n_convs() == 20
    assert reuse["distinct_schedules"] == 11    # per-model fold reuse
    assert reuse["hits"] == 9


def test_forward_matches_xla_reference(tiny_resnet):
    params, x, ref = tiny_resnet
    out = np.asarray(resnet.forward(params, x, impl="xla"))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


def test_gradients_flow_through_fused_residual_vjp(tiny_resnet):
    """Grads of the fused pallas network (residual epilogue custom VJP
    included) match grads of the reference walk, for params and input."""
    params, x, _ = tiny_resnet
    net = resnet.compile_forward(params, img=IMG, batch=2, policy="pallas",
                                 jit=False)

    def loss_fused(p, xx):
        return jnp.sum(net.apply(p, xx) ** 2)

    def loss_ref(p, xx):
        return jnp.sum(resnet.forward(p, xx, impl="direct") ** 2)

    (gp_f, gx_f) = jax.grad(loss_fused, argnums=(0, 1))(params, x)
    (gp_r, gx_r) = jax.grad(loss_ref, argnums=(0, 1))(params, x)

    def close(a, b, msg, tol=1e-5):
        # scale-aware: the unnormalized 20-conv trunk drives activations
        # (and grads) to ~1e9, so elementwise rtol drowns in fp32
        # cancellation noise; measured agreement is ~3e-7 of array scale
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * (np.abs(b).max() + 1e-30),
                                   err_msg=msg)

    close(gx_f, gx_r, "dL/dx")
    for name in ("stem", "s2b0_down", "s4b1_c2", "fc"):
        for leaf in ("w", "b"):
            close(gp_f[name][leaf], gp_r[name][leaf], f"{name}/{leaf}")


# --------------------------------------------------------------------------
# every residual block lowers to fused pallas_calls (jaxpr-asserted)
# --------------------------------------------------------------------------

def test_fused_network_single_pallas_call_per_conv(tiny_resnet):
    """The fused net's jaxpr has exactly n_convs()=20 pallas_calls and no
    standalone residual add, ReLU, or pool between them: each residual
    block is its convs' fused kernels and nothing else.  Asserted through
    the structured jaxpr auditor (``repro.analysis.audit_compiled``)."""
    params, _, _ = tiny_resnet
    net = resnet.compile_forward(params, img=IMG, batch=1, policy="pallas",
                                 jit=False)
    shape = (1, 3, IMG, IMG)
    audit = audit_compiled(net, params, shape)
    assert audit.ok, "\n".join(map(str, audit.findings))
    assert audit.pallas_calls == resnet.n_convs() == 20
    assert audit.top("custom_jvp_call") == 0     # no standalone relu
    assert audit.top("reduce_max") == 0          # no standalone pool
    # only the fc head's bias add is a top-level add — the 8 residual
    # shortcut adds all flush inside their conv's pallas_call
    assert audit.top("add") == 1
    unfused = resnet.compile_forward(params, img=IMG, batch=1,
                                     policy="pallas", jit=False,
                                     fuse_epilogues=False)
    audit_un = audit_compiled(unfused, params, shape)
    assert audit_un.pallas_calls == 20
    assert audit_un.top("add") == 1 + 20 + 8     # fc + biases + shortcuts
    assert audit_un.top("custom_jvp_call") == 17  # stem + 2 per block


# --------------------------------------------------------------------------
# ScheduleKey coverage: stride>1 and R=S=1 paths
# --------------------------------------------------------------------------

def test_schedule_keys_cover_stride2_and_1x1(tiny_resnet):
    params, _, _ = tiny_resnet
    net = resnet.compile_forward(params, img=IMG, batch=1, policy="pallas")
    keys = {k for _, k in net.layer_keys}
    assert any(k.stride == 2 and k.r == k.s == 3 for k in keys)
    assert any(k.stride == 2 and k.r == k.s == 1 for k in keys)
    downs = [(n, k) for n, k in net.layer_keys if n.endswith("_down")]
    assert len(downs) == 3 and all(k.r == k.s == 1 for _, k in downs)
    # the two stride flavours are distinct schedule identities
    assert net.distinct_schedules == 11


def test_schedule_cache_shared_across_models(tiny_resnet):
    """One ScheduleCache serves both registered models, and at matched
    widths their geometries overlap — the later model compiles with free
    cross-model cache hits."""
    from repro.models import vgg
    params_r, _, _ = tiny_resnet
    params_v = vgg.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                               img=IMG, classes=CLASSES)
    cache = ScheduleCache()
    net_r = resnet.compile_forward(params_r, img=IMG, batch=1,
                                   policy="reference", cache=cache)
    net_v = vgg.compile_forward(params_v, img=IMG, batch=1,
                                policy="reference", cache=cache)
    keys_r = {k for _, k in net_r.layer_keys}
    keys_v = {k for _, k in net_v.layer_keys}
    assert cache.distinct == len(keys_r | keys_v) == 14
    # at matched widths the models *share* 5 stride-1 3x3 geometries —
    # cross-model fold reuse: vgg compiles with 5 free hits from resnet
    assert len(keys_r & keys_v) == 5
    assert net_r.build_stats.misses == 11
    assert net_v.build_stats.misses == 3 and net_v.build_stats.hits == 10


# --------------------------------------------------------------------------
# serving: the same continuous-batching engine, model-agnostic
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["auto", "pallas"])
def test_serving_bitwise_equals_direct_forward(tiny_resnet, policy):
    """Per request, served logits are bitwise-equal to a direct
    ``compile_forward`` of the same (unpadded) images — padding and
    packing are pure batching concerns.  (Single-image requests are
    checked to tolerance instead: XLA specializes the batch-1 fc matmul
    into a differently-rounded program, independent of the batcher.)"""
    from repro.serve.vision import VisionEngine
    params, _, _ = tiny_resnet
    rng = np.random.default_rng(3)
    sizes = (3, 1, 2)
    imgs = [rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)
            for n in sizes]
    eng = VisionEngine(params, resnet.to_graph(), img=IMG, policy=policy,
                       buckets=(2, 4))
    reqs = [eng.submit(im) for im in imgs]
    eng.run()
    for req, im in zip(reqs, imgs):
        direct = resnet.compile_forward(params, img=IMG,
                                        batch=im.shape[0], policy=policy,
                                        cache=eng.compiler.cache)
        want = np.asarray(direct(params, jnp.asarray(im)))
        assert req.done and req.logits.shape == (im.shape[0], CLASSES)
        if im.shape[0] > 1:
            np.testing.assert_array_equal(req.logits, want, err_msg=req.rid)
        else:
            np.testing.assert_allclose(req.logits, want, rtol=1e-5)


def test_bucket_compiler_pay_once_across_buckets(tiny_resnet):
    params, _, _ = tiny_resnet
    comp = resnet.bucket_compiler(params, img=IMG, policy="auto")
    comp.network_for(1)
    misses = comp.cache.stats.misses
    assert comp.cache.distinct == 11
    n2 = comp.network_for(4)
    assert comp.cache.stats.misses == misses     # batch excluded from keys
    assert n2.build_stats.hits == len(n2.layer_schedules)
