"""Grouped/depthwise convolution through the fold-schedule engine:
kernel-level oracle checks against ``lax.conv_general_dilated``
(feature_group_count), BN-folding bitwise invariance, gradients through
the inverted-residual VJP, MobileNetV2 end-to-end + serving equivalence,
and tuning-JSON forward/backward compatibility for the ``groups`` axis."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit_compiled
from repro.core.engine import (ScheduleCache, ScheduleKey,
                               tuning_candidates)
from repro.core.loopnest import ConvLoopNest
from repro.kernels.ops import conv2d
from repro.models import mobilenet

IMG, WIDTH, CLASSES = 32, 0.0625, 10


def _randomize_bn(params, seed=7):
    """Give every BN entry non-trivial statistics so the scale/shift fold
    is exercised (init stats are identity)."""
    rng = np.random.default_rng(seed)
    for name, leaf in params.items():
        if not name.endswith("_bn"):
            continue
        n = leaf["gamma"].shape[0]
        leaf["gamma"] = jnp.asarray(1.0 + 0.2 * rng.standard_normal(n),
                                    jnp.float32)
        leaf["beta"] = jnp.asarray(0.2 * rng.standard_normal(n), jnp.float32)
        leaf["mean"] = jnp.asarray(0.3 * rng.standard_normal(n), jnp.float32)
        leaf["var"] = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    return params


@pytest.fixture(scope="module")
def tiny_mnv2():
    params = _randomize_bn(mobilenet.init_params(
        jax.random.PRNGKey(0), width_mult=WIDTH, img=IMG, classes=CLASSES))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, IMG, IMG))
    ref = np.asarray(mobilenet.forward(params, x, impl="xla"))
    return params, x, ref


# --------------------------------------------------------------------------
# kernel level: grouped/depthwise fold kernels vs the lax oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("c,nf,g,r,stride,pad,hw", [
    (8, 16, 4, 3, 1, 1, 13),     # grouped 3x3, odd width
    (12, 12, 3, 3, 2, 1, 17),    # grouped 3x3 stride 2, odd width
    (6, 18, 2, 1, 1, 0, 8),      # grouped 1x1 (ResNeXt-style projection)
    (16, 16, 16, 3, 1, 1, 9),    # depthwise, odd width
    (10, 10, 10, 3, 2, 1, 15),   # depthwise stride 2, odd width
    (24, 24, 24, 3, 2, 1, 16),   # depthwise stride 2, even width
])
def test_grouped_kernels_match_lax_oracle(c, nf, g, r, stride, pad, hw):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, c, hw, hw)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((nf, c // g, r, r)), jnp.float32)
    want = np.asarray(jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=g))
    impls = ["fold_dw"] if g == c == nf else ["fold_ws", "fold_os"]
    for impl in impls + ["direct", "fold_auto"]:
        got = np.asarray(conv2d(x, w, stride=stride, pad=pad, impl=impl,
                                interpret=True, groups=g))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=impl)


def test_depthwise_selects_dedicated_dataflow():
    """groups == C == N_F resolves to the no-reduction kernel: dataflow
    'depthwise', a single nf fold in the grid, and a ``ScheduleKey``
    distinct from the dense geometry of the same tensor shape."""
    cache = ScheduleCache()
    cv = ConvLoopNest(n=1, nf=16, c=16, r=3, s=3, x=16, y=16,
                      stride=1, pad=1, groups=16)
    sched = cache.schedule_for(cv)
    assert cv.depthwise
    assert sched.dataflow == "depthwise" and sched.impl() == "fold_dw"
    assert sched.plan.grid[0] == 1 and sched.plan.groups == 16
    assert list(sched.cost_dict) == ["depthwise"]
    dense = cache.schedule_for(dataclasses.replace(cv, groups=1))
    assert dense.key != sched.key          # groups is schedule identity
    assert cache.distinct == 2


def test_grouped_tuning_candidates_respect_group_boundaries():
    cv = ConvLoopNest(n=1, nf=24, c=12, r=3, s=3, x=9, y=9,
                      stride=1, pad=1, groups=3)
    cands = tuning_candidates(cv)
    assert cands, "no candidates raced"
    for label, plan, df in cands:
        assert cv.nfg % plan.nf_block == 0, (label, plan)
        assert cv.cg % plan.c_block == 0, (label, plan)
        assert df in ("weight_stationary", "output_stationary")
    dw = ConvLoopNest(n=1, nf=16, c=16, r=3, s=3, x=9, y=9,
                      stride=1, pad=1, groups=16)
    assert all(df == "depthwise" for _, _, df in tuning_candidates(dw))


# --------------------------------------------------------------------------
# MobileNetV2 end-to-end through the shared graph lowering
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["reference", "pallas", "auto"])
def test_compile_forward_matches_lax_oracle(tiny_mnv2, policy):
    params, x, ref = tiny_mnv2
    net = mobilenet.compile_forward(params, img=IMG, batch=2, policy=policy)
    out = np.asarray(net(params, x))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)
    reuse = net.fold_reuse()
    assert reuse["conv_layers"] == mobilenet.n_convs() == 52
    assert reuse["distinct_schedules"] == 27
    assert reuse["hits"] == 25


def test_schedule_keys_cover_grouped_geometry(tiny_mnv2):
    params, _, _ = tiny_mnv2
    net = mobilenet.compile_forward(params, img=IMG, batch=1,
                                    policy="pallas")
    keys = {k for _, k in net.layer_keys}
    dw_keys = {k for k in keys if k.groups > 1}
    assert dw_keys and all(k.groups == k.c == k.nf for k in dw_keys)
    assert any(k.stride == 2 for k in dw_keys)      # strided depthwise
    assert any(k.r == k.s == 1 and k.groups == 1 for k in keys)  # 1x1s
    by_name = dict(net.layer_schedules)
    assert all(by_name[f"{n}_dw"].dataflow == "depthwise"
               for n, *_ in mobilenet.block_specs())


def test_fused_network_single_pallas_call_per_conv(tiny_mnv2):
    """The fused net is exactly n_convs()=52 pallas_calls with no
    standalone BN, ReLU6, or residual add between them: the whole
    inverted-residual chain (expand -> depthwise -> project+residual)
    flushes inside its convs' kernels.  The only top-level tensor math
    left is the per-layer BN statistic fold (rank-1 vectors) and the
    head."""
    params, _, _ = tiny_mnv2
    net = mobilenet.compile_forward(params, img=IMG, batch=1,
                                    policy="pallas", jit=False)
    shape = (1, 3, IMG, IMG)
    # the structured auditor owns the 4-D filtering and jit-name
    # resolution these assertions used to hand-roll (rank-1 BN-vector
    # folds and the 2-D head don't count; jnp.clip traces as a jit eqn
    # named 'clip')
    audit = audit_compiled(net, params, shape)
    assert audit.ok, "\n".join(map(str, audit.findings))
    assert audit.pallas_calls == mobilenet.n_convs() == 52
    assert audit.top("custom_jvp_call") == 0       # no standalone relu
    assert audit.top("reduce_max") == 0            # no standalone pool
    # no standalone relu6 and no standalone residual add or BN affine:
    # nothing 4-D escapes the kernels
    assert all(audit.op4d(p) == 0
               for p in ("clip", "max", "min", "add", "mul"))
    unfused = mobilenet.compile_forward(params, img=IMG, batch=1,
                                        policy="pallas", jit=False,
                                        fuse_epilogues=False)
    audit_un = audit_compiled(unfused, params, shape)
    assert audit_un.pallas_calls == 52
    # standalone relu6s: stem + head + 2 per block (1 for the t=1 block)
    assert audit_un.op4d("clip") == 35
    # one BN shift add per conv + the residual skips
    assert audit_un.op4d("add") == 52 + mobilenet.n_residual_adds()


def test_bn_folding_bitwise_invariance(tiny_mnv2):
    """Fusing batch-norm into the conv epilogue is a scheduling decision,
    not a numerics change: the fused net (BN as in-kernel scale/shift) is
    bitwise-equal to the unfused one (standalone XLA batchnorm ops), with
    randomized BN statistics."""
    params, x, _ = tiny_mnv2
    fused = mobilenet.compile_forward(params, img=IMG, batch=2,
                                      policy="pallas")
    unfused = mobilenet.compile_forward(params, img=IMG, batch=2,
                                        policy="pallas",
                                        fuse_epilogues=False,
                                        cache=fused.cache)
    np.testing.assert_array_equal(np.asarray(fused(params, x)),
                                  np.asarray(unfused(params, x)))


def test_gradients_through_inverted_residual_vjp(tiny_mnv2):
    """Grads of the fused pallas network — including through the folded
    BN scale/shift and the fused residual — match the reference walk, for
    conv weights, BN statistics, and the input."""
    params, x, _ = tiny_mnv2
    net = mobilenet.compile_forward(params, img=IMG, batch=2,
                                    policy="pallas", jit=False)

    def loss_fused(p, xx):
        return jnp.mean(net.apply(p, xx) ** 2)

    def loss_ref(p, xx):
        return jnp.mean(mobilenet.forward(p, xx, impl="direct") ** 2)

    (gp_f, gx_f) = jax.grad(loss_fused, argnums=(0, 1))(params, x)
    (gp_r, gx_r) = jax.grad(loss_ref, argnums=(0, 1))(params, x)

    def close(a, b, msg, tol=1e-5):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * (np.abs(b).max() + 1e-30),
                                   err_msg=msg)

    close(gx_f, gx_r, "dL/dx")
    for name in ("stem", "b1_exp", "b3_dw", "b3_proj", "head"):
        close(gp_f[name]["w"], gp_r[name]["w"], f"{name}/w")
        for leaf in ("gamma", "beta", "mean", "var"):
            close(gp_f[f"{name}_bn"][leaf], gp_r[f"{name}_bn"][leaf],
                  f"{name}_bn/{leaf}")
    close(gp_f["fc"]["w"], gp_r["fc"]["w"], "fc/w")


# --------------------------------------------------------------------------
# serving: the same continuous-batching engine, grouped models included
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["auto", "pallas"])
def test_serving_bitwise_equals_direct_forward(tiny_mnv2, policy):
    """Per request, served logits are bitwise-equal to a direct
    ``compile_forward`` of the same (unpadded) images.  (Single-image
    requests are checked to tolerance: XLA specializes the batch-1 head
    matmul into a differently-rounded program, independent of the
    batcher — same caveat as the ResNet suite.)"""
    from repro.serve.vision import VisionEngine
    params, _, _ = tiny_mnv2
    rng = np.random.default_rng(3)
    sizes = (3, 1, 2)
    imgs = [rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)
            for n in sizes]
    eng = VisionEngine(params, mobilenet.to_graph(), img=IMG, policy=policy,
                       buckets=(2, 4))
    reqs = [eng.submit(im) for im in imgs]
    eng.run()
    for req, im in zip(reqs, imgs):
        direct = mobilenet.compile_forward(params, img=IMG,
                                           batch=im.shape[0], policy=policy,
                                           cache=eng.compiler.cache)
        want = np.asarray(direct(params, jnp.asarray(im)))
        assert req.done and req.logits.shape == (im.shape[0], CLASSES)
        if im.shape[0] > 1:
            np.testing.assert_array_equal(req.logits, want, err_msg=req.rid)
        else:
            np.testing.assert_allclose(req.logits, want, rtol=1e-5)


def test_serving_summary_mobilenetv2():
    from repro.serve.vision import serving_summary
    d = serving_summary("mobilenetv2", requests=5, img=IMG,
                        width_mult=WIDTH, policy="auto", buckets=(1, 2, 4),
                        seed=11)
    assert d["workload"]["model"] == "mobilenetv2"
    assert d["requests"] == 5 and d["images"] >= 5 and d["kips"] > 0
    assert d["compile"]["distinct_schedules"] == 27


@pytest.mark.parametrize("strides,stem_stride",
                         [(mobilenet.CIFAR, 1), (mobilenet.IMAGENET, 2)],
                         ids=["cifar", "imagenet"])
def test_zoo_registers_mobilenetv2(strides, stem_stride):
    """The model's registered name selects its stride table."""
    from repro.models.zoo import conv_model_names, get_conv_model
    assert strides.name in conv_model_names()
    spec = get_conv_model(strides.name)
    g = spec.to_graph()
    assert g.name == strides.name
    assert sum(1 for nd in g if nd.op == "conv") == mobilenet.n_convs()
    stem = next(nd for nd in g if nd.op == "conv")
    assert (stem.name, stem.stride, stem.pad) == ("stem", stem_stride, 1)


# --------------------------------------------------------------------------
# the published network (Table 2, 224 px, 1000 classes) and its boot report
# --------------------------------------------------------------------------

def _conv_inputs(strides, img, monkeypatch):
    """(input NCHW shape, OIHW weight shape, stride) of every conv the
    engine's forward runs, in order (reference mode, batch 1)."""
    import functools

    from repro.core import engine
    params = jax.eval_shape(functools.partial(
        mobilenet.init_params, width_mult=1.0, img=img, classes=1000),
        jax.random.PRNGKey(0))
    net = engine.compile_network(params, mobilenet.to_graph(strides),
                                 (1, 3, img, img), policy="reference",
                                 jit=False)
    seen = []
    step = engine._conv_step

    def spy(x, w, *a, stride, **kw):
        seen.append((x.shape, w.shape, stride))
        return step(x, w, *a, stride=stride, **kw)
    monkeypatch.setattr(engine, "_conv_step", spy)
    out = jax.eval_shape(net.apply, params,
                         jax.ShapeDtypeStruct((1, 3, img, img), jnp.float32))
    assert out.shape == (1, 1000)
    return seen


def test_published_table_downsamples_224_to_7(monkeypatch):
    seen = list(_conv_inputs(mobilenet.IMAGENET, 224, monkeypatch))
    assert len(seen) == mobilenet.n_convs(mobilenet.IMAGENET) == 52
    assert sum(1 for _, w, _ in seen if w[1] == 1) == 17     # depthwise
    assert mobilenet.n_residual_adds(mobilenet.IMAGENET) == 10
    sizes = []
    for x, _, stride in seen:
        if not sizes or sizes[-1] != x[2]:
            sizes.append(x[2])
    assert sizes == [224, 112, 56, 28, 14, 7]
    assert seen[0][2] == 2 and seen[-1][0][1:] == (320, 7, 7)
    # the CIFAR table is the same blocks with two strides dropped
    cifar = _conv_inputs(mobilenet.CIFAR, 32, monkeypatch)
    assert [w for _, w, _ in cifar] == [w for _, w, _ in seen]
    assert sorted({x[2] for x, _, _ in cifar}) == [4, 8, 16, 32]
    fused = mobilenet.to_graph(mobilenet.IMAGENET)
    assert sum(1 for nd in fused if nd.op == "residual_add") == 10


def test_published_param_count():
    """3,504,872 parameters at width 1.0 (conv and fc weights, fc bias,
    BN gamma and beta); the BN running statistics are buffers."""
    from repro.models.zoo import get_conv_model
    spec = get_conv_model("mobilenetv2_imagenet")
    shapes = jax.eval_shape(lambda k: spec.init_params(
        k, width_mult=1.0, img=224), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = sum(int(np.prod(a.shape)) for path, a in leaves
                if path[-1].key not in ("mean", "var"))
    assert count == 3_504_872
    assert shapes["fc"]["w"].shape == (1280, 1000)


def test_published_engine_forward_matches_reference_walk():
    """The fold kernels (interpreted) against the graph-free walk, on the
    published stride table at a small width and image."""
    from repro.models import zoo
    img = 64
    params = _randomize_bn(mobilenet.init_params(
        jax.random.PRNGKey(4), width_mult=0.25, img=img, classes=1000))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, img, img))
    net = zoo.compile_forward("mobilenetv2_imagenet", params, img=img,
                              batch=2, policy="pallas")
    got = np.asarray(net(params, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mobilenet.forward(params, x, impl="xla",
                                            strides=mobilenet.IMAGENET))
    assert got.shape == (2, 1000)
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_boot_report_counts_fold_launches_by_dataflow():
    """The boot report reads the compiled forward's launches: 17 of the
    52 convs run on the depthwise kernel, and all 52 take one output row
    per tap dot at this width; none where the convs run as XLA
    convolutions."""
    import types

    from repro.launch.server import boot_report
    from repro.models.zoo import get_conv_model
    from repro.serve.vision import VisionEngine
    spec = get_conv_model("mobilenetv2_imagenet")
    params = spec.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                              img=64)

    def report(policy):
        eng = VisionEngine(params, spec.to_graph(), img=64, policy=policy,
                           buckets=(2,))
        worker = types.SimpleNamespace(
            worker=types.SimpleNamespace(engine=eng))
        before = boot_report([worker])["fold_launches"]
        eng.compiler.network_for(2)
        return before, boot_report([worker])
    before, rep = report("pallas")
    assert before == {}
    assert rep["mode"] == "pallas"
    launches = rep["fold_launches"]
    assert launches["depthwise"] == 17
    assert sum(launches.values()) == 52
    assert set(launches) <= {"weight_stationary", "output_stationary",
                             "depthwise"}
    # launches by output rows per tap dot: at this width every launch is
    # depthwise, strided or a 1x1 over at most 128 channels, one row a dot
    assert rep["fold_rows_per_dot"] == {1: 52}
    ref = report("reference")[1]
    assert ref["fold_launches"] == ref["fold_rows_per_dot"] == {}


# --------------------------------------------------------------------------
# tuning-JSON forward/backward compatibility across the groups axis
# --------------------------------------------------------------------------

def _tuned_cache():
    cache = ScheduleCache()
    dense = ConvLoopNest(n=1, nf=16, c=8, r=3, s=3, x=12, y=12,
                         stride=1, pad=1)
    dw = ConvLoopNest(n=1, nf=8, c=8, r=3, s=3, x=12, y=12,
                      stride=1, pad=1, groups=8)
    fake = iter(range(1, 100))
    for cv in (dense, dw):
        cache.autotune_for(cv, timer=lambda plan, df: float(next(fake)))
    return cache, dense, dw


def test_tuning_json_roundtrip_with_groups(tmp_path):
    cache, dense, dw = _tuned_cache()
    path = str(tmp_path / "tune.json")
    assert cache.save_tuning(path) == 2
    fresh = ScheduleCache()
    assert fresh.load_tuning(path) == 2
    for cv in (dense, dw):
        a = cache.schedule_for(cv)
        b = fresh.schedule_for(cv)
        assert b.source == "loaded" and b.tuned
        assert (a.key, a.plan, a.dataflow) == (b.key, b.plan, b.dataflow)
    assert fresh.schedule_for(dw).plan.groups == 8


def test_tuning_json_backward_compat_pre_groups(tmp_path):
    """A cache written before the groups axis existed (no 'groups' field
    anywhere) loads with groups=1 instead of being skipped as rotted."""
    cache, dense, _ = _tuned_cache()
    path = str(tmp_path / "tune.json")
    cache.save_tuning(path)
    with open(path) as f:
        payload = json.load(f)
    old_entries = []
    for e in payload["entries"]:
        if e["key"].get("groups", 1) != 1:
            continue                      # old writers had no grouped keys
        for sec in ("key", "nest"):
            e[sec].pop("groups", None)
        e["plan"].pop("groups", None)
        old_entries.append(e)
    payload["entries"] = old_entries
    with open(path, "w") as f:
        json.dump(payload, f)
    fresh = ScheduleCache()
    assert fresh.load_tuning(path) == len(old_entries) == 1
    got = fresh.schedule_for(dense)
    assert got.source == "loaded" and got.key.groups == 1
    assert got.plan.groups == 1


def test_tuning_json_forward_compat_unknown_fields(tmp_path):
    """Entries from a *newer* writer (extra unknown fields on key/nest)
    load cleanly — unknown fields are dropped, not treated as rot."""
    cache, dense, dw = _tuned_cache()
    path = str(tmp_path / "tune.json")
    cache.save_tuning(path)
    with open(path) as f:
        payload = json.load(f)
    for e in payload["entries"]:
        e["key"]["from_the_future"] = 42
        e["nest"]["winograd"] = True
    with open(path, "w") as f:
        json.dump(payload, f)
    fresh = ScheduleCache()
    assert fresh.load_tuning(path) == 2
    assert fresh.schedule_for(dw).tuned


def test_bench_gate_distills_and_compares(tmp_path):
    """The CI perf gate: exact counters gate on any drift, latency gates
    one-sided within tolerance."""
    from benchmarks.check_bench import compare, extract
    bench = {
        "latency": {"auto_per_img_s": 0.01,
                    "pallas_unfused_per_img_s": 0.02,
                    "pallas_fused_per_img_s": 0.015},
        "fold_reuse": {"hits": 5, "misses": 8, "replans": 0,
                       "hit_rate": 0.38, "conv_layers": 13,
                       "distinct_schedules": 8},
        "pallas_calls": 13,
        "mobilenetv2": {
            "latency": {"pallas_fused_per_img_s": 0.03},
            "fold_reuse": {"hits": 25, "misses": 27, "replans": 0,
                           "conv_layers": 52, "distinct_schedules": 27},
            "pallas_calls": 52,
        },
        "serving_by_model": {
            "vgg16": {"kips": 1.0, "latency": {"p95_s": 0.05},
                      "compile": {"distinct_schedules": 8}},
        },
    }
    base = extract(bench)
    assert base["exact"]["vgg16.pallas_calls"] == 13
    assert base["exact"]["mobilenetv2.fold_reuse.conv_layers"] == 52
    assert compare(extract(bench), base, tol=0.2) == []
    # 10% slower: within budget; 30% slower: out of budget
    ok = json.loads(json.dumps(bench))
    ok["latency"]["pallas_fused_per_img_s"] = 0.0165
    assert compare(extract(ok), base, tol=0.2) == []
    slow = json.loads(json.dumps(bench))
    slow["latency"]["pallas_fused_per_img_s"] = 0.0196
    fails = compare(extract(slow), base, tol=0.2)
    assert len(fails) == 1 and fails[0][0] == "latency"
    # any pallas-call / fold-reuse drift fails regardless of tolerance
    drift = json.loads(json.dumps(bench))
    drift["mobilenetv2"]["pallas_calls"] = 53
    drift["fold_reuse"]["hits"] = 6
    kinds = {m for _, m, _ in compare(extract(drift), base, tol=10.0)}
    assert "mobilenetv2.pallas_calls" in kinds
    assert "vgg16.fold_reuse.hits" in kinds
    # throughput drop beyond tolerance fails
    slow_srv = json.loads(json.dumps(bench))
    slow_srv["serving_by_model"]["vgg16"]["kips"] = 0.7
    fails = compare(extract(slow_srv), base, tol=0.2)
    assert [k for k, _, _ in fails] == ["throughput"]


def test_bench_gate_validates_baseline_schema():
    """A malformed baseline is refused up front with *every* defect
    reported in one pass — not a KeyError on the first missing section."""
    from benchmarks.check_bench import extract, validate_baseline
    good = extract({"pallas_calls": 13,
                    "latency": {"auto_per_img_s": 0.01}})
    assert validate_baseline(good) == []
    # several defects at once: all surface in a single validation run
    bad = {"exact": {"vgg16.pallas_calls": 13.5,
                     "vgg16.fold_reuse.hits": "five"},
           "latency": {"serving.vgg16.p95_s": -0.1},
           "extra_section": {}}
    problems = validate_baseline(bad)
    assert len(problems) == 9
    text = "\n".join(problems)
    assert "not an integral count" in text          # 13.5
    assert "not a number" in text                   # "five"
    assert "negative value" in text                 # -0.1
    assert "missing section 'throughput'" in text
    assert "missing section 'robustness'" in text
    assert "missing section 'observability'" in text
    assert "missing section 'quantization'" in text
    assert "missing section 'transport'" in text
    assert "unknown section 'extra_section'" in text
    assert validate_baseline([1, 2]) \
        == ["baseline must be a JSON object, got list"]
