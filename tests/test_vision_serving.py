"""Continuous-batching image serving (DESIGN.md §6): bucket policy,
batcher packing/drain order, engine outputs vs the direct compiled
forward, pay-once compilation across buckets, and mesh-sharded
equivalence."""
import json
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.batcher import BucketPolicy, ImageBatcher

# the checkout under test: subprocess programs import its src/
ROOT = pathlib.Path(__file__).resolve().parents[1]

IMG, WIDTH, CLASSES = 32, 0.0625, 10


@pytest.fixture(scope="module")
def vgg_params():
    from repro.models import vgg
    return vgg.init_params(jax.random.PRNGKey(0), width_mult=WIDTH,
                           img=IMG, classes=CLASSES)


def _requests(rng, sizes):
    return [rng.standard_normal((n, 3, IMG, IMG)).astype(np.float32)
            for n in sizes]


# --------------------------------------------------------------------------
# bucket policy + batcher (host side, no jax)
# --------------------------------------------------------------------------

def test_bucket_selection_deterministic():
    pol = BucketPolicy((1, 2, 4, 8))
    assert [pol.bucket_for(n) for n in (1, 2, 3, 4, 5, 8)] == \
           [1, 2, 4, 4, 8, 8]
    # pure function of n: repeated calls never drift
    assert all(pol.bucket_for(n) == pol.bucket_for(n) for n in range(1, 9))
    with pytest.raises(ValueError, match="exceed"):
        pol.bucket_for(9)
    with pytest.raises(ValueError):
        BucketPolicy(())
    # mesh alignment: every width becomes a multiple of the data-axis size
    assert BucketPolicy((1, 2, 4, 6)).aligned(4).widths == (4, 8)


def test_batcher_packs_fifo_and_pads():
    b = ImageBatcher(BucketPolicy((1, 2, 4)), IMG)
    rng = np.random.default_rng(0)
    for imgs in _requests(rng, (2, 1, 3, 1)):
        b.submit(imgs)
    fb1 = b.form()                      # 2+1 fit, 3 would overflow max=4
    assert [r.rid for r in fb1.requests] == [0, 1]
    assert (fb1.bucket, fb1.n_images) == (4, 3)
    assert fb1.x.shape == (4, 3, IMG, IMG)
    assert not fb1.x[3].any()           # zero padding row
    np.testing.assert_array_equal(fb1.x[:2], fb1.requests[0].images)
    assert fb1.occupancy == pytest.approx(3 / 4)
    fb2 = b.form()                      # 3+1 fills the max bucket exactly
    assert [r.rid for r in fb2.requests] == [2, 3]
    assert (fb2.bucket, fb2.n_images, fb2.occupancy) == (4, 4, 1.0)
    assert b.form() is None


def test_batcher_rejects_oversize_and_bad_shape():
    b = ImageBatcher(BucketPolicy((1, 2)), IMG)
    with pytest.raises(ValueError, match="split it client-side"):
        b.submit(np.zeros((3, 3, IMG, IMG), np.float32))
    with pytest.raises(ValueError, match="must be"):
        b.submit(np.zeros((1, 3, IMG, IMG // 2), np.float32))
    # a bare (C, H, W) image is promoted to a 1-image request
    req = b.submit(np.zeros((3, IMG, IMG), np.float32))
    assert req.n == 1


def test_scatter_slices_per_request():
    b = ImageBatcher(BucketPolicy((4,)), IMG)
    rng = np.random.default_rng(1)
    for imgs in _requests(rng, (1, 2)):
        b.submit(imgs)
    fb = b.form()
    logits = np.arange(4 * CLASSES, dtype=np.float32).reshape(4, CLASSES)
    ImageBatcher.scatter(fb, logits)
    r1, r2 = fb.requests
    np.testing.assert_array_equal(r1.logits, logits[:1])
    np.testing.assert_array_equal(r2.logits, logits[1:3])
    assert r1.done and r2.done and r1.latency_s >= 0.0


# --------------------------------------------------------------------------
# engine vs the direct compiled forward (pad-and-slice correctness)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["auto", "pallas"])
def test_engine_outputs_bitwise_equal_direct_forward(vgg_params, policy):
    """Per request, the served logits must be bitwise-equal to a direct
    ``compile_network`` forward of the same (unpadded) images — padding
    and packing are pure batching concerns, invisible to the numerics."""
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    sizes = (1, 3, 2) if policy == "auto" else (1, 2)
    rng = np.random.default_rng(2)
    imgs = _requests(rng, sizes)
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy=policy,
                       buckets=(2, 4))
    reqs = [eng.submit(im) for im in imgs]
    eng.run()
    for req, im in zip(reqs, imgs):
        direct = vgg.compile_forward(vgg_params, img=IMG,
                                     batch=im.shape[0], policy=policy,
                                     cache=eng.compiler.cache)
        want = np.asarray(direct(vgg_params, jnp.asarray(im)))
        assert req.done and req.logits.shape == (im.shape[0], CLASSES)
        np.testing.assert_array_equal(req.logits, want)


def test_deadlined_requests_keep_bitwise_equivalence(vgg_params):
    """Attaching a (generous) SLO changes accounting, never numerics:
    logits stay bitwise-equal to the direct forward and every deadline
    is counted hit."""
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    rng = np.random.default_rng(9)
    imgs = _requests(rng, (1, 3, 2))
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy="auto",
                       buckets=(2, 4))
    reqs = [eng.submit(im, deadline_s=300.0) for im in imgs]
    m = eng.run()
    assert m.deadline_total == 3 and m.deadline_hits == 3
    assert m.deadline_hit_rate == 1.0
    for req, im in zip(reqs, imgs):
        direct = vgg.compile_forward(vgg_params, img=IMG,
                                     batch=im.shape[0], policy="auto",
                                     cache=eng.compiler.cache)
        want = np.asarray(direct(vgg_params, jnp.asarray(im)))
        assert req.deadline_met is True
        np.testing.assert_array_equal(req.logits, want)


def test_queue_drain_order_is_fifo(vgg_params):
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy="auto",
                       buckets=(1, 2))
    rng = np.random.default_rng(3)
    reqs = [eng.submit(im) for im in _requests(rng, (1,) * 5)]
    done_order = []
    while eng.pending:
        before = {r.rid for r in reqs if r.done}
        eng.step()
        done_order.extend(sorted(r.rid for r in reqs
                                 if r.done and r.rid not in before))
    assert done_order == [0, 1, 2, 3, 4]


def test_slot_refill_under_mixed_sizes(vgg_params):
    """A mixed-size stream drains completely, with batches refilled in
    arrival order and occupancy/per-bucket accounting consistent."""
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy="auto",
                       buckets=(1, 2, 4))
    rng = np.random.default_rng(4)
    sizes = (3, 1, 1, 4, 2, 1)
    reqs = [eng.submit(im) for im in _requests(rng, sizes)]
    m = eng.run()
    assert all(r.done for r in reqs)
    assert m.images == sum(sizes) and m.requests == len(sizes)
    # FIFO packing: (3+1)->4, (1)->1 [the 4 doesn't fit behind it],
    # (4)->4, (2+1)->4
    assert m.batches == 4
    assert m.per_bucket == {4: 3, 1: 1}
    # occupancies stream into a bounded histogram (obs/metrics.py):
    # exact count/mean survive, the raw list does not
    assert m.occupancy_hist.count == 4
    assert m.slot_occupancy == pytest.approx(0.9375)


def test_run_max_batches_never_drops_requests(vgg_params):
    """Hitting the batch budget must leave unserved requests queued, not
    popped into a staged batch that is silently discarded (regression)."""
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy="auto",
                       buckets=(1, 2))
    rng = np.random.default_rng(8)
    reqs = [eng.submit(im) for im in _requests(rng, (1,) * 8)]
    m = eng.run(max_batches=2)
    assert m.batches == 2
    assert [r.rid for r in reqs if r.done] == [0, 1, 2, 3]
    assert eng.pending == 4                       # the rest still queued
    eng.run()                                     # and still servable
    assert all(r.done for r in reqs)
    assert eng.run(max_batches=0).batches == 4    # zero budget: a no-op


def test_metrics_shape_and_kips(vgg_params):
    from repro.models import vgg
    from repro.serve.vision import VisionEngine
    eng = VisionEngine(vgg_params, vgg.to_graph(), img=IMG, policy="auto",
                       buckets=(2,))
    eng.warmup()
    rng = np.random.default_rng(5)
    for im in _requests(rng, (2, 2, 1)):
        eng.submit(im)
    eng.run()
    d = eng.metrics_dict()
    assert d["images"] == 5 and d["batches"] == 3
    assert d["kips"] > 0 and d["images_per_s"] == pytest.approx(
        d["kips"] * 1e3, rel=1e-3)
    lat = d["latency"]
    assert lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"]
    assert d["compile"]["buckets"] == [2]
    assert d["mesh"] is None


# --------------------------------------------------------------------------
# pay-once compilation across buckets
# --------------------------------------------------------------------------

def test_bucket_compiler_shares_schedules_across_buckets(vgg_params):
    from repro.models import vgg
    comp = vgg.bucket_compiler(vgg_params, img=IMG, policy="auto")
    n1 = comp.network_for(1)
    assert comp.network_for(1) is n1            # memoized per width
    misses_after_first = comp.cache.stats.misses
    assert comp.cache.distinct == 8             # VGG's 8 fold geometries
    n2 = comp.network_for(4)
    # second bucket: pure cache hits — ScheduleKey excludes the batch axis
    assert comp.cache.stats.misses == misses_after_first
    assert n2.build_stats.hits == len(n2.layer_schedules)
    assert comp.buckets == [1, 4] and 4 in comp and 3 not in comp
    with pytest.raises(ValueError):
        comp.network_for(0)


def test_bucket_compiler_autotune_pay_once_across_buckets(tmp_path):
    """With autotune, the first bucket measures; later buckets (and the
    shared tuning JSON) never re-measure."""
    from repro.core.engine import BucketCompiler
    from repro.models.common import DTypePolicy, TreeMaker
    tm = TreeMaker("init", key=jax.random.PRNGKey(0),
                   dtype_policy=DTypePolicy(param=jnp.float32,
                                            compute=jnp.float32))
    params = {"c1": {"w": tm.param((8, 3, 3, 3), (None, None, None, None)),
                     "b": tm.param((8,), (None,), init="zeros")}}
    calls = {"n": 0}

    def timer(plan, dataflow):
        calls["n"] += 1
        return float(plan.p_block)

    path = str(tmp_path / "tuning.json")
    comp = BucketCompiler(params, (("c1", 3, 8),), 16, policy="pallas",
                          autotune=True, tuning_path=path,
                          autotune_timer=timer)
    comp.network_for(1)
    measured = calls["n"]
    assert measured > 0
    comp.network_for(2)
    comp.network_for(4)
    assert calls["n"] == measured               # pay-once across buckets
    assert len(json.load(open(path))["entries"]) == 1
    assert comp.stats()["buckets"] == [1, 2, 4]


# --------------------------------------------------------------------------
# mesh-sharded serving (2 forced host devices, subprocess-isolated)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["auto", "pallas"])
@pytest.mark.parametrize("mesh_shape", ["2x1", "1x2"])
def test_mesh_sharded_matches_single_device(mesh_shape, policy):
    """The identical engine code on a 2-device CPU mesh — batch (image
    folds) on the data axis, N_F (filter folds) on the model axis via
    ``MappingPlan.partition_spec`` — produces the single-device outputs
    bitwise.  Under ``pallas`` every fold kernel runs per shard inside
    ``shard_map`` (GSPMD cannot partition a Mosaic kernel)."""
    data, model = (int(t) for t in mesh_shape.split("x"))
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys; sys.path.insert(0, "src")
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_local_mesh
        from repro.models import vgg
        from repro.serve.vision import VisionEngine

        params = vgg.init_params(jax.random.PRNGKey(0), width_mult={WIDTH},
                                 img={IMG}, classes={CLASSES})
        rng = np.random.default_rng(0)
        imgs = [rng.standard_normal((n, 3, {IMG}, {IMG})).astype(np.float32)
                for n in (1, 3, 2)]

        single = VisionEngine(params, vgg.to_graph(), img={IMG},
                              policy="{policy}", buckets=(2, 4))
        reqs_s = [single.submit(im) for im in imgs]
        single.run()

        mesh = make_local_mesh({data}, {model})
        eng = VisionEngine(params, vgg.to_graph(), img={IMG},
                           policy="{policy}", buckets=(2, 4), mesh=mesh)
        assert all(w % {data} == 0 for w in eng.batcher.policy.widths)
        x = jax.ShapeDtypeStruct((4, 3, {IMG}, {IMG}), np.float32,
                                 sharding=eng._x_sharding)
        net = eng.compiler.network_for(4)
        hlo = net.apply.lower(eng.params, x).as_text()
        per_shard = hlo.count("sdy.manual_computation")   # shard_map bodies
        assert per_shard == ({policy!r} == "pallas") * len(
            net.layer_schedules), per_shard
        reqs_m = [eng.submit(im) for im in imgs]
        eng.run()
        for rs, rm in zip(reqs_s, reqs_m):
            assert np.array_equal(rs.logits, rm.logits), rs.rid
        # the sharding really is the MappingPlan's partition_spec binding
        spec = eng.params["conv3_1"]["w"].sharding.spec
        want = eng.plan.partition_spec(("N_F", None, None, None))
        assert spec == want, (spec, want)
        print("MESH_OK", dict(mesh.shape))
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_OK" in out.stdout


# --------------------------------------------------------------------------
# launcher / bench snapshot plumbing
# --------------------------------------------------------------------------

def test_merge_bench_json_preserves_sections(tmp_path):
    from repro.launch.serve import merge_bench_json
    path = str(tmp_path / "BENCH_vgg.json")
    json.dump({"latency": {"x": 1}}, open(path, "w"))
    merge_bench_json({"kips": 2.0}, path)
    data = json.load(open(path))
    assert data["latency"] == {"x": 1} and data["serving"] == {"kips": 2.0}
    # corrupt snapshot: overwritten, not fatal
    open(path, "w").write("{nope")
    merge_bench_json({"kips": 3.0}, path)
    assert json.load(open(path))["serving"] == {"kips": 3.0}


def test_serving_summary_emits_all_metrics(tmp_path):
    from repro.serve.vision import serving_summary
    d = serving_summary("vgg16", requests=6, img=IMG, width_mult=WIDTH,
                        policy="auto", buckets=(1, 2, 4), seed=7)
    for k in ("images", "requests", "batches", "kips", "latency",
              "slot_occupancy", "per_bucket_batches", "compile",
              "workload", "robustness"):
        assert k in d, k
    assert d["requests"] == 6 and d["images"] >= 6
    assert d["workload"]["model"] == "vgg16"
    assert d["compile"]["distinct_schedules"] == 8
    assert set(d["latency"]) == {"p50_s", "p95_s", "p99_s", "mean_s"}
    # a healthy deadline-free run: every request ok, nothing shed or
    # degraded, nothing lost, and a deterministic 1.0 deadline hit rate
    rb = d["robustness"]
    assert rb["outcomes"] == {"ok": 6} and rb["submitted"] == 6
    assert rb["shed"] == rb["expired"] == rb["failed"] == 0
    assert rb["degraded_batches"] == 0 and rb["lost_requests"] == 0
    assert rb["deadline_hit_rate"] == 1.0


def test_merge_bench_json_per_model_keys(tmp_path):
    """Per-model serving metrics land under serving_by_model.<name> and a
    non-vgg16 model never clobbers the legacy flat serving section."""
    from repro.launch.serve import merge_bench_json
    path = str(tmp_path / "BENCH_vgg.json")
    json.dump({"latency": {"x": 1}}, open(path, "w"))
    merge_bench_json({"kips": 1.0}, path, model="vgg16")
    merge_bench_json({"kips": 2.0}, path, model="resnet18")
    data = json.load(open(path))
    assert data["latency"] == {"x": 1}                 # micro preserved
    assert data["serving"] == {"kips": 1.0}            # vgg16 stays legacy
    assert data["serving_by_model"] == {"vgg16": {"kips": 1.0},
                                        "resnet18": {"kips": 2.0}}
    # re-serving one model leaves the other model's snapshot intact
    merge_bench_json({"kips": 3.0}, path, model="resnet18")
    data = json.load(open(path))
    assert data["serving"] == {"kips": 1.0}
    assert data["serving_by_model"]["resnet18"] == {"kips": 3.0}
    assert data["serving_by_model"]["vgg16"] == {"kips": 1.0}
