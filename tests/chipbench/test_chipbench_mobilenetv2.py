"""The MobileNetV2 reference (``chipbench/models/mobilenetv2.py``) against
the program's published network on the CPU, at a small width, on the
served param tree; its FLOP count at the published width; and a run of
its cell driven in process."""
import functools

import jax
import numpy as np
import pytest

from chipbench import flops, run, spec
from chipbench.models.common import seed_key
from chipbench.server_child import ServerSide

BENCH = spec.load_benchmark()
CELL = "mobilenetv2-224.bulk"


def small(**kw):
    cfg = dict(spec.config(BENCH, spec.workload(BENCH, CELL)["config"]))
    cfg.update(width_mult=0.25, img=64)
    cfg.update(kw)
    return cfg


def params_and_images(cfg, seed, n=2):
    model = spec.model_module(cfg["family"])
    params = jax.jit(functools.partial(model.init_params, cfg=cfg))(
        seed_key(seed))
    x = np.random.default_rng(seed).standard_normal(
        (n, cfg["channels"], cfg["img"], cfg["img"]), np.float32)
    return model, params, x


def rel_err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float((np.abs(got - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def test_reference_matches_program_forward():
    from repro.models import mobilenet
    cfg = small()
    model, params, x = params_and_images(cfg, 2 ** 40 + 3)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: mobilenet.forward(
            p, x, impl="xla", strides=mobilenet.IMAGENET))(params, x)
    got = jax.jit(lambda p, x: model.forward(p, x, cfg))(params, x)
    assert got.shape == (2, cfg["classes"])
    assert rel_err(got, want) < 1e-5


def test_param_tree_is_the_served_one():
    from repro.models.zoo import get_conv_model
    cfg = small()
    _, params, _ = params_and_images(cfg, 1)
    served = jax.eval_shape(functools.partial(
        get_conv_model(cfg["program_model"]).init_params,
        width_mult=cfg["width_mult"], img=cfg["img"],
        classes=cfg["classes"]), jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert shapes == jax.tree.map(lambda a: a.shape, served)
    # drawn statistics, not identity, so the fused scale/shift is checked
    bn = [v for k, v in params.items() if k.endswith("_bn")]
    assert len(bn) == 52
    for leaf, ident in (("gamma", 1.0), ("beta", 0.0), ("mean", 0.0),
                        ("var", 1.0)):
        assert all(float(np.abs(b[leaf] - ident).max()) > 0 for b in bn)
    assert float(np.abs(params["fc"]["b"]).max()) > 0


def test_published_flops_and_layers():
    cfg = spec.config(BENCH, "mobilenetv2-224")
    layers = spec.model_module(cfg["family"]).layers(cfg)
    assert flops.flops_per_image(layers) / 1e9 == pytest.approx(
        cfg["gflop_per_image"], rel=1e-3)
    convs = [ly for ly in layers if ly["kind"] == "conv"]
    dw = [ly for ly in convs if ly["groups"] > 1]
    assert len(convs) == 52 and len(dw) == 17
    assert all(ly["cin"] == 1 and ly["groups"] == ly["cout"] for ly in dw)
    assert sum(ly["residual"] for ly in convs) == 10
    assert sorted({flops.conv_out(ly) for ly in convs}) == [7, 14, 28, 56,
                                                            112]
    assert layers[-1]["din"] == cfg["head_width"]


def test_flops_match_program_nests(monkeypatch):
    """Per conv, the reference's layer FLOPs equal the grouped loop nest
    the program's forward runs."""
    from repro.core import engine
    from repro.core.loopnest import ConvLoopNest
    from repro.models.zoo import get_conv_model
    cfg = small()
    layers = [ly for ly in spec.model_module(cfg["family"]).layers(cfg)
              if ly["kind"] == "conv"]
    params = jax.eval_shape(functools.partial(
        get_conv_model(cfg["program_model"]).init_params,
        width_mult=cfg["width_mult"], img=cfg["img"]),
        jax.random.PRNGKey(0))
    shape = (2, cfg["channels"], cfg["img"], cfg["img"])
    net = engine.compile_network(params, get_conv_model(
        cfg["program_model"]).to_graph(), shape, policy="reference",
        jit=False)
    nests = []
    step = engine._conv_step

    def spy(x, w, *a, stride, pad, groups, **kw):
        n, c, h, wd = x.shape
        nests.append(ConvLoopNest(n=n, nf=w.shape[0], c=c, r=w.shape[2],
                                  s=w.shape[3], x=h, y=wd, stride=stride,
                                  pad=pad, groups=groups))
        return step(x, w, *a, stride=stride, pad=pad, groups=groups, **kw)
    monkeypatch.setattr(engine, "_conv_step", spy)
    jax.eval_shape(net.apply, params,
                   jax.ShapeDtypeStruct(shape, np.float32))
    assert len(nests) == len(layers)
    for ly, cv in zip(layers, nests):
        assert flops.conv_launch(ly, 2)["flops"] == cv.flops, ly["name"]
        assert (flops.conv_out(ly), ly["stride"], ly["groups"]) == \
            (cv.p, cv.stride, cv.groups), ly["name"]


def test_control_is_over_the_limit():
    """The three-pass bf16 control reads above ``logit_err_limit`` even at
    a small width, where it sums fewer products than at the published
    one."""
    cfg = small()
    model, params, x = params_and_images(cfg, 9)
    hi, ctl = (jax.jit(lambda p, x, pr=pr: model.forward(p, x, cfg, pr))(
        params, x) for pr in ("highest", "bf16x3"))
    assert cfg["check"]["logit_err_limit"] < rel_err(ctl, hi) < 1e-3


def test_relu6_outputs_are_mostly_unclipped():
    """The seeded draws keep most ReLU6 outputs strictly inside (0, 6), so
    the comparison sees the activations, not their clip values."""
    cfg = small()
    model, params, x = params_and_images(cfg, 2 ** 33 + 5)

    def relu6_outputs(p, x):
        taps = []
        model.forward(p, x, cfg, "highest", taps=taps)
        return taps
    taps = jax.jit(relu6_outputs)(params, x)
    assert len(taps) == 35      # stem, head, and 2 per block (1 for t=1)
    v = np.concatenate([np.asarray(t).ravel() for t in taps])
    zero, six = float((v <= 0).mean()), float((v >= 6).mean())
    assert zero < 0.3 and six < 0.05
    assert 1 - zero - six > 0.7


def tiny():
    cell = spec.workload(BENCH, CELL)
    cfg = small(img=32)
    mix = dict(spec.traffic(cell["traffic"]), clients=2, buckets=[8],
               sizes={"min": 4, "max": 4}, bodies_per_size=2,
               reference_block=8)
    return cell, cfg, mix


@pytest.mark.parametrize("control", [False, True],
                         ids=["sound", "bf16x3-control"])
def test_in_process_run_of_the_cell(control, monkeypatch):
    """The cell's pieces drive a whole run on the CPU, and the control in
    the program's place fails ``correct``."""
    from repro.core.engine import CompiledNetwork
    cell, cfg, mix = tiny()
    if control:
        model = spec.model_module(cfg["family"])
        fwd = jax.jit(lambda p, x: model.forward(p, x, cfg, "bf16x3"))
        monkeypatch.setattr(CompiledNetwork, "__call__",
                            lambda self, params, x: fwd(params, x))
    seed = 2 ** 34 + 17
    side = ServerSide(cfg, mix, seed, require_tpu=False)
    res = run.run_cell(side, BENCH, cell, cfg, mix, seed, 1.0, False,
                       t_start=0.0)
    assert res["attempted"] > 0
    assert res["correct"] is not control, res["checks"]
    assert res["checks"]["failed"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
