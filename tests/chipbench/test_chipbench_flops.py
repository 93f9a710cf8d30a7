"""FLOPs, bytes and peaks against the program's own loop nests."""
import functools

import jax
import numpy as np
import pytest

from chipbench import flops, peaks, spec

BENCH = spec.load_benchmark()


def program_nests(cfg, monkeypatch):
    """The ConvLoopNest of every conv the program's compiled forward
    runs, in order, recorded at its conv step while tracing (batch 2)."""
    from repro.core import engine
    from repro.core.loopnest import ConvLoopNest
    from repro.models.zoo import get_conv_model
    prog = get_conv_model(cfg["program_model"])
    model = spec.model_module(cfg["family"])
    params = jax.eval_shape(functools.partial(model.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    shape = (2, cfg["channels"], cfg["img"], cfg["img"])
    net = engine.compile_network(params, prog.to_graph(), shape,
                                 policy="reference", jit=False)
    out = []
    step = engine._conv_step

    def spy(x, w, *a, stride, pad, **kw):
        n, c, h, wd = x.shape
        nf, _, r, s = w.shape
        out.append(ConvLoopNest(n=n, nf=nf, c=c, r=r, s=s, x=h, y=wd,
                                stride=stride, pad=pad))
        return step(x, w, *a, stride=stride, pad=pad, **kw)
    monkeypatch.setattr(engine, "_conv_step", spy)
    jax.eval_shape(net.apply, params,
                   jax.ShapeDtypeStruct(shape, np.float32))
    return out


@pytest.mark.parametrize("config", ["vgg16-224", "resnet18-cifar32"])
def test_conv_flops_match_program_nests(config, monkeypatch):
    cfg = spec.config(BENCH, config)
    layers = spec.model_module(cfg["family"]).layers(cfg)
    convs = [ly for ly in layers if ly["kind"] == "conv"]
    nests = program_nests(cfg, monkeypatch)
    assert len(nests) == len(convs)
    for ly, cv in zip(convs, nests):
        launch = flops.conv_launch(ly, 2)
        assert launch["flops"] == cv.flops, ly["name"]
        assert flops.conv_out(ly) == cv.p == cv.q
        assert (ly["cin"], ly["cout"], ly["k"], ly["stride"]) == \
            (cv.c, cv.nf, cv.r, cv.stride)


@pytest.mark.parametrize("config,gflop,fused_pools,residuals", [
    ("vgg16-224", 30.94, 5, 0), ("resnet18-cifar32", 1.111, 0, 8)])
def test_per_image_flops_and_epilogues(config, gflop, fused_pools,
                                       residuals):
    """Published totals, and the fused epilogues the byte count assumes
    match the program's fusion pass."""
    from repro.core.graph import fuse_graph
    from repro.models.zoo import get_conv_model
    cfg = spec.config(BENCH, config)
    layers = spec.model_module(cfg["family"]).layers(cfg)
    assert flops.flops_per_image(layers) / 1e9 == pytest.approx(gflop,
                                                                 rel=1e-3)
    fused = fuse_graph(get_conv_model(cfg["program_model"]).to_graph())
    epi = {nd.name: nd.epilogue for nd in fused.nodes if nd.op == "conv"}
    for ly in layers:
        if ly["kind"] != "conv":
            continue
        e = epi[ly["name"]]
        assert bool(e and e.pool) == ly["pool"], ly["name"]
        assert bool(e and e.residual) == ly["residual"], ly["name"]
    assert sum(ly.get("pool", False) for ly in layers) == fused_pools
    assert sum(ly.get("residual", False) for ly in layers) == residuals


def test_launch_bytes_are_inputs_weights_outputs_once():
    ly = {"kind": "conv", "name": "c", "cin": 4, "cout": 8, "k": 3,
          "stride": 1, "pad": 1, "h": 6, "w": 6, "pool": True,
          "residual": False}
    got = flops.conv_launch(ly, 2)
    assert got["flops"] == 2 * 2 * 8 * 4 * 9 * 6 * 6
    assert got["bytes"] == 4 * (2 * 4 * 36 + 8 * 4 * 9 + 8 + 2 * 8 * 9)
    peak = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert flops.roofline_min_s(got, peak) == got["flops"] / 1e3


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peak_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("TPU v4")
