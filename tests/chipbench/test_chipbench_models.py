"""The benchmark's plain references against the program's own forwards
on the CPU, at a small width, on the served param tree."""
import functools

import jax
import numpy as np
import pytest

from chipbench import spec
from chipbench.models.common import seed_key

BENCH = spec.load_benchmark()


def small(config, **kw):
    cfg = dict(spec.config(BENCH, config))
    cfg.update(width_mult=0.0625, **kw)
    return cfg


CASES = {"vgg16-224": dict(img=32, classes=10),
         "resnet18-cifar32": {}}


def params_and_images(cfg, seed):
    model = spec.model_module(cfg["family"])
    params = jax.jit(functools.partial(model.init_params, cfg=cfg))(
        seed_key(seed))
    x = np.random.default_rng(seed).standard_normal(
        (4, cfg["channels"], cfg["img"], cfg["img"]), np.float32)
    return model, params, x


@pytest.mark.parametrize("config", sorted(CASES))
def test_reference_matches_program_forward(config):
    from repro.models import resnet, vgg
    cfg = small(config, **CASES[config])
    model, params, x = params_and_images(cfg, 2 ** 40 + 3)
    prog = vgg if cfg["family"] == "vgg16" else resnet
    with jax.default_matmul_precision("highest"):
        want = np.asarray(prog.forward(params, x, impl="xla"))
    got = np.asarray(jax.jit(lambda p, x: model.forward(p, x, cfg))(
        params, x))
    assert got.shape == (4, cfg["classes"])
    err = np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)
    assert err.max() < 1e-5


@pytest.mark.parametrize("config", sorted(CASES))
def test_param_tree_is_the_served_one(config):
    from repro.models.zoo import get_conv_model
    cfg = small(config, **CASES[config])
    model, params, _ = params_and_images(cfg, 1)
    served = jax.eval_shape(functools.partial(
        get_conv_model(cfg["program_model"]).init_params,
        width_mult=cfg["width_mult"], img=cfg["img"],
        classes=cfg["classes"]), jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert shapes == jax.tree.map(lambda a: a.shape, served)
    biases = [p["b"] for p in params.values()]
    assert all(float(np.abs(b).max()) > 0 for b in biases)


def test_seed_key_uses_every_bit():
    a, b = seed_key(5), seed_key(2 ** 40 + 5)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))
