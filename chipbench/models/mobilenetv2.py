"""Plain reference of MobileNetV2 (Sandler et al., "MobileNetV2: Inverted
Residuals and Linear Bottlenecks", arXiv:1801.04381, Table 2): a 3x3 stem
of ``stem_width`` filters at ``stem_stride``, then the bottleneck rows
(t, c, n, s) of ``bottlenecks`` — n inverted residual blocks of c output
channels, the first at stride s — then a 1x1 head of ``head_width``
filters, global average pool and a dense classifier.  Each block is a 1x1
expand to ``cin * t`` channels (left out where t == 1), a 3x3 depthwise
conv at the block's stride, and a linear 1x1 project, with an identity
skip where the block neither strides nor changes width.  Every conv has
no bias and is followed by BatchNorm, applied here as written, ``(y -
mean) / sqrt(var + 1e-5) * gamma + beta``, never folded; ReLU6 follows
the stem, the expand, the depthwise and the head convs, and nothing
follows the project.  Float32 throughout, NCHW.  Padding is symmetric, 1
on every 3x3, as torchvision's ``mobilenet_v2`` has it; dropout is the
identity at inference.

The parameters are the served tree: ``{conv: {"w"}}`` (depthwise weights
(C, 1, 3, 3)), ``{conv + "_bn": {"gamma", "beta", "mean", "var"}}`` and
``fc: {"w", "b"}``, keyed by the served graph's names (``stem``,
``b<i>_exp``/``_dw``/``_proj``, ``head``, ``fc``).  ``width_mult`` below
1 exists for the CPU tests only.
"""
from __future__ import annotations

import math

from chipbench.models import common

BN_EPS = 1e-5
BIAS_STD = 0.1


def _w(c: int, m: float) -> int:
    return max(int(c * m), 1)


def _out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def blocks(cfg: dict) -> list:
    """(name, cin, cout, stride, expand t, hidden width) per block."""
    m = float(cfg.get("width_mult", 1.0))
    out = []
    cin = _w(int(cfg["stem_width"]), m)
    for t, c, n, s in cfg["bottlenecks"]:
        cout = _w(int(c), m)
        for i in range(int(n)):
            out.append((f"b{len(out)}", cin, cout, int(s) if i == 0 else 1,
                        int(t), cin * int(t)))
            cin = cout
    return out


def layers(cfg: dict) -> list:
    """Every conv and dense layer with its shapes for one image, in the
    order the served graph launches them.  ``residual`` marks the project
    conv whose epilogue adds the skip.  A depthwise conv has ``cin`` 1,
    its channels per group, and ``groups`` its channel count, so that
    ``cout * cin * k * k`` counts its MACs per output pixel."""
    m = float(cfg.get("width_mult", 1.0))
    h = int(cfg["img"])

    def conv(name, cin, cout, k, stride, h, residual=False, groups=1):
        return {"kind": "conv", "name": name, "cin": cin, "cout": cout,
                "k": k, "stride": stride, "pad": k // 2, "h": h, "w": h,
                "pool": False, "residual": residual, "groups": groups}
    stem, ss = _w(int(cfg["stem_width"]), m), int(cfg["stem_stride"])
    out = [conv("stem", int(cfg["channels"]), stem, 3, ss, h)]
    h = _out(h, 3, ss, 1)
    for name, cin, cout, stride, t, hidden in blocks(cfg):
        if t != 1:
            out.append(conv(f"{name}_exp", cin, hidden, 1, 1, h))
        out.append(conv(f"{name}_dw", 1, hidden, 3, stride, h,
                        groups=hidden))
        h = _out(h, 3, stride, 1)
        out.append(conv(f"{name}_proj", hidden, cout, 1, 1, h,
                        residual=stride == 1 and cin == cout))
    head = _w(int(cfg["head_width"]), m)
    out.append(conv("head", out[-1]["cout"], head, 1, 1, h))
    out.append({"kind": "dense", "name": "fc", "din": head,
                "dout": int(cfg["classes"]), "relu": False})
    return out


def init_params(key, cfg: dict) -> dict:
    """Conv weights N(0, 1/fan_in); BatchNorm statistics drawn so that
    most ReLU6 outputs lie strictly between 0 and 6: gamma U(0.5, 1),
    beta U(0.5, 2), mean N(0, 0.1^2), var U(0.5, 1.5); fc weights N(0,
    1/din) and biases N(0, 0.1^2).  Each kind of draw is made once for
    every layer and cut into the layers' pieces."""
    import jax
    import jax.numpy as jnp
    ls = layers(cfg)
    convs, fc = ls[:-1], ls[-1]
    kw, kg, kbeta, km, kv, kfw, kfb = jax.random.split(key, 7)
    sizes = [ly["cout"] * ly["cin"] * ly["k"] ** 2 for ly in convs]
    flat = jax.random.normal(kw, (sum(sizes),), jnp.float32)
    n_bn = sum(ly["cout"] for ly in convs)
    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n_bn,), jnp.float32, lo, hi)
    stats = {"gamma": uniform(kg, 0.5, 1.0), "beta": uniform(kbeta, 0.5, 2.0),
             "mean": 0.1 * jax.random.normal(km, (n_bn,), jnp.float32),
             "var": uniform(kv, 0.5, 1.5)}
    p = {}
    w0 = c0 = 0
    for ly, size in zip(convs, sizes):
        shape = (ly["cout"], ly["cin"], ly["k"], ly["k"])
        fan_in = ly["cin"] * ly["k"] * ly["k"]
        p[ly["name"]] = {"w": flat[w0:w0 + size].reshape(shape)
                         / math.sqrt(fan_in)}
        p[ly["name"] + "_bn"] = {k: v[c0:c0 + ly["cout"]]
                                 for k, v in stats.items()}
        w0, c0 = w0 + size, c0 + ly["cout"]
    p["fc"] = {"w": jax.random.normal(kfw, (fc["din"], fc["dout"]),
                                      jnp.float32) / math.sqrt(fc["din"]),
               "b": common.bias(kfb, fc["dout"], BIAS_STD)}
    return p


def conv(x, w, *, stride: int, pad: int, groups: int, precision: str):
    """NCHW x OIHW convolution of ``groups`` groups, no bias."""
    from jax import lax
    common._check(precision)

    def op(u, v, out):
        return lax.conv_general_dilated(
            u, v, window_strides=(stride, stride),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
            precision=lax.Precision.HIGHEST if out is None else None,
            preferred_element_type=out)
    if precision == "highest":
        return op(x, w, None)
    return common._three_pass(op, x, w)


def batchnorm(y, bn: dict):
    """Inference BatchNorm on NCHW, as written."""
    import jax.numpy as jnp

    def col(v):
        return v[None, :, None, None]
    return ((y - col(bn["mean"])) / jnp.sqrt(col(bn["var"]) + BN_EPS)
            * col(bn["gamma"]) + col(bn["beta"]))


def relu6(y):
    import jax.numpy as jnp
    return jnp.clip(y, 0.0, 6.0)


def forward(params: dict, x, cfg: dict, precision: str = "highest",
            taps: list = None):
    """x: (N, C, H, W) float32 -> (N, classes) logits.  ``taps``, when
    given, collects every ReLU6 output, for the clipped shares."""

    def cbn(name, x, stride, act=True, groups=1):
        w = params[name]["w"]
        y = batchnorm(conv(x, w, stride=stride, pad=w.shape[-1] // 2,
                           groups=groups, precision=precision),
                      params[name + "_bn"])
        if not act:
            return y
        y = relu6(y)
        if taps is not None:
            taps.append(y)
        return y
    x = cbn("stem", x, int(cfg["stem_stride"]))
    for name, cin, cout, stride, t, hidden in blocks(cfg):
        h = cbn(f"{name}_exp", x, 1) if t != 1 else x
        h = cbn(f"{name}_dw", h, stride, groups=hidden)
        h = cbn(f"{name}_proj", h, 1, act=False)
        x = x + h if (stride == 1 and cin == cout) else h
    x = cbn("head", x, 1).mean(axis=(2, 3))
    p = params["fc"]
    return common.dense(x, p["w"], p["b"], precision=precision)
