"""Plain reference of ResNet-18 for CIFAR-10, as in kuangliu/pytorch-cifar
``models/resnet.py`` ``ResNet18()``: a 3x3 stride-1 stem of 64 filters,
four stages of two BasicBlocks at widths 64/128/256/512 (the first block
of stages 2-4 has stride 2), each block ``relu(conv2(relu(conv1(x))) +
shortcut(x))`` with a 1x1 projection shortcut of the block's stride where
the shape changes, then the classifier.  Float32 throughout, NCHW.

Departures from the source, both as the served model has them (listed
under ``assumed`` in the configuration):

* BatchNorm is folded into each conv's bias, which is exact at
  inference; the convs therefore carry biases.
* The classifier flattens the 512x4x4 map into a 8192x10 dense layer
  where the source average-pools to 512 and uses 512x10.

The parameters are a dict ``{layer: {"w", "b"}}`` keyed by the served
graph's names (``stem``, ``s<stage>b<block>_c1``/``_c2``/``_down``,
``fc``).  ``width_mult`` below 1 exists for the CPU tests only.
"""
from __future__ import annotations

from chipbench.models import common

BIAS_STD = 0.1


def _w(c: int, m: float) -> int:
    return max(int(c * m), 1)


def _out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def blocks(cfg: dict) -> list:
    """(name, cin, cout, stride, has_projection) per BasicBlock."""
    m = float(cfg.get("width_mult", 1.0))
    out = []
    cin = _w(int(cfg["stem_width"]), m)
    for si, (n_blocks, base) in enumerate(
            zip(cfg["blocks_per_stage"], cfg["stage_widths"]), start=1):
        cout = _w(int(base), m)
        for bi in range(int(n_blocks)):
            stride = 2 if (si > 1 and bi == 0) else 1
            out.append((f"s{si}b{bi}", cin, cout, stride,
                        stride != 1 or cin != cout))
            cin = cout
    return out


def layers(cfg: dict) -> list:
    """Every conv and dense layer with its shapes for one image, in the
    order the served graph launches them.  ``residual`` marks the conv
    whose epilogue adds the shortcut."""
    m = float(cfg.get("width_mult", 1.0))
    h = int(cfg["img"])
    stem = _w(int(cfg["stem_width"]), m)

    def conv(name, cin, cout, k, stride, pad, h, residual=False):
        return {"kind": "conv", "name": name, "cin": cin, "cout": cout,
                "k": k, "stride": stride, "pad": pad, "h": h, "w": h,
                "pool": False, "residual": residual}
    out = [conv("stem", int(cfg["channels"]), stem, 3, 1, 1, h)]
    for name, cin, cout, stride, down in blocks(cfg):
        ho = _out(h, 3, stride, 1)
        out.append(conv(f"{name}_c1", cin, cout, 3, stride, 1, h))
        out.append(conv(f"{name}_c2", cout, cout, 3, 1, 1, ho,
                        residual=True))
        if down:
            out.append(conv(f"{name}_down", cin, cout, 1, stride, 0, h))
        h = ho
    last = out[-1]["cout"]
    out.append({"kind": "dense", "name": "fc", "din": last * h * h,
                "dout": int(cfg["classes"]), "relu": False})
    return out


def init_params(key, cfg: dict) -> dict:
    import jax
    ls = layers(cfg)
    keys = jax.random.split(key, 2 * len(ls))
    p = {}
    for i, ly in enumerate(ls):
        kw, kb = keys[2 * i], keys[2 * i + 1]
        if ly["kind"] == "conv":
            fan_in = ly["cin"] * ly["k"] * ly["k"]
            shape = (ly["cout"], ly["cin"], ly["k"], ly["k"])
            n = ly["cout"]
        else:
            fan_in = ly["din"]
            shape = (ly["din"], ly["dout"])
            n = ly["dout"]
        p[ly["name"]] = {"w": common.he_normal(kw, shape, fan_in),
                         "b": common.bias(kb, n, BIAS_STD)}
    return p


def forward(params: dict, x, cfg: dict, precision: str = "highest"):
    """x: (N, C, H, W) float32 -> (N, classes) logits."""
    import jax

    def cb(name, x, stride, pad):
        p = params[name]
        return common.conv(x, p["w"], p["b"], stride=stride, pad=pad,
                           precision=precision)
    x = jax.nn.relu(cb("stem", x, 1, 1))
    for name, _, _, stride, down in blocks(cfg):
        h = jax.nn.relu(cb(f"{name}_c1", x, stride, 1))
        h = cb(f"{name}_c2", h, 1, 1)
        sc = cb(f"{name}_down", x, stride, 0) if down else x
        x = jax.nn.relu(h + sc)
    p = params["fc"]
    return common.dense(x.reshape(x.shape[0], -1), p["w"], p["b"],
                        precision=precision)
