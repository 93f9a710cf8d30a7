"""Plain reference of VGG-16, configuration D of Simonyan & Zisserman
(arXiv:1409.1556, Table 1): thirteen 3x3 stride-1 pad-1 convolutions,
each with bias and ReLU, a 2x2 max-pool after the 2nd, 4th, 7th, 10th
and 13th, then flatten and three fully connected layers (ReLU after the
first two).  Float32 throughout, NCHW.

The parameters are a dict ``{layer: {"w", "b"}}`` keyed by the names the
served graph reads (``conv1_1`` .. ``conv5_3``, ``fc1`` .. ``fc3``):
conv weights OIHW, dense weights (in, out).  ``init_params`` makes them
from a key, He-normal with nonzero biases; the benchmark hands the same
tree to the server and keeps it for this reference.

``width_mult`` below 1 narrows every layer the way the served model does
(``max(int(c * m), 1)``, fc at least 8) and exists for the CPU tests only;
the configurations run at 1.0.
"""
from __future__ import annotations

from chipbench.models import common

BIAS_STD = 0.1


def _w(c: int, m: float, floor: int = 1) -> int:
    return max(int(c * m), floor)


def layers(cfg: dict) -> list:
    """Every conv and dense layer, in order, with its shapes for one
    image.  A conv carries ``pool`` when the 2x2 max-pool follows it."""
    m = float(cfg.get("width_mult", 1.0))
    h = w = int(cfg["img"])
    cin = int(cfg["channels"])
    out = []
    widths = cfg["conv_widths"]
    block = 0
    conv_i = 0
    for i, c in enumerate(widths):
        if c == "M":
            continue
        if conv_i == 0 or widths[i - 1] == "M":
            block += 1
            conv_i = 0
        conv_i += 1
        cout = _w(int(c), m)
        pool = i + 1 < len(widths) and widths[i + 1] == "M"
        out.append({"kind": "conv", "name": f"conv{block}_{conv_i}",
                    "cin": cin, "cout": cout, "k": 3, "stride": 1,
                    "pad": 1, "h": h, "w": w, "pool": pool,
                    "residual": False})
        cin = cout
        if pool:
            h, w = h // 2, w // 2
    din = cin * h * w
    fcs = [_w(int(f), m, 8) for f in cfg["fc_widths"]] + [int(cfg["classes"])]
    for j, dout in enumerate(fcs, start=1):
        out.append({"kind": "dense", "name": f"fc{j}", "din": din,
                    "dout": dout, "relu": j < len(fcs)})
        din = dout
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
    for ly in layers(cfg):
        p = params[ly["name"]]
        if ly["kind"] == "conv":
            x = jax.nn.relu(common.conv(x, p["w"], p["b"], stride=1, pad=1,
                                        precision=precision))
            if ly["pool"]:
                x = common.maxpool2(x)
        else:
            x = common.dense(x.reshape(x.shape[0], -1), p["w"], p["b"],
                             precision=precision)
            if ly["relu"]:
                x = jax.nn.relu(x)
    return x
