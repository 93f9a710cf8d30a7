"""MobileNetV2 — the grouped/depthwise stress test for the fold engine.

Where ResNet-18 generalized ``ScheduleKey`` to stride-2 and 1x1
geometries, MobileNetV2 is the model class the grouped fold geometry
exists for (MINISA's lightweight-conv coverage argument): every inverted
residual block is a 1x1 **expand** conv, a 3x3 **depthwise** conv (the
groups == C degenerate fold geometry with no depth reduction at all), and
a 1x1 linear **project** conv, all batch-normalized, activations ReLU6,
with a residual skip when the block neither strides nor changes width.
After ``fuse_graph`` each block is exactly three fused ``pallas_call``s
(two when the expand ratio is 1): expand = conv+BN+ReLU6, depthwise =
dw-conv+BN+ReLU6 on the dedicated no-reduction kernel, project =
conv+BN(+residual) — batch-norm folds to the epilogue's scale/shift at
trace time (``core/graph.py:bn_scale_shift``), so no standalone BN, ReLU6
or add op survives in the lowered jaxpr.

One block list and one graph builder serve two stride tables
(``StrideTable``): ``IMAGENET`` is the published network (Sandler et al.,
arXiv:1801.04381, Table 2, width 1.0: 3x3 stride-2 stem, 224px in, 7px at
the head, 1000 classes), registered as ``mobilenetv2_imagenet``;
``CIFAR``, the default and ``mobilenetv2``, is the same table with the
stem's and the 24-channel row's strides dropped to 1 (32px in, 4px at the
head, 10 classes).  Both end in global average pool and a single fc
classifier.  ``forward`` is the graph-free reference walk used as the test
oracle; ``to_graph`` exports the ``StreamGraph`` the engine lowers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import BucketCompiler, CompiledNetwork
from repro.core.graph import StreamGraph, bn_scale_shift
from repro.kernels.ops import conv2d

from repro.models.common import Axes, TreeMaker

__all__ = ["StrideTable", "TABLE2", "IMAGENET", "CIFAR", "block_specs",
           "n_convs", "n_residual_adds", "init_params", "forward",
           "to_graph", "compile_forward", "bucket_compiler", "n_classes"]

# (expand ratio t, output channels c, repeats n, first-block stride s) of
# every bottleneck row, Table 2 of the paper
TABLE2: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))
STEM_CH, HEAD_CH = 32, 1280
n_classes = 10          # CIFAR-scale default


@dataclasses.dataclass(frozen=True)
class StrideTable:
    """Where the network downsamples: the 3x3 stem's stride and the
    bottleneck rows (t, c, n, s)."""
    name: str
    stem_stride: int
    rows: Tuple[Tuple[int, int, int, int], ...]


IMAGENET = StrideTable("mobilenetv2_imagenet", 2, TABLE2)
# 32px inputs keep three downsamples: 32 -> 16 -> 8 -> 4
CIFAR = StrideTable("mobilenetv2", 1, tuple(
    (t, c, n, 1 if c == 24 else s) for t, c, n, s in TABLE2))


def _width(c: int, mult: float) -> int:
    return max(int(c * mult), 1)


def block_specs(width_mult: float = 1.0, strides: StrideTable = CIFAR
                ) -> List[Tuple[str, int, int, int, int, int]]:
    """The inverted-residual block list:
    (name, cin, cout, stride, expand_t, hidden).

    ``hidden = cin * t`` is the expanded width the depthwise conv runs at
    (its group count).  A block carries a residual skip iff it neither
    strides nor changes width — the structure is width-independent."""
    specs = []
    cin = _width(STEM_CH, width_mult)
    bi = 0
    for t, c, n, s in strides.rows:
        cout = _width(c, width_mult)
        for i in range(n):
            stride = s if i == 0 else 1
            specs.append((f"b{bi}", cin, cout, stride, t, cin * t))
            cin = cout
            bi += 1
    return specs


def n_convs(strides: StrideTable = CIFAR) -> int:
    """Conv count (= fused pallas_call count): stem + head + 3 per block
    (2 when t == 1) — 52 for either table."""
    return 2 + sum(2 + (t != 1)
                   for _, _, _, _, t, _ in block_specs(strides=strides))


def n_residual_adds(strides: StrideTable = CIFAR) -> int:
    """Blocks with an identity skip (stride 1, cin == cout) — their adds
    all flush inside the project conv's kernel when fused."""
    return sum(1 for _, cin, cout, stride, _, _
               in block_specs(strides=strides)
               if stride == 1 and cin == cout)


def init_params(key: jax.Array, *, width_mult: float = 1.0,
                img: int = 32, classes: int = n_classes,
                dtype=jnp.float32) -> Dict[str, Any]:
    from repro.models.common import DTypePolicy
    tm = TreeMaker("init", key=key,
                   dtype_policy=DTypePolicy(param=dtype, compute=dtype))

    def conv_entry(cout: int, cin: int, k: int) -> Dict[str, Any]:
        # no bias: batch-norm's shift is the additive term
        return {"w": tm.param((cout, cin, k, k),
                              (Axes.HEADS, Axes.EMBED, None, None))}

    def bn_entry(cout: int) -> Dict[str, Any]:
        # identity statistics at init; inference folds them to scale/shift
        return {"gamma": tm.param((cout,), (Axes.HEADS,), init="ones"),
                "beta": tm.param((cout,), (Axes.HEADS,), init="zeros"),
                "mean": tm.param((cout,), (Axes.HEADS,), init="zeros"),
                "var": tm.param((cout,), (Axes.HEADS,), init="ones")}

    stem = _width(STEM_CH, width_mult)
    p: Dict[str, Any] = {"stem": conv_entry(stem, 3, 3),
                         "stem_bn": bn_entry(stem)}
    for name, cin, cout, _, t, hidden in block_specs(width_mult):
        if t != 1:
            p[f"{name}_exp"] = conv_entry(hidden, cin, 1)
            p[f"{name}_exp_bn"] = bn_entry(hidden)
        p[f"{name}_dw"] = conv_entry(hidden, 1, 3)       # (C, 1, R, S)
        p[f"{name}_dw_bn"] = bn_entry(hidden)
        p[f"{name}_proj"] = conv_entry(cout, hidden, 1)
        p[f"{name}_proj_bn"] = bn_entry(cout)
    head = max(_width(HEAD_CH, width_mult), 8)
    last = block_specs(width_mult)[-1][2]
    p["head"] = conv_entry(head, last, 1)
    p["head_bn"] = bn_entry(head)
    # global average pool feeds the classifier, so fc is width-only
    p["fc"] = {"w": tm.param((head, classes), (Axes.EMBED, Axes.VOCAB)),
               "b": tm.param((classes,), (Axes.VOCAB,), init="zeros")}
    return p


def to_graph(strides: StrideTable = CIFAR) -> StreamGraph:
    """Export MobileNetV2 as a streaming graph.  Every conv is followed by
    a ``batchnorm`` node (own parameter entry) and — except the linear
    projection — ``relu6``; the fusion pass folds each chain into the
    conv's epilogue, and the identity-skip ``residual_add`` into the
    project conv (``Epilogue(scale=True, residual=True)``)."""
    g = StreamGraph(name=strides.name)

    def conv_bn(name: str, src=None, *, stride=1, pad=0, dw=False,
                act=True) -> str:
        if dw:
            g.depthwise_conv(name, src, stride=stride, pad=1)
        else:
            g.conv(name, src, stride=stride, pad=pad)
        g.batchnorm(param=f"{name}_bn")
        if act:
            g.relu6()
        return g.output

    prev = conv_bn("stem", stride=strides.stem_stride, pad=1)
    for name, cin, cout, stride, t, _ in block_specs(strides=strides):
        h = prev
        if t != 1:
            h = conv_bn(f"{name}_exp", h)
        h = conv_bn(f"{name}_dw", h, stride=stride, dw=True)
        h = conv_bn(f"{name}_proj", h, act=False)        # linear bottleneck
        if stride == 1 and cin == cout:
            prev = g.residual_add(f"{name}_add", h, prev)
        else:
            prev = h
    conv_bn("head", prev)
    g.global_avgpool()
    g.flatten()
    g.dense("fc")
    return g


def forward(params: Dict[str, Any], x: jnp.ndarray,
            impl: Optional[str] = None,
            strides: StrideTable = CIFAR) -> jnp.ndarray:
    """Graph-free per-layer reference walk (the test oracle): x is
    (N, 3, H, W) NCHW -> (N, classes) logits.  ``impl`` selects the conv
    implementation as in ``kernels/ops.conv2d`` (grouped layers pass
    their group count through)."""

    def conv_bn(name, x, stride, pad, dw=False, act=True):
        w = params[name]["w"]
        # depthwise weights are (C, 1, R, S): the group count is the
        # actual (width-scaled) channel count, read off the tensor
        y = conv2d(x, w, stride=stride, pad=pad, impl=impl,
                   groups=int(w.shape[0]) if dw else 1)
        scale, shift = bn_scale_shift(params[f"{name}_bn"])
        y = y * scale[None, :, None, None] + shift[None, :, None, None]
        return jnp.clip(y, 0.0, 6.0) if act else y

    x = conv_bn("stem", x, strides.stem_stride, 1)
    for name, cin, cout, stride, t, _ in block_specs(strides=strides):
        h = x
        if t != 1:
            h = conv_bn(f"{name}_exp", h, 1, 0)
        h = conv_bn(f"{name}_dw", h, stride, 1, dw=True)
        h = conv_bn(f"{name}_proj", h, 1, 0, act=False)
        x = x + h if (stride == 1 and cin == cout) else h
    x = conv_bn("head", x, 1, 0)
    x = x.mean(axis=(2, 3))                  # global average pool
    return x @ params["fc"]["w"] + params["fc"]["b"]


def compile_forward(params: Dict[str, Any], *, img: int,
                    **compile_kw) -> CompiledNetwork:
    """Compile MobileNetV2 into a static fold schedule through the shared
    graph lowering (``models/zoo.py:compile_forward``) — the depthwise
    layers exercise the ``fold_dw`` kernel and the grouped ``ScheduleKey``
    axis; ``net.fold_reuse()`` reports the per-model fold-reuse metric."""
    from repro.models import zoo
    return zoo.compile_forward("mobilenetv2", params, img=img, **compile_kw)


def bucket_compiler(params: Dict[str, Any], *, img: int,
                    **compile_kw) -> BucketCompiler:
    """Serving compile surface: one memoized compiled forward per batch
    bucket over one shared ``ScheduleCache`` — see ``serve/vision.py``."""
    from repro.models import zoo
    return zoo.bucket_compiler("mobilenetv2", params, img=img, **compile_kw)
