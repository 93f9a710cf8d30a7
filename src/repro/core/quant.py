"""Int8 quantization scheme for fold streaming (DESIGN.md §12).

The paper's argument is that fold throughput is bounded by bytes moved
per fold, not FLOPs — so the single biggest lever the engine has left is
streaming the weight and activation blocks at one byte per element
instead of four.  This module owns the *scheme*; the kernels
(``kernels/conv2d_ws.py``), the engine (``core/engine.py``) and the
traffic model consume it:

* **Weights** — symmetric per-output-channel scales (axis 0 of the OIHW
  tensor): ``w[o] ~= w_q[o] * w_scale[o]`` with ``w_q`` int8 in
  [-127, 127].  Per-channel costs one (NF,) fp32 vector and removes the
  cross-filter dynamic-range coupling that per-tensor weight scales
  suffer from.
* **Activations** — per-tensor scales from a calibration pass
  (``quantize_graph``): the fp32 reference forward runs over a small
  batch and each conv records the max |x| reaching it.  Zero-padding is
  exact in the quantized domain (``Q(0) == 0``), so convs quantize
  *before* spatial padding.
* **Accumulation** — int8 x int8 products accumulate in **int32** (the
  kernels' VMEM scratch switches dtype); ``int32_accumulator_bound``
  proves the worst case ``127 * 127 * (C/G) * R * S`` fits, and
  ``analysis/plan_check.check_plan(precision="int8")`` gates it
  statically (finding ``quant.acc-overflow``).
* **Requantization** — the combined dequant scale
  ``dq[o] = w_scale[o] * x_scale`` folds into the *existing* epilogue
  scale/shift slot (the PR-5 BN-fold hook).  With the fp32 epilogue
  order ``(acc + bias) * bn_scale + bn_shift`` the int8 flush is the
  single affine

      y = acc_i32 * (dq * bn_scale) + (bias * bn_scale + bn_shift)

  (``requant_affine``), after which residual / ReLU / ReLU6 / pool run
  unchanged in fp32 — no new epilogue stages, bitwise-shared flush code.

``distributed/compression.py`` re-exports ``quantize_int8`` /
``dequantize_int8`` from here (the gradient-compression path and the
fold-streaming path share one definition of the per-tensor scheme).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.epilogue import Epilogue, apply_epilogue, maxpool2x2
from repro.core.graph import (DEPTHWISE, GraphError, as_graph,
                              bn_scale_shift)

__all__ = [
    "PRECISIONS",
    "INT8_QMAX",
    "INT32_ACC_MAX",
    "quantize_int8",
    "dequantize_int8",
    "weight_scales",
    "quantize_weight",
    "act_scale",
    "quantize_act",
    "quantize_act_jit",
    "quantize_weight_jit",
    "requant_epilogue",
    "requant_affine",
    "int32_accumulator_bound",
    "QuantRecipe",
    "quantize_graph",
    "default_calib_batch",
]

PRECISIONS = ("fp32", "int8")
INT8_QMAX = 127.0
INT32_ACC_MAX = 2 ** 31 - 1


def check_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r} "
                         f"(want one of {PRECISIONS})")
    return precision


# --------------------------------------------------------------------------
# Scalar / tensor quantizers
# --------------------------------------------------------------------------

def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tensor symmetric int8: ``x ~= q * scale`` with q in [-127, 127].
    Returns ``(q, scale)``; the scale is a scalar fp32 array."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32))) + 1e-12
    scale = amax / INT8_QMAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    """Invert ``quantize_int8`` (up to the scheme's rounding error:
    ``|x - dequant(quant(x))| <= scale / 2`` elementwise, clip-free by
    construction of the scale)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def weight_scales(w: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Symmetric per-output-channel scales for an OIHW weight tensor:
    one fp32 scale per filter (axis 0), ``amax / 127`` over the filter's
    own taps."""
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes)
    return amax / INT8_QMAX + 1e-12


def quantize_weight(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric int8 weights: ``(w_q, w_scale)`` with
    ``w_q`` int8 OIHW and ``w_scale`` an (NF,) fp32 vector."""
    scale = weight_scales(w)
    shape = (-1,) + (1,) * (w.ndim - 1)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale.reshape(shape)),
                 -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scale


def act_scale(x: jnp.ndarray) -> float:
    """Per-tensor activation scale from a calibration tensor (max |x| over
    the whole batch), as a concrete python float — activation scales are
    compile-time constants baked into the lowered network."""
    return float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / INT8_QMAX + 1e-12


def quantize_act(x: jnp.ndarray, scale) -> jnp.ndarray:
    """Quantize an activation tensor with a calibrated per-tensor scale.
    Out-of-calibration values saturate at ±127 (standard static-range
    post-training quantization)."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -INT8_QMAX, INT8_QMAX).astype(jnp.int8)


# jit-wrapped entry points for use inside a traced forward: each call is
# one opaque ``jit`` equation named after the function, so the jaxpr
# auditor (``analysis/jaxpr_audit.py``) sees a deliberate quantize step —
# not a leaked 4-D clip/mul that would trip ``audit.unfused-op``.
quantize_act_jit = jax.jit(quantize_act)
quantize_weight_jit = jax.jit(quantize_weight)


# --------------------------------------------------------------------------
# Epilogue requantization (the PR-5 BN-fold hook)
# --------------------------------------------------------------------------

def requant_epilogue(epi: Optional[Epilogue]) -> Epilogue:
    """The epilogue the int8 kernel flushes: dequant rides the scale/shift
    affine slot, and the bias column is folded *into* that affine
    (``requant_affine``), so ``bias`` is always off and ``scale`` always
    on.  Residual / ReLU / ReLU6 / pool pass through unchanged."""
    epi = epi or Epilogue()
    return dataclasses.replace(epi, bias=False, scale=True)


def requant_affine(dq: jnp.ndarray, epi: Optional[Epilogue],
                   bias: Optional[jnp.ndarray],
                   bn_scale: Optional[jnp.ndarray],
                   bn_shift: Optional[jnp.ndarray]
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold dequant + bias + BN into one flush-time affine.

    fp32 flush order is ``(conv + bias) * bn_scale + bn_shift``; with
    ``conv ~= acc * dq`` that is ``acc * (dq * bn_scale) +
    (bias * bn_scale + bn_shift)`` — exactly the existing scale/shift
    epilogue slot.  ``dq`` is the (NF,) combined dequant vector
    (``w_scale * x_scale``)."""
    epi = epi or Epilogue()
    dq = dq.astype(jnp.float32)
    nf = dq.shape[0]
    scale = dq * bn_scale.astype(jnp.float32) if epi.scale else dq
    shift = jnp.zeros((nf,), jnp.float32)
    if epi.bias:
        b32 = bias.astype(jnp.float32)
        shift = b32 * bn_scale.astype(jnp.float32) if epi.scale else b32
    if epi.scale:
        shift = shift + bn_shift.astype(jnp.float32)
    return scale, shift


def int32_accumulator_bound(cg: int, r: int, s: int) -> int:
    """Worst-case |int32 accumulator| for one output element: ``C/G * R *
    S`` products of magnitude at most ``127 * 127``.  Must stay below
    ``INT32_ACC_MAX`` for the depth-fold reduction to be overflow-free
    (at VGG's deepest nest, 512*3*3 * 16129 ~= 7.4e7 — three decimal
    orders of headroom)."""
    return int(INT8_QMAX) * int(INT8_QMAX) * int(cg) * int(r) * int(s)


# --------------------------------------------------------------------------
# Graph calibration pass
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantRecipe:
    """Per-conv-node scales produced by ``quantize_graph``.

    ``act_scales`` maps conv node name -> per-tensor input-activation
    scale (a python float — a compile-time constant of the lowered
    network).  ``w_scales`` maps conv node name -> the (NF,) per-output-
    channel weight scale vector, recorded for reporting; the lowering
    recomputes it from the live params so retrained weights stay
    consistent."""
    act_scales: Dict[str, float]
    w_scales: Dict[str, Any]

    def scale_for(self, node_name: str) -> float:
        try:
            return self.act_scales[node_name]
        except KeyError:
            raise GraphError(
                f"{node_name}: no calibrated activation scale — the "
                "QuantRecipe was built for a different graph "
                "(re-run quantize_graph)") from None


def default_calib_batch(input_shape: Tuple[int, ...],
                        batch: int = 4) -> jnp.ndarray:
    """The deterministic fallback calibration batch
    ``compile_network(precision="int8")`` uses when the caller supplies
    no recipe: standard-normal images, PRNGKey(0), at most ``batch``
    samples."""
    n = max(1, min(int(input_shape[0]), batch))
    shape = (n,) + tuple(int(d) for d in input_shape[1:])
    return jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)


def quantize_graph(graph, params: Dict[str, Any],
                   calib_batch: jnp.ndarray) -> QuantRecipe:
    """Calibration pass over a ``StreamGraph``: run the fp32 reference
    forward on ``calib_batch`` and record, per conv node, the per-tensor
    input-activation scale and the per-output-channel weight scales.

    Runs on the *pre-fusion* graph the models export (fusion preserves
    conv node names, so the recipe keys match the fused lowering).  Pure
    reference semantics — no Pallas, no schedule cache."""
    from repro.kernels.ref import conv2d_direct
    g = as_graph(graph)
    env: Dict[str, jnp.ndarray] = {g.input: calib_batch}
    act_scales: Dict[str, float] = {}
    w_scales: Dict[str, Any] = {}
    for nd in g.nodes:
        srcs = [env[i] for i in nd.all_inputs()]
        x = srcs[0]
        if nd.op == "conv":
            w = params[nd.param]["w"]
            groups = x.shape[1] if nd.groups == DEPTHWISE else nd.groups
            act_scales[nd.name] = act_scale(x)
            w_scales[nd.name] = weight_scales(w)
            y = conv2d_direct(x, w, nd.stride, nd.pad, groups)
            if nd.epilogue is not None:
                epi = nd.epilogue
                if epi.pool and (y.shape[2] < 2 or y.shape[3] < 2):
                    epi = dataclasses.replace(epi, pool=None)
                b = params[nd.param]["b"] if epi.bias else None
                scale = shift = None
                if epi.scale:
                    scale, shift = bn_scale_shift(params[nd.bn_param])
                res = env[nd.residual] if epi.residual else None
                y = apply_epilogue(y, b, epi, res, scale, shift)
            env[nd.name] = y
        elif nd.op == "bias":
            env[nd.name] = x + params[nd.param]["b"][None, :, None, None]
        elif nd.op == "batchnorm":
            scale, shift = bn_scale_shift(params[nd.param])
            env[nd.name] = (x * scale[None, :, None, None]
                            + shift[None, :, None, None])
        elif nd.op == "relu":
            env[nd.name] = jax.nn.relu(x)
        elif nd.op == "relu6":
            env[nd.name] = jnp.clip(x, 0.0, 6.0)
        elif nd.op == "global_avgpool":
            env[nd.name] = x.mean(axis=(2, 3), keepdims=True)
        elif nd.op == "maxpool2":
            env[nd.name] = maxpool2x2(x)
        elif nd.op == "residual_add":
            env[nd.name] = srcs[0] + srcs[1]
        elif nd.op == "flatten":
            env[nd.name] = x.reshape(x.shape[0], -1)
        elif nd.op == "dense":
            pd = params[nd.param]
            env[nd.name] = x @ pd["w"] + pd["b"]
        else:  # pragma: no cover — StreamGraph construction validates ops
            raise GraphError(f"{nd.name}: cannot calibrate op {nd.op!r}")
    return QuantRecipe(act_scales=act_scales, w_scales=w_scales)
