"""Public jit'd wrappers for the fold-streamed kernels.

Dispatch policy:
  * On TPU, the Pallas kernels run compiled (interpret=False) with the
    dataflow selected per layer by the engine's perfmodel cost estimates.
  * On CPU (this container), the kernels run under ``interpret=True`` for
    validation; the default *production* path on CPU is the pure-jnp
    reference (XLA fuses it well), so that models remain fast to test.
  * ``impl`` forces a specific path:
      "fold_ws"   — weight-stationary Pallas (paper-faithful dataflow)
      "fold_os"   — output-stationary Pallas (beyond-paper optimized)
      "fold_dw"   — the dedicated depthwise kernel (groups == C == N_F,
                    no depth-fold reduction)
      "fold_auto" — Pallas with the dataflow picked by the engine's
                    cost model (``core/engine.py``)
      "im2col"    — GEMM baseline (what the paper argues against;
                    dense-only)
      "direct"    — shifted-matmul reference (grouped via ``groups``)
      "xla"       — lax.conv_general_dilated (feature_group_count)
    "direct" and "xla" contract at ``Precision.HIGHEST``: float32 on every
    backend, so they stay references on TPU too.
  * ``plan`` pins a pre-solved ``ConvBlockPlan`` (the engine's schedule
    cache passes these in, so repeated geometries skip re-planning).

Gradients: conv ops carry a ``jax.custom_vjp`` whose backward pass is
expressed with the same reference primitives (transposed conv relations),
so every impl is trainable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.epilogue import Epilogue, apply_epilogue
from repro.kernels import ref as _ref
from repro.kernels.conv1d_causal import conv1d_causal_folded
from repro.kernels.conv2d_ws import conv2d_folded

__all__ = ["conv2d", "conv2d_fused", "conv2d_int8", "conv1d_causal",
           "default_conv_impl"]


def default_conv_impl() -> str:
    return "fold_auto" if jax.default_backend() == "tpu" else "direct"


# "fold_ws_psum" is the PR-1 weight-stationary formulation (partial-sum
# folds staged in HBM, reduced with XLA) — kept for benchmarking only;
# "fold_dw" is the dedicated depthwise kernel (no depth-fold reduction)
_FOLD_IMPLS = ("fold_ws", "fold_os", "fold_auto", "fold_ws_psum", "fold_dw")


def _resolve_fold_dataflow(x, w, stride: int, pad: int, impl: str, plan,
                           groups: int = 1):
    """Map a fold impl string to (plan, dataflow) for the Pallas kernel."""
    if impl == "fold_ws_psum":
        return plan, "weight_stationary_psum"
    if impl == "fold_dw":
        return plan, "depthwise"
    if impl == "fold_auto":
        # one-shot engine planning (use models via the engine's
        # ScheduleCache / compile_network to amortize this); a supplied
        # plan is kept and only the dataflow is selected against it
        from repro.core.engine import plan_and_dataflow, select_dataflow
        from repro.core.loopnest import ConvLoopNest
        n, c, xh, xw = x.shape
        nf, _, r, s = w.shape
        cv = ConvLoopNest(n=n, nf=nf, c=c, r=r, s=s, x=xh, y=xw,
                          stride=stride, pad=pad, groups=groups)
        if plan is None:
            return plan_and_dataflow(cv)
        return plan, select_dataflow(cv, plan)
    return plan, ("weight_stationary" if impl == "fold_ws"
                  else "output_stationary")


def _conv2d_fwd_impl(x, w, stride: int, pad: int, impl: str,
                     plan=None, interpret=None, groups: int = 1):
    if impl == "xla":
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
            precision=jax.lax.Precision.HIGHEST)
    if impl == "direct":
        return _ref.conv2d_direct(x, w, stride, pad, groups)
    if impl == "im2col":
        if groups > 1:
            raise ValueError("the im2col GEMM baseline is dense-only "
                             "(grouped oracle: impl='direct' or 'xla')")
        return _ref.conv2d_im2col(x, w, stride, pad)
    if impl in _FOLD_IMPLS:
        plan, dataflow = _resolve_fold_dataflow(x, w, stride, pad, impl,
                                                plan, groups)
        xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        return conv2d_folded(xp, w, stride=stride, dataflow=dataflow,
                             plan=plan, interpret=interpret, groups=groups)
    raise ValueError(f"unknown conv impl {impl!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _conv2d(x, w, stride, pad, impl, plan, interpret, groups):
    return _conv2d_fwd_impl(x, w, stride, pad, impl, plan, interpret, groups)


def _conv2d_vjp_fwd(x, w, stride, pad, impl, plan, interpret, groups):
    return (_conv2d_fwd_impl(x, w, stride, pad, impl, plan, interpret,
                             groups), (x, w))


def _conv2d_vjp_bwd(stride, pad, impl, plan, interpret, groups, res, g):
    x, w = res
    if groups > 1:
        # grouped transposed-conv relations via the differentiable
        # reference (the hand-written dense relations below assume a full
        # depth reduction)
        _, vjp = jax.vjp(
            lambda xx, ww: _ref.conv2d_direct(xx, ww, stride, pad, groups),
            x, w)
        return vjp(g)
    n, c, xh, xw_ = x.shape
    nf, _, r, s = w.shape
    # dL/dx: transposed conv = conv of dilated g with spatially-flipped,
    # io-transposed w.
    g32 = g.astype(jnp.float32)
    w_flip = jnp.flip(w, axis=(2, 3)).transpose(1, 0, 2, 3)  # (C, NF, R, S)
    dx = jax.lax.conv_general_dilated(
        g32, w_flip.astype(jnp.float32), window_strides=(1, 1),
        padding=[(r - 1 - pad, r - 1 - pad + (xh + 2 * pad - r) % stride),
                 (s - 1 - pad, s - 1 - pad + (xw_ + 2 * pad - s) % stride)],
        lhs_dilation=(stride, stride),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    dx = dx[:, :, :xh, :xw_].astype(x.dtype)
    # dL/dw: correlate x with g.
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad))
                 ).astype(jnp.float32)
    p, q = g.shape[2], g.shape[3]
    dw = jnp.zeros((nf, c, r, s), dtype=jnp.float32)
    for ri in range(r):
        for si in range(s):
            win = xp[:, :, ri:ri + p * stride:stride,
                     si:si + q * stride:stride]
            dw = dw.at[:, :, ri, si].set(
                jnp.einsum("nfpq,ncpq->fc", g32, win))
    return dx, dw.astype(w.dtype)


_conv2d.defvjp(_conv2d_vjp_fwd, _conv2d_vjp_bwd)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, pad: int = 0,
           impl: Optional[str] = None, plan=None,
           interpret: Optional[bool] = None,
           groups: int = 1) -> jnp.ndarray:
    """Convolution through the fold framework.  x: NCHW, w: OIHW (the
    channel dim is per-group, C/groups, when ``groups > 1``).

    ``plan`` (a ``ConvBlockPlan``, typically from the engine's schedule
    cache), ``interpret`` and ``groups`` thread through to the fold
    kernels; all are static (hashable) and participate in jit caching.
    """
    return _conv2d(x, w, stride, pad, impl or default_conv_impl(), plan,
                   interpret, groups)


# ---------------------------------------------------------------------------
# Fused conv + epilogue (one pallas_call per conv→bias→ReLU(→pool) chain)
# ---------------------------------------------------------------------------


def _conv2d_fused_fwd_impl(x, w, b, scale, shift, residual, stride: int,
                           pad: int, epi: Epilogue, impl: str, plan,
                           interpret, groups: int = 1):
    if impl in _FOLD_IMPLS:
        plan, dataflow = _resolve_fold_dataflow(x, w, stride, pad, impl,
                                                plan, groups)
        xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        return conv2d_folded(xp, w, stride=stride, dataflow=dataflow,
                             plan=plan, interpret=interpret,
                             bias=b, epilogue=epi, residual=residual,
                             scale=scale, shift=shift, groups=groups)
    # non-Pallas impls: run the plain conv, then the reference epilogue
    # chain (XLA fuses it into the same computation anyway)
    y = _conv2d_fwd_impl(x, w, stride, pad, impl, plan, interpret, groups)
    return apply_epilogue(y, b, epi, residual, scale, shift)


# One custom_vjp covers every optional-operand combination: unused
# operands are passed as None (an empty pytree — no gradient slot), so a
# plain conv+bias, a BN-folded MobileNet block, and a ResNet residual
# block all share this op and all train end to end.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _conv2d_fused(x, w, b, scale, shift, res, stride, pad, epi, impl, plan,
                  interpret, groups):
    return _conv2d_fused_fwd_impl(x, w, b, scale, shift, res, stride, pad,
                                  epi, impl, plan, interpret, groups)


def _conv2d_fused_vjp_fwd(x, w, b, scale, shift, res, stride, pad, epi,
                          impl, plan, interpret, groups):
    out = _conv2d_fused_fwd_impl(x, w, b, scale, shift, res, stride, pad,
                                 epi, impl, plan, interpret, groups)
    return out, (x, w, b, scale, shift, res)


def _conv2d_fused_vjp_bwd(stride, pad, epi, impl, plan, interpret, groups,
                          saved, g):
    # rematerialize through the reference chain: the Pallas kernel never
    # stores pre-activation intermediates, so the backward pass recomputes
    # them (standard rematerialization; every impl stays trainable)
    x, w, b, scale, shift, res = saved

    def ref_chain(x, w, b, scale, shift, res):
        return apply_epilogue(_ref.conv2d_direct(x, w, stride, pad, groups),
                              b, epi, res, scale, shift)

    _, vjp = jax.vjp(ref_chain, x, w, b, scale, shift, res)
    return vjp(g)


_conv2d_fused.defvjp(_conv2d_fused_vjp_fwd, _conv2d_fused_vjp_bwd)


def conv2d_fused(x: jnp.ndarray, w: jnp.ndarray,
                 b: Optional[jnp.ndarray] = None, *, stride: int = 1,
                 pad: int = 0, epilogue: Optional[Epilogue] = None,
                 impl: Optional[str] = None, plan=None,
                 interpret: Optional[bool] = None,
                 residual: Optional[jnp.ndarray] = None,
                 scale: Optional[jnp.ndarray] = None,
                 shift: Optional[jnp.ndarray] = None,
                 groups: int = 1) -> jnp.ndarray:
    """Convolution with the epilogue flushed in-kernel.  x: NCHW, w: OIHW
    (per-group channel dim when ``groups > 1``), b: (NF,) per-filter bias
    (required when ``epilogue.bias``), scale/shift: (NF,) folded-BN
    vectors (required when ``epilogue.scale``), residual: (N, NF, P, Q)
    shortcut (required when ``epilogue.residual``).

    On the fold impls the epilogue executes inside the conv's single
    ``pallas_call`` at partial-sum flush time (``kernels/conv2d_ws.py``);
    the whole conv→bias/BN(→+shortcut)→ReLU[6](→pool) chain is one kernel
    launch and the pre-activation tensor never reaches HBM.  Output is
    (N, NF, P, Q), or (N, NF, P//2, Q//2) when ``epilogue.pool`` fuses the
    2x2 max-pool.
    """
    epi = epilogue if epilogue is not None else Epilogue(
        bias=b is not None, residual=residual is not None,
        scale=scale is not None)
    if epi.residual != (residual is not None):
        raise ValueError("epilogue.residual and the residual argument must "
                         "be supplied together")
    if epi.scale != (scale is not None and shift is not None):
        raise ValueError("epilogue.scale and the scale/shift arguments "
                         "must be supplied together")
    fwd_impl = impl or default_conv_impl()
    return _conv2d_fused(x, w, b, scale, shift, residual, stride, pad, epi,
                         fwd_impl, plan, interpret, groups)


# ---------------------------------------------------------------------------
# Int8 quantized conv + epilogue (inference-only)
# ---------------------------------------------------------------------------


def conv2d_int8(x: jnp.ndarray, w: jnp.ndarray,
                b: Optional[jnp.ndarray] = None, *, x_scale,
                stride: int = 1, pad: int = 0,
                epilogue: Optional[Epilogue] = None,
                impl: Optional[str] = None, plan=None,
                interpret: Optional[bool] = None,
                residual: Optional[jnp.ndarray] = None,
                scale: Optional[jnp.ndarray] = None,
                shift: Optional[jnp.ndarray] = None,
                groups: int = 1) -> jnp.ndarray:
    """Int8 quantized convolution with the requantizing epilogue.

    ``x``/``w`` are the *fp32* tensors; ``x_scale`` is the calibrated
    per-tensor activation scale (``core/quant.py:quantize_graph``).  The
    weights quantize per-output-channel at trace time, the activations
    quantize with the static calibrated scale, and the combined dequant
    ``w_scale * x_scale`` folds — together with bias and folded-BN —
    into the flush-time scale/shift affine (``requant_affine``), so the
    epilogue contract is unchanged: residual / ReLU[6] / pool run in fp32
    after the affine, and the fold impls still lower to one
    ``pallas_call`` per conv (streaming int8 blocks, accumulating int32).

    Inference-only by design: no custom VJP — straight-through gradients
    of a static-range PTQ net are a training technique (QAT) this engine
    does not model.  Output is fp32.
    """
    from repro.core.quant import (quantize_act_jit, quantize_weight_jit,
                                  requant_affine, requant_epilogue)
    epi = epilogue or Epilogue()
    if epi.bias and b is None:
        raise ValueError("epilogue.bias=True needs a bias vector")
    if epi.scale != (scale is not None and shift is not None):
        raise ValueError("epilogue.scale and the scale/shift arguments "
                         "must be supplied together")
    if epi.residual != (residual is not None):
        raise ValueError("epilogue.residual and the residual argument must "
                         "be supplied together")
    wq, w_scale = quantize_weight_jit(w)
    xq = quantize_act_jit(x, x_scale)
    comb_scale, comb_shift = requant_affine(
        w_scale * jnp.float32(x_scale), epi, b, scale, shift)
    epi_q = requant_epilogue(epi)
    fwd_impl = impl or default_conv_impl()
    if fwd_impl in _FOLD_IMPLS:
        plan, dataflow = _resolve_fold_dataflow(xq, wq, stride, pad,
                                                fwd_impl, plan, groups)
        xp = jnp.pad(xq, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        return conv2d_folded(xp, wq, stride=stride, dataflow=dataflow,
                             plan=plan, interpret=interpret,
                             epilogue=epi_q, residual=residual,
                             scale=comb_scale, shift=comb_shift,
                             groups=groups)
    # reference path: the same int8 operands through XLA's conv with an
    # int32 accumulator, then the identical requant epilogue chain — so
    # reference and pallas int8 modes share one quantization error
    acc = jax.lax.conv_general_dilated(
        xq, wq, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, preferred_element_type=jnp.int32)
    return apply_epilogue(acc.astype(jnp.float32), None, epi_q, residual,
                          comb_scale, comb_shift)


# ---------------------------------------------------------------------------


def _conv1d_fwd_impl(x, w, impl: str):
    if impl == "fold":
        from repro.core.engine import pallas_interpret_default
        return conv1d_causal_folded(x, w,
                                    interpret=pallas_interpret_default())
    return _ref.conv1d_causal_ref(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv1d(x, w, impl):
    return _conv1d_fwd_impl(x, w, impl)


def _conv1d_vjp_fwd(x, w, impl):
    return _conv1d_fwd_impl(x, w, impl), (x, w)


def _conv1d_vjp_bwd(impl, res, g):
    x, w = res
    k = w.shape[0]
    t = x.shape[1]
    g32 = g.astype(jnp.float32)
    # dx[b,t,d] = sum_k w[k,d] * g[b, t + K - 1 - k, d]  (anticausal)
    gp = jnp.pad(g32, ((0, 0), (0, k - 1), (0, 0)))
    dx = jnp.zeros(x.shape, jnp.float32)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    dw = jnp.zeros(w.shape, jnp.float32)
    for ki in range(k):
        dx += gp[:, k - 1 - ki:k - 1 - ki + t, :] * w[ki]
        dw = dw.at[ki].set(jnp.einsum("btd,btd->d", g32,
                                      xp[:, ki:ki + t, :]))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv1d.defvjp(_conv1d_vjp_fwd, _conv1d_vjp_bwd)


def conv1d_causal(x: jnp.ndarray, w: jnp.ndarray,
                  impl: Optional[str] = None) -> jnp.ndarray:
    """Depthwise causal conv1d.  x: (B, T, D), w: (K, D)."""
    if impl is None:
        impl = "fold" if jax.default_backend() == "tpu" else "ref"
    return _conv1d(x, w, impl)
