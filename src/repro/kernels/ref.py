"""Pure-jnp oracles for the Pallas kernels.

``conv2d_im2col`` doubles as the GEMM-lowering baseline the paper argues
against (§II): it materializes the Toeplitz/im2col patch matrix and runs one
big matmul, discarding the 7-D structure.  The benchmarks compare its memory
traffic against the fold-streamed kernel.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["conv2d_direct", "conv2d_im2col", "conv1d_causal_ref"]


_HIGHEST = jax.lax.Precision.HIGHEST


def _pad_nchw(x: jnp.ndarray, pad: int) -> jnp.ndarray:
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))


def conv2d_direct(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                  pad: int = 0, groups: int = 1) -> jnp.ndarray:
    """Direct 7-loop convolution, vectorized as R*S shifted matmuls.

    x: (N, C, X, Y)  w: (NF, C/groups, R, S)  ->  (N, NF, P, Q)

    This is the semantics oracle: it walks the (R, S) loops explicitly and
    accumulates partial sums, mirroring the paper's reduction order.  With
    ``groups > 1`` each filter contracts only its own group's C/G channel
    slice (the depth reduction runs per group; depthwise = groups == C).

    Contractions run at ``Precision.HIGHEST`` (TPU's default would round
    the operands to bf16).  The tap weights are broadcast over N so that
    each image is its own matmul: an image's result does not depend on
    how many images share its batch, which keeps it bitwise equal across
    serving bucket widths.
    """
    n, c, _, _ = x.shape
    nf, cw, r, s = w.shape
    assert c == cw * groups, (c, cw, groups)
    xp = _pad_nchw(x, pad)
    p = (xp.shape[2] - r) // stride + 1
    q = (xp.shape[3] - s) // stride + 1
    if groups == 1:
        acc = jnp.zeros((n, nf, p, q), dtype=jnp.float32)
        for ri in range(r):
            for si in range(s):
                win = xp[:, :, ri:ri + p * stride:stride,
                         si:si + q * stride:stride]      # (N, C, P, Q)
                wt = jnp.broadcast_to(w[:, :, ri, si], (n, nf, c))
                acc = acc + jnp.einsum("ncpq,nfc->nfpq", win, wt,
                                       preferred_element_type=jnp.float32,
                                       precision=_HIGHEST)
        return acc.astype(x.dtype)
    nfg = nf // groups
    xg = xp.reshape(n, groups, cw, xp.shape[2], xp.shape[3])
    wg = w.reshape(groups, nfg, cw, r, s)
    acc = jnp.zeros((n, groups, nfg, p, q), dtype=jnp.float32)
    for ri in range(r):
        for si in range(s):
            win = xg[:, :, :, ri:ri + p * stride:stride,
                     si:si + q * stride:stride]          # (N, G, Cg, P, Q)
            wt = jnp.broadcast_to(wg[:, :, :, ri, si], (n, groups, nfg, cw))
            acc = acc + jnp.einsum("ngcpq,ngfc->ngfpq", win, wt,
                                   preferred_element_type=jnp.float32,
                                   precision=_HIGHEST)
    return acc.reshape(n, nf, p, q).astype(x.dtype)


def conv2d_im2col(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                  pad: int = 0) -> jnp.ndarray:
    """The GEMM baseline: im2col + one (N*P*Q, C*R*S) x (C*R*S, NF) matmul."""
    n, c, _, _ = x.shape
    nf, _, r, s = w.shape
    xp = _pad_nchw(x, pad)
    p = (xp.shape[2] - r) // stride + 1
    q = (xp.shape[3] - s) // stride + 1
    cols = []
    for ri in range(r):
        for si in range(s):
            cols.append(xp[:, :, ri:ri + p * stride:stride,
                           si:si + q * stride:stride])
    # (N, C, R*S, P, Q) -> (N, P*Q, C*R*S), channel-major to match OIHW
    patches = jnp.stack(cols, axis=2)
    patches = patches.reshape(n, c * r * s, p * q).transpose(0, 2, 1)
    wmat = w.reshape(nf, c * r * s).T                     # (C*R*S, NF)
    out = jnp.einsum("nmk,kf->nmf", patches, wmat,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1).reshape(n, nf, p, q).astype(x.dtype)


def conv1d_causal_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv1d (Mamba2 / Zamba2 block).

    x: (B, T, D)   w: (K, D)   ->  (B, T, D)
    out[b, t, d] = sum_k w[k, d] * x[b, t - K + 1 + k, d]
    """
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    t = x.shape[1]
    acc = jnp.zeros(x.shape, dtype=jnp.float32)
    for ki in range(k):
        acc = acc + xp[:, ki:ki + t, :].astype(jnp.float32) * w[ki]
    return acc.astype(x.dtype)
