"""Building blocks of the benchmark's plain reference forwards.

Every contraction is float32 at ``Precision.HIGHEST`` (``"highest"``), or,
for the control that ``correct`` has to reject, the three-pass bfloat16
split that XLA calls ``HIGH`` (``"bf16x3"``): each operand is cut into a
bfloat16 head and a bfloat16 tail and the three products head*head,
head*tail and tail*head are accumulated in float32.  The split is written
out here, not left to XLA's precision flag, which the CPU ignores, so
that the control computes the same numbers on the CPU as on the chip.

JAX is imported inside the functions: the load-generating parent process
imports the model modules for their layer shapes and must never load JAX.
"""
from __future__ import annotations

import math

PRECISIONS = ("highest", "bf16x3")


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed``: ``PRNGKey``
    alone keeps only the low 32 bits."""
    import jax
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _split(a):
    """``a`` = head + tail in bfloat16.  The head is ``a`` rounded to
    bfloat16 (to nearest, ties to even) on its bit pattern; a float32 ->
    bfloat16 -> float32 round trip would do the same, but XLA on the TPU
    removes such a pair of converts (excess precision), which leaves a
    tail of 0."""
    import jax.numpy as jnp
    from jax import lax
    bits = lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    hi = lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _three_pass(op, a, b):
    import jax.numpy as jnp
    (ah, al), (bh, bl) = _split(a), _split(b)
    f = lambda u, v: op(u, v, jnp.float32)  # noqa: E731
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def _check(precision: str) -> None:
    if precision not in PRECISIONS:
        raise ValueError(f"unknown reference precision {precision!r}; "
                         f"want one of {PRECISIONS}")


def conv(x, w, b, *, stride: int, pad: int, precision: str):
    """NCHW x OIHW convolution plus bias."""
    from jax import lax
    _check(precision)

    def op(u, v, out):
        return lax.conv_general_dilated(
            u, v, window_strides=(stride, stride),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST if out is None else None,
            preferred_element_type=out)
    y = op(x, w, None) if precision == "highest" else _three_pass(op, x, w)
    return y + b[None, :, None, None]


def dense(x, w, b, *, precision: str):
    """(N, K) @ (K, D) plus bias."""
    import jax.numpy as jnp
    from jax import lax
    _check(precision)

    def op(u, v, out):
        return jnp.dot(u, v, precision=lax.Precision.HIGHEST
                       if out is None else None, preferred_element_type=out)
    y = op(x, w, None) if precision == "highest" else _three_pass(op, x, w)
    return y + b


def maxpool2(x):
    """2x2 max-pool, stride 2, on NCHW (odd edges dropped, as in VGG)."""
    n, c, h, w = x.shape
    x = x[:, :, :h // 2 * 2, :w // 2 * 2]
    return x.reshape(n, c, h // 2, 2, w // 2, 2).max(axis=(3, 5))


def he_normal(key, shape, fan_in: int):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
        2.0 / fan_in)


def bias(key, n: int, std: float):
    """Nonzero biases, so that the fused bias epilogue is checked."""
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, (n,), jnp.float32) * std
