"""Production mesh builders.

Functions (never module-level constants) so importing this module touches no
jax device state.  Geometry per the assignment: one pod = 16x16 = 256 chips
(data x model); multi-pod = 2 pods = 512 chips with a leading "pod" axis
that carries only DP gradient reduction (DCN-friendly collectives), while
"model" carries TP/EP traffic (ICI).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if devices is None:
        n = 1
        for s in shape:
            n *= s
        devices = jax.devices()[:n]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first ``data * model`` local devices.
    Axes are ``Auto``: GSPMD propagates shardings from the placed params
    and inputs, and ``with_sharding_constraint`` pins them where the code
    asks (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    devices = jax.devices()[:data * model]
    return jax.make_mesh((data, model), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
