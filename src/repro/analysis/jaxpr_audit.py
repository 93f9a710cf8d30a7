"""Jaxpr auditor: prove what the compiled network actually traced to.

The fold-schedule engine's whole claim is "one conv block = one
``pallas_call``, nothing 4-D escapes the kernels".  Tests used to prove
this with ad-hoc ``str(jaxpr).count("pallas_call")`` scraping;
``audit_compiled`` promotes that into a structured API:

* ``pallas_calls``  — recursive count of pallas_call equations.
* ``top_counts``    — top-level primitive histogram, with ``jit``
                      equations resolved to their traced-function name
                      (``jnp.clip`` traces as a jit named ``"clip"``).
* ``ops4d``         — the same histogram restricted to equations touching
                      a 4-D tensor: rank-1 BN-statistic folds and the 2-D
                      fc head don't count, escaped epilogue tensor math
                      does.
* findings          — ``audit.pallas-count`` when a pallas-mode network
                      does not lower to exactly one call per conv layer;
                      ``audit.unfused-op`` when a *fused* network leaks a
                      4-D epilogue primitive (add/mul/clip/max/min/
                      reduce_max/custom_jvp_call) to the top level.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.analysis.report import Report

__all__ = ["AuditReport", "audit_compiled", "EPILOGUE_PRIMS"]

# primitives a fused epilogue must NOT leak to the top level on a 4-D
# tensor: bias/residual adds, BN affine mul/adds, relu (custom_jvp_call),
# relu6 (clip -> max/min), max-pool (reduce_max)
EPILOGUE_PRIMS = ("add", "mul", "clip", "max", "min", "reduce_max",
                  "custom_jvp_call")


def _sub_jaxprs(params):
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, jex_core.ClosedJaxpr):
                    yield w.jaxpr
                elif isinstance(w, jex_core.Jaxpr):
                    yield w


def _count_recursive(jaxpr, name: str) -> int:
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            n += 1
        for sub in _sub_jaxprs(e.params):
            n += _count_recursive(sub, name)
    return n


def _resolved_name(eqn) -> str:
    name = eqn.primitive.name
    if name == "jit":
        return eqn.params.get("name", name)
    return name


def _is_4d(eqn) -> bool:
    return any(getattr(v.aval, "ndim", 0) == 4 for v in eqn.invars)


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """What one compiled network's jaxpr contains (see module docstring)."""
    pallas_calls: int
    conv_layers: int
    mode: str                    # "pallas" | "reference"
    fused: bool
    n_eqns: int                  # top-level equation count
    top_counts: Dict[str, int]   # resolved top-level primitive histogram
    ops4d: Dict[str, int]        # ... restricted to 4-D-operand equations
    findings: Report

    @property
    def ok(self) -> bool:
        return self.findings.ok

    def top(self, name: str) -> int:
        return self.top_counts.get(name, 0)

    def op4d(self, name: str) -> int:
        return self.ops4d.get(name, 0)

    def as_dict(self) -> dict:
        return {"pallas_calls": self.pallas_calls,
                "conv_layers": self.conv_layers,
                "mode": self.mode, "fused": self.fused,
                "n_eqns": self.n_eqns,
                "top_counts": dict(self.top_counts),
                "ops4d": dict(self.ops4d),
                "report": self.findings.as_dict()}


def audit_compiled(net, params, input_shape: Tuple[int, ...]
                   ) -> AuditReport:
    """Trace ``net.apply`` on a zeros input of ``input_shape`` and audit
    the jaxpr.  ``net`` is a ``CompiledNetwork`` (``core/engine.py``)."""
    x0 = jnp.zeros(tuple(input_shape), jnp.float32)
    closed = jax.make_jaxpr(net.apply)(params, x0)
    jaxpr = closed.jaxpr
    # a jitted forward is one opaque jit equation: audit what it wraps
    while (len(jaxpr.eqns) == 1
           and jaxpr.eqns[0].primitive.name == "jit"):
        jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr

    pallas_calls = _count_recursive(jaxpr, "pallas_call")
    conv_layers = len(net.layer_schedules)
    top_counts: Counter = Counter(_resolved_name(e) for e in jaxpr.eqns)
    ops4d: Counter = Counter(_resolved_name(e) for e in jaxpr.eqns
                             if _is_4d(e))

    rep = Report()
    if net.mode == "pallas" and pallas_calls != conv_layers:
        rep.add("audit.pallas-count", "jaxpr",
                f"{pallas_calls} pallas_call equation(s) but the network "
                f"has {conv_layers} conv layers — fold kernels were "
                f"duplicated or lost")
    if net.mode == "pallas" and net.fused:
        for prim in EPILOGUE_PRIMS:
            leaked = ops4d.get(prim, 0)
            if leaked:
                rep.add("audit.unfused-op", "jaxpr",
                        f"{leaked} top-level 4-D {prim!r} equation(s): "
                        f"epilogue math escaped the fused kernels")
    return AuditReport(pallas_calls=pallas_calls, conv_layers=conv_layers,
                       mode=net.mode, fused=net.fused,
                       n_eqns=len(jaxpr.eqns),
                       top_counts=dict(top_counts), ops4d=dict(ops4d),
                       findings=rep)
