"""Cached fold-schedule execution engine (DESIGN.md §4, §7).

The paper compiles the 7-D loop nest into a *static* fold schedule once and
then streams data through it; its headline end-to-end numbers (>90% PE
utilization, 12.7 KIPS) rest on the observation that a network's conv
layers collapse to a handful of distinct loop-nest geometries whose
schedules can be reused ("fold reuse").  This module is the software
analogue of that compile-once discipline — deliberately model-agnostic:
models describe themselves as streaming graphs (``core/graph.py``) and the
engine knows nothing about any particular network.

* ``ScheduleKey`` canonicalizes a ``ConvLoopNest`` to its *filter-fold
  geometry* ``(N_F, C, R, S, stride, dilation)``.  The key deliberately
  excludes the spatial extents (X, Y, and the batch N): the Filter Fold —
  the weight block resident in VMEM — depends only on the filter tensor,
  while the Image Folds merely stream more or fewer positions through it.
  A deep trunk's conv layers therefore collapse to a few distinct keys.

* ``ConvSchedule`` is one cached schedule: the ``ConvBlockPlan`` solved
  once per key, plus the dataflow (``weight_stationary`` vs
  ``output_stationary``) selected from ``core/perfmodel.py`` cost constants
  instead of a hard-coded default.

* ``ScheduleCache`` is the registry: hit/miss/replan counters double as the
  paper's fold-reuse metric, and the partially-applied Pallas kernels are
  memoized per (key, interpret) so repeated layers share one closure.

* ``compile_network`` lowers a ``StreamGraph`` (or a legacy conv-spec
  sequence) through one shared ``ScheduleCache``, builds the whole-network
  static schedule up front, and returns a jit-compiled end-to-end forward
  with the schedule baked in.

* the ``interpret`` policy (``resolve_execution``) auto-selects real Pallas
  lowering when a TPU backend is present and falls back cleanly to the
  fused-XLA reference path otherwise, so the compiled network is always the
  fastest correct option for the current backend.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.epilogue import (Epilogue, epilogue_out_hw, maxpool2x2)
from repro.core.graph import (DEPTHWISE, GraphError, StreamGraph, as_graph,
                              bn_scale_shift, fuse_graph)
from repro.core.loopnest import ConvLoopNest
from repro.core.mapping import (VMEM_LIMIT_BYTES, WS_ACC_BYTES_LIMIT,
                                ConvBlockPlan, conv_working_set,
                                plan_conv_blocks, serving_conv_plan)
from repro.core.perfmodel import MavecConfig

__all__ = [
    "ScheduleKey",
    "ConvSchedule",
    "CacheStats",
    "ScheduleCache",
    "Epilogue",
    "dataflow_costs",
    "dataflow_traffic_bytes",
    "select_dataflow",
    "plan_and_dataflow",
    "tuning_candidates",
    "measure_schedule_ms",
    "autotune_schedule",
    "pallas_interpret_default",
    "resolve_execution",
    "CompiledNetwork",
    "compile_network",
    "BucketCompiler",
]


# --------------------------------------------------------------------------
# Canonical schedule keys
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScheduleKey:
    """Filter-fold geometry of a conv loop nest — the schedule identity.

    Spatial extents (X, Y) and batch (N) are excluded: they change how many
    image folds stream through the schedule, not the schedule itself (the
    block plan is clamped to the actual dims at kernel-bind time).
    """
    nf: int
    c: int
    r: int
    s: int
    stride: int
    dilation: int = 1
    groups: int = 1      # channel groups (depthwise = groups == c == nf);
    #                      part of the filter-fold identity: the same
    #                      (nf, c, r, s) tensor folds differently per group
    precision: str = "fp32"   # streamed dtype ("fp32" | "int8"): an int8
    #                           filter fold is a different resident tensor
    #                           (1 byte/elem, int32 accumulator), so it is
    #                           a different schedule identity

    @classmethod
    def from_loopnest(cls, cv: ConvLoopNest,
                      precision: str = "fp32") -> "ScheduleKey":
        return cls(nf=cv.nf, c=cv.c, r=cv.r, s=cv.s,
                   stride=cv.stride, dilation=cv.dilation, groups=cv.groups,
                   precision=precision)

    def __str__(self) -> str:
        g = f"/g{self.groups}" if self.groups > 1 else ""
        pr = f"/{self.precision}" if self.precision != "fp32" else ""
        return f"{self.r}x{self.s}x{self.c}->{self.nf}/s{self.stride}{g}{pr}"


@dataclasses.dataclass(frozen=True)
class ConvSchedule:
    """One compiled fold schedule: block plan + selected dataflow.

    ``nest`` records the loop nest the plan was solved against (the largest
    spatial extent seen for this key); ``costs`` are the estimated cycles
    per dataflow that drove the selection, kept for reporting.
    """
    key: ScheduleKey
    nest: ConvLoopNest
    plan: ConvBlockPlan
    dataflow: str                              # weight_/output_stationary
    costs: Tuple[Tuple[str, float], ...]       # (dataflow, est. cycles)
    source: str = "model"                      # model | measured | loaded
    measured_ms: Optional[float] = None        # winner's median, if measured
    timings: Tuple[Tuple[str, float], ...] = ()  # (candidate, median ms)

    @property
    def cost_dict(self) -> Dict[str, float]:
        return dict(self.costs)

    @property
    def tuned(self) -> bool:
        return self.source in ("measured", "loaded")

    def impl(self) -> str:
        """The ``kernels.ops.conv2d`` impl string for this dataflow."""
        if self.dataflow == "depthwise":
            return "fold_dw"
        return ("fold_ws" if self.dataflow == "weight_stationary"
                else "fold_os")


# --------------------------------------------------------------------------
# Dataflow selection from perfmodel cost estimates
# --------------------------------------------------------------------------

def stream_bytes_per_elem(precision: str, bytes_per_elem: int = 4) -> int:
    """Bytes per *streamed* weight/activation element at a precision.
    Outputs (and the accumulator) stay at ``bytes_per_elem`` — the int8
    path dequantizes at flush time and writes fp32."""
    if precision == "int8":
        return 1
    if precision == "fp32":
        return bytes_per_elem
    raise ValueError(f"unknown precision {precision!r} (want fp32|int8)")


def traffic_components(cv: ConvLoopNest, plan: ConvBlockPlan, dataflow: str,
                       bytes_per_elem: int = 4,
                       precision: str = "fp32") -> Dict[str, float]:
    """Per-tensor-class HBM byte split for one dataflow formulation —
    weights and input at the *streamed* dtype, output at the accumulate/
    write dtype.  ``dataflow_traffic_bytes`` sums these; benchmarks
    report them so per-dtype totals are visible (the int8 win is on the
    weight/input streams only)."""
    bpe = bytes_per_elem
    sbpe = stream_bytes_per_elem(precision, bytes_per_elem)
    sizes = cv.tensor_sizes()
    w_bytes = sizes["filter"] * sbpe
    in_bytes = cv.n * cv.c * cv.padded_x * cv.padded_y * sbpe
    out_bytes = sizes["output"] * bpe
    clamped = plan.clamped(cv.nf, cv.c, cv.p)
    g_nf, g_c, g_p = clamped.grid
    if cv.depthwise:
        if dataflow != "depthwise":
            raise ValueError(f"depthwise nest has no {dataflow!r} "
                             "formulation")
        return {"weights": w_bytes, "input": in_bytes, "output": out_bytes}
    g_nfg = max(g_nf // cv.groups, 1)       # nf folds per group
    # psum staging: every depth fold's partial-sum tensor is written to
    # HBM and read back by the XLA reduce, then the final output is
    # written — (2*g_c + 1) output-sized transfers.  This holds at
    # g_c == 1 too (the partial tensor still round-trips), which is what
    # lets the model distinguish psum staging from the in-kernel
    # accumulator even for single-depth-fold layers.  Partial sums are
    # always accumulator-width (fp32/int32), never int8.
    psum = (2 * g_c + 1) * out_bytes
    acc_bytes = clamped.nf_block * g_p * clamped.p_block * cv.q * bpe
    ws_out = out_bytes if acc_bytes <= WS_ACC_BYTES_LIMIT else psum
    if dataflow == "weight_stationary":
        return {"weights": w_bytes, "input": g_nfg * in_bytes,
                "output": ws_out}
    if dataflow == "weight_stationary_psum":
        return {"weights": w_bytes, "input": g_nfg * in_bytes,
                "output": psum}
    if dataflow == "output_stationary":
        return {"weights": g_p * w_bytes, "input": g_nfg * in_bytes,
                "output": out_bytes}
    raise ValueError(f"unknown dataflow {dataflow!r}")


def dataflow_traffic_bytes(cv: ConvLoopNest, plan: ConvBlockPlan,
                           bytes_per_elem: int = 4,
                           precision: str = "fp32") -> Dict[str, float]:
    """Modeled HBM bytes per dataflow formulation — the single source of
    truth shared by ``dataflow_costs`` and ``benchmarks/kernel_bench``.

    ``weight_stationary_psum`` is the PR-1 staging formulation; the
    in-kernel ``weight_stationary`` entry prices the psum fallback the
    kernel takes when its full-height accumulator would exceed
    ``WS_ACC_BYTES_LIMIT`` (the epilogue-fused kernel falls back to
    output-stationary instead, which this tensor-level model cannot see —
    psum staging is the conservative price for both).

    Grouped nests stream each group's input slice only through that
    group's filter folds, so the WS input re-stream factor is the
    *per-group* nf-fold count, not the global one.  A depthwise nest has
    a single ``"depthwise"`` entry — every tensor is touched exactly once
    (no depth folds to re-stream anything for).

    ``precision="int8"`` prices the weight/activation streams at one byte
    per element (``traffic_components``); outputs and staged partial sums
    stay accumulator-width.
    """
    dws = (("depthwise",) if cv.depthwise else
           ("weight_stationary", "weight_stationary_psum",
            "output_stationary"))
    return {df: sum(traffic_components(cv, plan, df, bytes_per_elem,
                                       precision).values())
            for df in dws}


def dataflow_costs(cv: ConvLoopNest, plan: ConvBlockPlan,
                   cfg: Optional[MavecConfig] = None,
                   precision: str = "fp32") -> Dict[str, float]:
    """Estimated execution cycles of each dataflow for this layer.

    Both dataflows reduce depth folds in-kernel (PR 2) and do the same
    MACs; they differ in off-chip traffic and on-chip accumulator size:

      weight_stationary  — weights fetched once; every NF fold re-streams
        the input; the output accumulates in a *full-height* VMEM scratch
        and hits HBM exactly once.  When that accumulator cannot fit
        ``WS_ACC_BYTES_LIMIT`` the kernel falls back to staging partial-
        sum folds through HBM (the PR-1 ``weight_stationary_psum``
        traffic), and the model prices exactly that fallback.
      output_stationary  — partial sums live in a block-sized VMEM
        accumulator and the output is written exactly once, but the weight
        block is re-fetched for every P fold (the grid re-walks the C
        folds per P).

    Traffic is converted to cycles with the ``MavecConfig`` off-chip
    bandwidth and clock; the shared compute term is MACs spread over the
    tile's PEs.  Purely geometric — deterministic for a given nest.

    Calibration (PR 2, methodology — ``benchmarks/kernel_bench.calibrate``):
    measured on this container's CPU backend with the Pallas kernels under
    ``interpret=True`` (the roadmap's real-TPU validation is still open),
    median-of-5 after one warmup, per-kernel over three small geometries
    with g_c forced > 1.  Findings: single-kernel interpret-mode wall time
    is dispatch-dominated, not bandwidth-dominated — the model's psum
    ratio (1.7-2.2x extra WS traffic for the PR-1 formulation) showed up
    as measured ratios of only 0.5-1.1x, because XLA's host-side psum
    reduce is nearly free on CPU while the in-kernel reduction pays per-
    grid-step ``pl.when`` overhead.  At the *network* level the fused
    in-kernel path is what wins on this backend (benchmarks/fig9: ~1.2x
    per image, fused vs unfused pallas engine).  Consequently the absolute
    ``offchip_gbps``/``freq_ghz`` constants are kept at the paper's §V.A
    values — they model the target accelerator, not this CI host — and
    this function's ranking is treated as the *no-tuning default only*:
    ``autotune_schedule`` below replaces it with real measurements
    (pay-once, JSON-persisted) whenever trusting the model is not good
    enough.  Re-run ``calibrate()`` on a real TPU before trusting absolute
    cycle counts.
    """
    cfg = cfg or MavecConfig()
    traffic = dataflow_traffic_bytes(cv, plan, cfg.bytes_per_elem, precision)

    def cycles(traffic_bytes: float) -> float:
        return traffic_bytes / (cfg.offchip_gbps * 1e9) * (cfg.freq_ghz * 1e9)

    compute = cv.macs / cfg.tile_pes
    if cv.depthwise:
        # one dataflow exists: no depth folds, so weight- vs output-
        # stationary is a distinction without a difference
        return {"depthwise": compute + cycles(traffic["depthwise"])}
    return {
        "weight_stationary": compute + cycles(traffic["weight_stationary"]),
        "output_stationary": compute + cycles(traffic["output_stationary"]),
    }


def select_dataflow(cv: ConvLoopNest, plan: ConvBlockPlan,
                    cfg: Optional[MavecConfig] = None,
                    costs: Optional[Dict[str, float]] = None,
                    precision: str = "fp32") -> str:
    """Pick the cheaper dataflow; ties go to ``output_stationary`` (its
    single output write avoids the host-side partial-sum reduce).
    Depthwise nests have exactly one dataflow — the dedicated kernel with
    no depth-fold reduction."""
    if cv.depthwise:
        return "depthwise"
    costs = (costs if costs is not None
             else dataflow_costs(cv, plan, cfg, precision))
    if costs["output_stationary"] <= costs["weight_stationary"]:
        return "output_stationary"
    return "weight_stationary"


def plan_and_dataflow(cv: ConvLoopNest,
                      cfg: Optional[MavecConfig] = None,
                      precision: str = "fp32"
                      ) -> Tuple[ConvBlockPlan, str]:
    """Uncached one-shot planning (the ``impl="fold_auto"`` path)."""
    plan = plan_conv_blocks(cv)
    return plan, select_dataflow(cv, plan, cfg, precision=precision)


# --------------------------------------------------------------------------
# Measured autotuning (the analytical ranking above is the no-tuning default)
# --------------------------------------------------------------------------

def tuning_candidates(cv: ConvLoopNest,
                      base_plan: Optional[ConvBlockPlan] = None,
                      vmem_limit: int = VMEM_LIMIT_BYTES
                      ) -> List[Tuple[str, ConvBlockPlan, str]]:
    """The candidate set ``autotune_schedule`` races: the analytical plan
    plus nearby block-shape variants — every blocked axis of the fold
    geometry (P, C, and since PR 3 the NF filter-fold axis too) — crossed
    with both dataflows.

    Kept deliberately small (<= 12 timed runs per geometry, usually fewer
    after dedup): tuning is pay-once per ``ScheduleKey`` and persisted as
    JSON, but each timing is a real on-device run.

    Grouped geometries snap the varied blocks back to divisors of the
    per-group extents (``mapping.largest_divisor_le``) so every candidate
    honors the no-fold-straddles-a-group invariant; depthwise geometries
    vary the channel/P blocks only and race the single ``"depthwise"``
    dataflow.
    """
    from repro.core.mapping import largest_divisor_le
    base = (base_plan or plan_conv_blocks(cv, vmem_limit=vmem_limit)
            ).clamped(cv.nf, cv.c, cv.p)

    if cv.depthwise:
        def with_dw(c_b: int, p_b: int) -> ConvBlockPlan:
            c_b = max(1, min(c_b, -(-cv.c // 8) * 8 if cv.c >= 8 else cv.c))
            p_b = max(1, min(p_b, cv.p))
            grid = (1, math.ceil(cv.c / c_b), math.ceil(cv.p / p_b))
            return dataclasses.replace(
                base, nf_block=c_b, c_block=c_b, p_block=p_b, grid=grid,
                vmem_bytes=conv_working_set(cv, c_b, c_b, p_b))

        c_b, p_b = base.c_block, base.p_block
        plans: Dict[Tuple[int, int, int], Tuple[str, ConvBlockPlan]] = {}
        for label, plan in (
                ("base", base),
                ("p_half", with_dw(c_b, p_b // 2)),
                ("p_double", with_dw(c_b, p_b * 2)),
                ("c_half", with_dw(c_b // 2, p_b)),
                ("c_double", with_dw(c_b * 2, p_b)),
        ):
            plans.setdefault((plan.nf_block, plan.c_block, plan.p_block),
                             (label, plan))
        return [(label, plan, "depthwise") for label, plan in plans.values()]

    def with_blocks(nf_b: int, c_b: int, p_b: int) -> ConvBlockPlan:
        if cv.groups > 1:
            nf_b = largest_divisor_le(cv.nfg, max(nf_b, 1))
            c_b = largest_divisor_le(cv.cg, max(c_b, 1))
            grid = (cv.groups * (cv.nfg // nf_b), cv.cg // c_b,
                    math.ceil(cv.p / max(1, min(p_b, cv.p))))
        else:
            if cv.nf >= 8:                  # keep the MXU-lane alignment
                nf_b = -(-nf_b // 8) * 8
            nf_b = max(1, min(nf_b,
                              -(-cv.nf // 8) * 8 if cv.nf >= 8 else cv.nf))
            c_b = max(1, min(c_b, cv.c))
            grid = (math.ceil(cv.nf / nf_b), math.ceil(cv.c / c_b),
                    math.ceil(cv.p / max(1, min(p_b, cv.p))))
        p_b = max(1, min(p_b, cv.p))
        return dataclasses.replace(
            base, nf_block=nf_b, c_block=c_b, p_block=p_b, grid=grid,
            vmem_bytes=conv_working_set(cv, nf_b, c_b, p_b))

    nf_b, c_b, p_b = base.nf_block, base.c_block, base.p_block
    plans = {}
    for label, plan in (
            ("base", base),
            ("p_half", with_blocks(nf_b, c_b, p_b // 2)),
            ("p_double", with_blocks(nf_b, c_b, p_b * 2)),
            ("c_half", with_blocks(nf_b, c_b // 2, p_b)),
            ("nf_half", with_blocks(nf_b // 2, c_b, p_b)),
            ("nf_double", with_blocks(nf_b * 2, c_b, p_b)),
    ):
        plans.setdefault((plan.nf_block, plan.c_block, plan.p_block),
                         (label, plan))
    return [(label, plan, df) for label, plan in plans.values()
            for df in ("weight_stationary", "output_stationary")]


def measure_schedule_ms(cv: ConvLoopNest, plan: ConvBlockPlan, dataflow: str,
                        *, interpret: Optional[bool] = None,
                        reps: int = 3, warmup: int = 1,
                        epilogue: Optional[Epilogue] = None,
                        precision: str = "fp32") -> float:
    """Median-of-``reps`` wall time (ms) of one fold-kernel run on-device.

    Synthesizes the layer's tensors — including a shortcut tensor when the
    deployment epilogue fuses a residual add — and jits the kernel with
    the candidate plan/dataflow (and, when supplied, the ``epilogue``, so
    the timed kernel — including its pool-driven even-P-block
    normalization and the resident shortcut's VMEM footprint — is the one
    that will actually execute), runs ``warmup`` throwaway calls, then
    times ``reps`` calls with ``block_until_ready``.  With
    ``precision="int8"`` the operands are synthesized *quantized* and the
    epilogue is the requant form, so the race times the int8 stream it
    will deploy.
    """
    from repro.kernels.conv2d_ws import conv2d_folded
    if interpret is None:
        interpret = pallas_interpret_default()
    kx, kw, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(
        kx, (cv.n, cv.c, cv.padded_x, cv.padded_y), jnp.float32)
    w = jax.random.normal(kw, (cv.nf, cv.cg, cv.r, cv.s), jnp.float32)
    if precision == "int8":
        from repro.core.quant import (act_scale, quantize_act,
                                      quantize_weight, requant_affine,
                                      requant_epilogue)
        x = quantize_act(x, act_scale(x))
        w, w_scale = quantize_weight(w)
        has_epi = epilogue is not None
        scale, shift = requant_affine(
            w_scale, epilogue,
            jnp.zeros((cv.nf,), jnp.float32)
            if has_epi and epilogue.bias else None,
            jnp.ones((cv.nf,), jnp.float32)
            if has_epi and epilogue.scale else None,
            jnp.zeros((cv.nf,), jnp.float32)
            if has_epi and epilogue.scale else None)
        epilogue = requant_epilogue(epilogue)
        bias = None
    else:
        bias = (jnp.zeros((cv.nf,), jnp.float32)
                if epilogue is not None and epilogue.bias else None)
        scale = shift = None
        if epilogue is not None and epilogue.scale:
            scale = jnp.ones((cv.nf,), jnp.float32)
            shift = jnp.zeros((cv.nf,), jnp.float32)
    residual = (jax.random.normal(kr, (cv.n, cv.nf, cv.p, cv.q), jnp.float32)
                if epilogue is not None and epilogue.residual else None)
    fn = jax.jit(functools.partial(conv2d_folded, stride=cv.stride,
                                   plan=plan, dataflow=dataflow,
                                   interpret=interpret, epilogue=epilogue,
                                   groups=cv.groups))
    kw_args = dict(bias=bias, residual=residual, scale=scale, shift=shift)
    for _ in range(max(warmup, 1)):
        fn(x, w, **kw_args).block_until_ready()
    ts = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        fn(x, w, **kw_args).block_until_ready()
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]


def autotune_schedule(cv: ConvLoopNest, cfg: Optional[MavecConfig] = None,
                      *, vmem_limit: int = VMEM_LIMIT_BYTES,
                      interpret: Optional[bool] = None,
                      reps: int = 3, warmup: int = 1,
                      epilogue: Optional[Epilogue] = None,
                      timer: Optional[Callable[[ConvBlockPlan, str], float]]
                      = None,
                      precision: str = "fp32") -> ConvSchedule:
    """Race the candidate set on-device and return the measured winner.

    Candidates are ranked strictly by their measured median — a
    measured-slower candidate can never outrank a measured-faster one (the
    analytical cost model has no vote once timings exist; it remains the
    default when no tuning is requested).  ``epilogue`` is the deployment
    epilogue, threaded into the measurements so the timed kernels match
    the executed ones.  ``timer`` overrides the measurement (tests inject
    deterministic fakes).
    """
    key = ScheduleKey.from_loopnest(cv, precision)
    if timer is None:
        timer = lambda plan, df: measure_schedule_ms(  # noqa: E731
            cv, plan, df, interpret=interpret, reps=reps, warmup=warmup,
            epilogue=epilogue, precision=precision)
    raced = []
    failed = []
    for label, plan, df in tuning_candidates(cv, vmem_limit=vmem_limit):
        try:
            raced.append((float(timer(plan, df)), f"{label}/{df}", plan, df))
        except Exception as e:             # candidate failure isolation: an
            failed.append((f"{label}/{df}", e))  # uncompilable variant must
            continue                             # not abort the whole race
    if not raced:
        raise RuntimeError(
            f"autotune: every candidate failed for {cv} — "
            + "; ".join(f"{lbl}: {e}" for lbl, e in failed))
    raced.sort(key=lambda t: t[0])         # measured-fastest first, always
    best_ms, _, best_plan, best_df = raced[0]
    costs = dataflow_costs(cv, best_plan, cfg, precision)
    return ConvSchedule(key=key, nest=cv, plan=best_plan, dataflow=best_df,
                        costs=tuple(sorted(costs.items())),
                        source="measured", measured_ms=best_ms,
                        timings=tuple((lbl, ms) for ms, lbl, _, _ in raced))


# --------------------------------------------------------------------------
# Interpret / execution policy
# --------------------------------------------------------------------------

def pallas_interpret_default() -> bool:
    """Pallas kernels lower for real only on TPU; elsewhere interpret."""
    return jax.default_backend() != "tpu"


def resolve_execution(policy: str = "auto") -> Tuple[str, bool]:
    """Resolve an execution policy to ``(mode, interpret)``.

      "auto"       — real Pallas lowering on TPU; on other backends fall
                     back cleanly to the fused-XLA reference conv (the
                     schedules are still built — planning and fold-reuse
                     accounting are backend-independent).
      "pallas"     — force the fold kernels (interpreted off-TPU).
      "reference"  — force the reference conv everywhere.
    """
    if policy == "auto":
        if jax.default_backend() == "tpu":
            return "pallas", False
        return "reference", False
    if policy == "pallas":
        return "pallas", pallas_interpret_default()
    if policy == "reference":
        return "reference", False
    raise ValueError(f"unknown execution policy {policy!r} "
                     "(want auto|pallas|reference)")


# --------------------------------------------------------------------------
# The schedule registry
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    replans: int = 0

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "replans": self.replans, "hit_rate": round(self.hit_rate, 4)}


class ScheduleCache:
    """Registry of fold schedules keyed by filter-fold geometry.

    ``schedule_for`` computes each geometry's ``ConvBlockPlan`` and
    dataflow once and reuses it for every later layer with the same key —
    the paper's fold reuse.  A reused plan is clamped to the actual dims by
    the kernel, so reuse across shrinking spatial extents is exact; if a
    *larger* spatial extent arrives later, the entry is re-planned in place
    (counted in ``stats.replans``) so the VMEM working-set bound stays
    honest.
    """

    def __init__(self, cfg: Optional[MavecConfig] = None,
                 vmem_limit: int = VMEM_LIMIT_BYTES):
        self.cfg = cfg or MavecConfig()
        self.vmem_limit = vmem_limit
        self.stats = CacheStats()
        self._entries: Dict[ScheduleKey, ConvSchedule] = {}
        # key: (schedule key, dataflow, interpret, epilogue)
        self._kernels: Dict[Tuple[ScheduleKey, str, bool,
                                  Optional[Epilogue]], Callable] = {}

    # -- registry ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    @property
    def distinct(self) -> int:
        return len(self._entries)

    def schedules(self) -> List[ConvSchedule]:
        return list(self._entries.values())

    def _build(self, cv: ConvLoopNest, key: ScheduleKey) -> ConvSchedule:
        plan = plan_conv_blocks(cv, vmem_limit=self.vmem_limit)
        costs = dataflow_costs(cv, plan, self.cfg, key.precision)
        dataflow = select_dataflow(cv, plan, self.cfg, costs=costs)
        return ConvSchedule(key=key, nest=cv, plan=plan, dataflow=dataflow,
                            costs=tuple(sorted(costs.items())))

    def schedule_for(self, cv: ConvLoopNest,
                     precision: str = "fp32") -> ConvSchedule:
        key = ScheduleKey.from_loopnest(cv, precision)
        hit = self._entries.get(key)
        if hit is not None:
            if (cv.padded_x > hit.nest.padded_x
                    or cv.padded_y > hit.nest.padded_y):
                # larger image than planned for: re-solve so the working
                # set still fits VMEM; the key (and cache slot) is stable.
                self.stats.replans += 1
                self._entries[key] = self._build(cv, key)
                self._kernels = {k: v for k, v in self._kernels.items()
                                 if k[0] != key}
                return self._entries[key]
            self.stats.hits += 1
            return hit
        self.stats.misses += 1
        sched = self._build(cv, key)
        self._entries[key] = sched
        return sched

    # -- measured autotuning ----------------------------------------------
    def autotune_for(self, cv: ConvLoopNest, *, reps: int = 3,
                     warmup: int = 1, interpret: Optional[bool] = None,
                     epilogue: Optional[Epilogue] = None,
                     timer: Optional[Callable[[ConvBlockPlan, str], float]]
                     = None, precision: str = "fp32") -> ConvSchedule:
        """Measured ``schedule_for``: the first layer with a given key
        races ``tuning_candidates`` on-device; every later layer (and every
        later session that loads the JSON tuning cache) reuses the winner —
        tuning is pay-once per ``ScheduleKey``.

        Scope of the measured guarantee: candidates are timed with the
        *first-seen* layer's ``epilogue``.  A later same-key layer with a
        different fused epilogue (e.g. a pre-pool trunk layer) reuses the
        winner's block geometry without re-measuring — the epilogue only
        changes the flush, not the fold geometry the race ranks."""
        key = ScheduleKey.from_loopnest(cv, precision)
        hit = self._entries.get(key)
        if (hit is not None and hit.tuned
                and cv.padded_x <= hit.nest.padded_x
                and cv.padded_y <= hit.nest.padded_y):
            self.stats.hits += 1
            return hit
        if hit is None:
            self.stats.misses += 1
        else:                       # model-sourced or spatially outgrown
            self.stats.replans += 1
        sched = autotune_schedule(cv, self.cfg, vmem_limit=self.vmem_limit,
                                  interpret=interpret, reps=reps,
                                  warmup=warmup, epilogue=epilogue,
                                  timer=timer, precision=precision)
        self._entries[key] = sched
        self._kernels = {k: v for k, v in self._kernels.items()
                         if k[0] != key}
        return sched

    # -- JSON persistence of tuning results --------------------------------
    def save_tuning(self, path: str) -> int:
        """Write every measured/loaded schedule to ``path`` (JSON).  Model-
        sourced entries are skipped — only real timings are persisted."""
        entries = []
        for key, s in sorted(self._entries.items(), key=lambda kv: str(kv[0])):
            if not s.tuned:
                continue
            entries.append({
                "key": dataclasses.asdict(key),
                "nest": dataclasses.asdict(s.nest),
                "plan": {"nf_block": s.plan.nf_block,
                         "c_block": s.plan.c_block,
                         "p_block": s.plan.p_block,
                         "grid": list(s.plan.grid),
                         "vmem_bytes": s.plan.vmem_bytes,
                         "groups": s.plan.groups},
                "dataflow": s.dataflow,
                "measured_ms": s.measured_ms,
                "timings": [[lbl, ms] for lbl, ms in s.timings],
            })
        payload = {"version": 1, "backend": jax.default_backend(),
                   "entries": entries}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        return len(entries)

    @staticmethod
    def _dataclass_kwargs(cls, d: dict) -> dict:
        """Tuning-JSON schema tolerance: drop fields this build doesn't
        know (a newer writer), and let dataclass defaults fill fields the
        file doesn't have (an older writer — e.g. a pre-groups cache
        defaults to ``groups=1`` instead of rotting)."""
        known = {f.name for f in dataclasses.fields(cls)}
        return {k: v for k, v in d.items() if k in known}

    def load_tuning(self, path: str) -> int:
        """Install previously-measured winners from ``path``.  Loaded
        entries hit in both ``schedule_for`` and ``autotune_for`` (no
        re-measurement), preserving the measured ranking exactly.

        Tuning JSON is schema-tolerant in both directions: entries written
        before the ``groups`` axis existed load with ``groups=1`` (the
        dense geometry they were measured on), a pre-int8 cache loads
        with ``precision="fp32"`` (all it could have measured), and
        unknown extra fields from a newer writer are ignored rather than
        treated as rot.

        Timings only transfer within a backend: a cache recorded on a
        different backend is ignored (returns 0, with a warning) so stale
        CPU-interpret rankings never reach a TPU deployment — the caller
        simply re-measures and overwrites.

        A missing, unreadable, or corrupt cache file is never fatal: the
        loader warns and returns 0 (or however many entries parsed before
        the corruption) and the engine falls back to the heuristic
        schedules / fresh measurements — a deployment must not fail to
        start because a tuning artifact rotted."""
        import warnings
        try:
            with open(path) as f:
                payload = json.load(f)
            entries = payload["entries"]
            if not isinstance(entries, list):
                raise TypeError(f"entries is {type(entries).__name__}, "
                                "not a list")
            recorded = payload.get("backend")
        except (OSError, ValueError, KeyError, TypeError) as e:
            warnings.warn(f"tuning cache {path!r} is missing or corrupt "
                          f"({type(e).__name__}: {e}); falling back to "
                          "heuristic schedules")
            return 0
        current = jax.default_backend()
        if recorded is not None and recorded != current:
            warnings.warn(f"tuning cache {path!r} was measured on backend "
                          f"{recorded!r} but this session runs {current!r}; "
                          "ignoring it (schedules will be re-measured)")
            return 0
        n = 0
        for e in entries:
            try:
                key = ScheduleKey(**self._dataclass_kwargs(ScheduleKey,
                                                           e["key"]))
                nest = ConvLoopNest(**self._dataclass_kwargs(ConvLoopNest,
                                                             e["nest"]))
                pd = e["plan"]
                plan = ConvBlockPlan(nf_block=int(pd["nf_block"]),
                                     c_block=int(pd["c_block"]),
                                     p_block=int(pd["p_block"]),
                                     grid=tuple(int(g) for g in pd["grid"]),
                                     vmem_bytes=int(pd["vmem_bytes"]),
                                     groups=int(pd.get("groups", 1)))
                dataflow = e["dataflow"]
                measured_ms = e.get("measured_ms")
                timings = tuple((lbl, float(ms))
                                for lbl, ms in e.get("timings", ()))
            except (KeyError, TypeError, ValueError) as err:
                warnings.warn(f"tuning cache {path!r}: skipping corrupt "
                              f"entry ({type(err).__name__}: {err})")
                continue
            costs = dataflow_costs(nest, plan, self.cfg, key.precision)
            self._entries[key] = ConvSchedule(
                key=key, nest=nest, plan=plan, dataflow=dataflow,
                costs=tuple(sorted(costs.items())), source="loaded",
                measured_ms=measured_ms, timings=timings)
            self._kernels = {k: v for k, v in self._kernels.items()
                             if k[0] != key}
            n += 1
        return n

    # -- kernel binding ----------------------------------------------------
    def kernel_for(self, sched: ConvSchedule,
                   interpret: Optional[bool] = None,
                   epilogue: Optional[Epilogue] = None) -> Callable:
        """The partially-applied fold kernel for a schedule: plan, dataflow,
        interpret mode and fused epilogue baked in; memoized per (key,
        dataflow, interpret, epilogue) so repeated layers share one
        closure.  With ``epilogue.bias`` the caller supplies the vector at
        call time (``fn(xp, w, bias=b)``).  ``compile_network``'s fused
        path routes through ``kernels.ops.conv2d_fused`` instead so the
        custom VJP keeps fused layers trainable; this binding is the raw
        inference-kernel surface."""
        from repro.kernels.conv2d_ws import conv2d_folded
        if interpret is None:
            interpret = pallas_interpret_default()
        kk = (sched.key, sched.dataflow, interpret, epilogue)
        fn = self._kernels.get(kk)
        if fn is None:
            fn = functools.partial(conv2d_folded, plan=sched.plan,
                                   dataflow=sched.dataflow,
                                   interpret=interpret, epilogue=epilogue,
                                   groups=sched.key.groups)
            self._kernels[kk] = fn
        return fn


# --------------------------------------------------------------------------
# Whole-network compilation: StreamGraph lowering
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledNetwork:
    """A whole-network static fold schedule plus its jitted forward.

    ``layer_schedules`` and ``build_stats`` are snapshots taken at compile
    time: they describe exactly what this network executes even if the
    (possibly shared) cache is mutated or replanned afterwards.
    """
    apply: Callable[[Dict[str, Any], jnp.ndarray], jnp.ndarray]
    layer_schedules: Tuple[Tuple[str, ConvSchedule], ...]  # per conv node
    build_stats: CacheStats        # cache activity during this compile only
    cache: ScheduleCache
    mode: str                # "pallas" | "reference"
    interpret: bool
    fused: bool = False      # epilogues flushed in-kernel (pallas mode)
    autotuned: bool = False  # schedules are measured winners
    graph: Optional[StreamGraph] = None   # the graph actually lowered
    precision: str = "fp32"  # streamed conv dtype ("fp32" | "int8")
    quant: Optional[Any] = None  # the QuantRecipe the int8 lowering baked in
    # per conv, the dataflow its fold kernel launches with (after the
    # kernel's VMEM fallback) and the output rows each of its tap dots
    # covers (``FoldKernelSpec.rows_per_dot``); empty where no fold
    # kernel runs
    fold_dataflows: Tuple[str, ...] = ()
    fold_rows_per_dot: Tuple[int, ...] = ()

    def __call__(self, params: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
        return self.apply(params, x)

    @property
    def layer_keys(self) -> Tuple[Tuple[str, ScheduleKey], ...]:
        return tuple((name, s.key) for name, s in self.layer_schedules)

    @property
    def distinct_schedules(self) -> int:
        return len({s.key for _, s in self.layer_schedules})

    def fold_reuse(self) -> dict:
        """The paper's fold-reuse metric for this network's build."""
        d = self.build_stats.as_dict()
        d.update(conv_layers=len(self.layer_schedules),
                 distinct_schedules=self.distinct_schedules)
        return d

    def describe(self) -> str:
        lines = [f"CompiledNetwork(mode={self.mode}, "
                 f"interpret={self.interpret}, fused={self.fused}, "
                 f"autotuned={self.autotuned}, "
                 f"precision={self.precision}, "
                 f"layers={len(self.layer_schedules)}, "
                 f"schedules={self.distinct_schedules})"]
        for name, sched in self.layer_schedules:
            ms = (f" {sched.measured_ms:.2f}ms"
                  if sched.measured_ms is not None else "")
            lines.append(f"  {name:<10} {str(sched.key):<24} "
                         f"{sched.dataflow:<18} grid={sched.plan.grid}"
                         f" [{sched.source}]{ms}")
        return "\n".join(lines)


# --------------------------------------------------------------------------
# static verification hooks (repro.analysis), memoized per geometry
# --------------------------------------------------------------------------

# schedules already proven this process: keyed on everything the checks
# read, so the verify=True default costs one lookup per layer after the
# first compile of a geometry.  Imports are lazy to keep the engine's
# import graph acyclic.
_VERIFIED_SCHEDULES: Dict[Tuple, bool] = {}


def _verify_graph(original, fused_graph, fused: bool) -> None:
    """Structural lint (+ fusion-legality diff when the fusion pass ran).
    Shape errors stay the walk's own ``GraphError``s — the lint here is
    params-free so it can never preempt them."""
    from repro.analysis.graph_check import check_fusion, lint_graph
    from repro.analysis.report import FoldLintError
    rep = lint_graph(fused_graph)
    errors = rep.errors
    if fused:
        errors = errors + check_fusion(original, fused_graph).errors
    if errors:
        raise FoldLintError(errors)


def _launch_spec(cv: ConvLoopNest, sched: "ConvSchedule", epi,
                 groups: int):
    """The fold kernel launch geometry (``FoldKernelSpec``) one conv
    layer's schedule binds."""
    from repro.kernels.conv2d_ws import fold_kernel_spec
    return fold_kernel_spec(
        (cv.n, cv.c, cv.padded_x, cv.padded_y),
        (cv.nf, cv.c // groups, cv.r, cv.s), stride=cv.stride,
        plan=sched.plan.clamped(cv.nf, cv.c, cv.p),
        dataflow=sched.dataflow, epilogue=epi, groups=groups)


def _verify_schedule(name: str, cv: ConvLoopNest, sched: "ConvSchedule",
                     epi, groups: int) -> None:
    """Prove one conv layer's schedule before its kernel is bound: the
    clamped block plan's invariants (including, for int8 schedules, the
    int32-accumulator overflow bound), then the full launch geometry's
    index-map coverage/race analysis (``FoldKernelSpec``).  ``epi`` is
    the epilogue the kernel actually flushes — the requant form for int8
    schedules."""
    plan = sched.plan.clamped(cv.nf, cv.c, cv.p)
    key = (sched.key, sched.dataflow, plan, epi, cv.n,
           cv.padded_x, cv.padded_y)
    if key in _VERIFIED_SCHEDULES:
        return
    from repro.analysis.index_check import check_kernel_spec
    from repro.analysis.plan_check import check_plan
    from repro.analysis.report import FoldLintError
    rep = check_plan(cv, plan, where=name, precision=sched.key.precision,
                     dataflow=sched.dataflow, epilogue=epi)
    if rep.ok:
        rep.extend(check_kernel_spec(_launch_spec(cv, sched, epi, groups),
                                     where=name))
    if not rep.ok:
        raise FoldLintError(rep.errors)
    _VERIFIED_SCHEDULES[key] = True


def _dense(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The classifier's dense layer, (N, K) @ (K, D) + b, contracted in
    fp32 on every backend (XLA's TPU default would round the operands to
    bf16).  The rows are zero-padded to a multiple of 8 for the dot: XLA
    compiles a one-row dot into a differently rounded program, so a
    one-image bucket — or a mesh's one-row batch slice per device — would
    otherwise round an image's logits differently from a wider batch."""
    n = x.shape[0]
    if n % 8:
        x = jnp.pad(x, ((0, -n % 8), (0, 0)))
    y = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    return y[:n] + b


def _conv_step(x, w, b, scale, shift, res, *, sched: "ConvSchedule",
               epi: Optional[Epilogue], stride: int, pad: int, groups: int,
               mode: str, interpret: bool, precision: str, x_scale):
    """One conv node of a compiled forward: ``b``/``scale``/``shift``/
    ``res`` are None unless the node's epilogue uses them."""
    from repro.kernels.ops import conv2d, conv2d_fused, conv2d_int8
    if precision == "int8":
        # quantized stream: weights quantize per-channel at trace time,
        # activations with the calibrated static scale; bias/BN/dequant
        # fold into one flush affine
        return conv2d_int8(
            x, w, b, x_scale=x_scale, stride=stride, pad=pad, epilogue=epi,
            impl="direct" if mode == "reference" else sched.impl(),
            plan=sched.plan, interpret=interpret, residual=res,
            scale=scale, shift=shift, groups=groups)
    if epi is not None:
        # an epilogue on a conv node is graph semantics and is honored in
        # every mode; in pallas mode it flushes in-kernel, in reference
        # mode (a caller-supplied pre-fused graph — this compile never
        # fuses there) it lowers through the XLA conv + reference epilogue
        if mode == "reference":
            return conv2d_fused(x, w, b, stride=stride, pad=pad,
                                epilogue=epi, impl="direct", residual=res,
                                scale=scale, shift=shift, groups=groups)
        return conv2d_fused(x, w, b, stride=stride, pad=pad, epilogue=epi,
                            impl=sched.impl(), plan=sched.plan,
                            interpret=interpret, residual=res, scale=scale,
                            shift=shift, groups=groups)
    if mode == "reference":
        return conv2d(x, w, stride=stride, pad=pad, impl="direct",
                      groups=groups)
    return conv2d(x, w, stride=stride, pad=pad, impl=sched.impl(),
                  plan=sched.plan, interpret=interpret, groups=groups)


def compile_network(params: Dict[str, Any],
                    graph,
                    input_shape: Tuple[int, int, int, int],
                    *,
                    policy: str = "auto",
                    cache: Optional[ScheduleCache] = None,
                    head: Optional[Callable] = None,
                    jit: bool = True,
                    fuse_epilogues: bool = True,
                    autotune: bool = False,
                    tuning_path: Optional[str] = None,
                    autotune_reps: int = 3,
                    autotune_timer: Optional[Callable] = None,
                    verify: bool = True,
                    tracer=None,
                    precision: str = "fp32",
                    quant=None,
                    mesh=None,
                    mesh_plan=None
                    ) -> CompiledNetwork:
    """Lower a streaming graph into a static fold schedule + jitted forward.

    ``graph`` is a ``core/graph.py:StreamGraph`` (any registered model
    exports one) or, for backward compatibility, a legacy conv-spec
    sequence converted by ``StreamGraph.from_conv_spec`` — note the
    legacy spec lowers the conv *trunk* only: classifier heads are graph
    nodes (see the model ``to_graph`` exporters) or an explicit ``head``
    callable, and the old implicit fc-head default is gone.  Conv/dense
    weights live at ``params[node.param]["w"]`` (OIHW / (in, out)) with
    biases at ``["b"]``.  ``input_shape`` is NCHW.

    All schedules are built eagerly here through the shared
    ``ScheduleCache`` — the returned forward never plans; its trace just
    binds the cached kernels.  ``head``, when given, post-processes the
    graph output (models usually express their classifier head as
    flatten/dense graph nodes instead).

    ``fuse_epilogues`` (pallas mode): the graph is first run through the
    fusion pass (``core/graph.py:fuse_graph``), so each conv's
    bias / residual-add / ReLU / 2x2-max-pool chain flushes inside the
    conv's ``pallas_call`` (``core/epilogue.py``) — one kernel launch per
    conv block, the pre-activation tensor never round-trips through HBM,
    and a residual block's shortcut add costs no extra kernel.  Reference
    mode keeps the separate XLA ops (XLA fuses them itself).  A fused
    pool on an output too small to pool in-kernel (P or Q < 2) is demoted
    back to a standalone op at lowering time.  Epilogues already present
    on the *incoming* graph's conv nodes (a caller-supplied pre-fused
    graph) are graph semantics — honored in every mode, lowered through
    the XLA conv + reference epilogue chain when the fold kernels don't
    run; ``fuse_epilogues`` only controls whether *this* compile runs the
    fusion pass.

    ``autotune=True`` replaces the analytical dataflow ranking with
    measured timings (``autotune_for``): pay-once per ``ScheduleKey``, and
    with ``tuning_path`` the results round-trip through JSON so later
    sessions skip the measurements entirely.

    ``verify=True`` (the default) statically verifies the lowering with
    ``repro.analysis`` before it runs: the graph is linted (and, when the
    fusion pass ran, diffed against an independent re-derivation of the
    fusion rules), and every pallas-mode conv schedule's block plan and
    kernel index maps are proven in-bounds / race-free / exactly-covering.
    Error-severity findings raise ``FoldLintError``.  Verification is
    memoized per schedule geometry (``_VERIFIED_SCHEDULES``), so the
    steady-state cost of the default is one dict lookup per layer.

    ``precision="int8"`` lowers every conv through the quantized fold
    stream (``core/quant.py``): int8 weight/activation blocks, int32
    in-kernel accumulation, dequant folded into the epilogue scale/shift
    slot.  ``quant`` supplies the calibrated ``QuantRecipe``; when None,
    a deterministic standard-normal calibration batch
    (``default_calib_batch``) runs the fp32 reference forward once to
    record per-conv activation scales.  Schedules live under int8
    ``ScheduleKey``s (the traffic model prices the 1-byte streams, which
    can flip the WS/OS choice), and verification proves the int32
    accumulator bound on top of the usual invariants.

    ``mesh`` (with ``mesh_plan``, a ``core/mapping.py:serving_conv_plan``
    naming its batch and filter axes) runs every fold kernel under
    ``shard_map``: GSPMD cannot partition a Mosaic kernel, so each device
    runs it on its own batch (and, where the weights split, filter)
    shard — ``distributed/sharding.py:fold_conv_shards``.  Schedules are
    planned and verified for that per-device nest.  Reference mode needs
    no such wrapper.
    """
    from repro.core.quant import check_precision
    check_precision(precision)
    # explicit None-check: an empty ScheduleCache is falsy (len 0) but
    # must still be used, so its stats/schedules reach the caller
    cache = cache if cache is not None else ScheduleCache()
    # ``tracer`` is duck-typed (obs/trace.py:Tracer) so the core layer
    # never imports the observability layer; spans are recorded with
    # explicit timestamps (add_span), which leaves no dangling state if
    # a GraphError aborts the compile mid-walk.  tid 3 is the compile
    # track (obs.trace.TID_COMPILE).
    _tc0 = float(tracer.clock()) if tracer is not None else 0.0
    mode, interpret = resolve_execution(policy)
    stats_before = dataclasses.replace(cache.stats)
    if autotune and tuning_path and os.path.exists(tuning_path):
        cache.load_tuning(tuning_path)
    fused = fuse_epilogues and mode == "pallas"
    base_graph = as_graph(graph)
    g = fuse_graph(base_graph) if fused else base_graph
    if verify:
        _verify_graph(base_graph, g, fused)
    if precision == "int8" and quant is None:
        # self-contained calibration: the fp32 reference forward over a
        # small deterministic batch records each conv's activation scale
        # (fusion preserves conv node names, so the recipe keys match)
        from repro.core.quant import default_calib_batch, quantize_graph
        quant = quantize_graph(base_graph, params,
                               default_calib_batch(input_shape))

    # -- shape-inferring walk: one step per node, schedules built eagerly --
    shapes: Dict[str, Tuple[int, ...]] = {g.input: tuple(input_shape)}
    layer_schedules: List[Tuple[str, ConvSchedule]] = []
    fold_dataflows: List[str] = []
    fold_rows_per_dot: List[int] = []
    plan_steps: List[Tuple] = []   # (op, out, in_names, static payload)

    def _need4d(nd, shape):
        if len(shape) != 4:
            raise GraphError(f"{nd.name}: {nd.op} expects an NCHW tensor, "
                             f"got shape {shape}")

    for nd in g.nodes:
        src = nd.inputs[0]
        s_in = shapes[src]
        if nd.op == "conv":
            _need4d(nd, s_in)
            n_, chan, h, w_ = s_in
            wshape = params[nd.param]["w"].shape       # (NF, C/groups, R, S)
            nf, cin, r, s = (int(d) for d in wshape)
            groups = chan if nd.groups == DEPTHWISE else nd.groups
            if cin * groups != chan:
                raise GraphError(
                    f"{nd.name}: weights expect {cin}x{groups} input "
                    f"channels, trunk carries {chan}")
            if nf % groups:
                raise GraphError(
                    f"{nd.name}: groups={groups} must divide the filter "
                    f"count {nf}")
            shards = None
            if mesh is not None and mode == "pallas":
                from repro.distributed.sharding import fold_conv_shards
                if mesh_plan is None:
                    mesh_plan = serving_conv_plan(n_, nf)
                shards = fold_conv_shards(mesh, mesh_plan, n=n_, nf=nf,
                                          c=chan, groups=groups)
                # the nest each device's kernel runs
                cv = ConvLoopNest(n=shards.n, nf=shards.nf, c=shards.c,
                                  r=r, s=s, x=h, y=w_, stride=nd.stride,
                                  pad=nd.pad, groups=shards.groups)
                groups = shards.groups
            else:
                cv = ConvLoopNest(n=n_, nf=nf, c=chan, r=r, s=s, x=h, y=w_,
                                  stride=nd.stride, pad=nd.pad,
                                  groups=groups)
            epi, demoted_pool = nd.epilogue, False
            if epi is not None and epi.pool and (cv.p < 2 or cv.q < 2):
                # output too small to pool in-kernel: demote to a
                # standalone op after the conv (same numerics)
                epi = dataclasses.replace(epi, pool=None)
                demoted_pool = True
            if epi is not None and epi.residual:
                if nd.residual is None:
                    raise GraphError(
                        f"{nd.name}: Epilogue(residual=True) needs the "
                        "node's residual skip-edge input set")
                want = (n_, nf, cv.p, cv.q)
                got = shapes[nd.residual]
                if tuple(got) != want:
                    raise GraphError(
                        f"{nd.name}: fused shortcut {nd.residual!r} has "
                        f"shape {got}, conv output is {want}")
            _tp0 = float(tracer.clock()) if tracer is not None else 0.0
            if autotune:
                # measurements always run the fold kernels under the
                # backend's own interpret policy (reference mode's
                # interpret=False would ask for real Pallas lowering
                # off-TPU), with the deployment epilogue baked in so the
                # timed kernel is the executed one
                sched = cache.autotune_for(
                    cv, reps=autotune_reps,
                    interpret=interpret if mode == "pallas" else None,
                    epilogue=epi, timer=autotune_timer,
                    precision=precision)
            else:
                sched = cache.schedule_for(cv, precision=precision)
            if tracer is not None:
                tracer.add_span(f"plan:{nd.name}", "compile", 3, _tp0,
                                float(tracer.clock()) - _tp0,
                                schedule=str(sched.key),
                                dataflow=sched.dataflow,
                                source=sched.source)
            x_scale = None
            if precision == "int8":
                x_scale = quant.scale_for(nd.name)
            if mode == "pallas":
                # the epilogue the kernel actually flushes — for int8 the
                # requant affine always occupies the scale slot
                kernel_epi = epi
                if precision == "int8":
                    from repro.core.quant import requant_epilogue
                    kernel_epi = requant_epilogue(epi)
                if verify:
                    _verify_schedule(nd.name, cv, sched, kernel_epi, groups)
                launch = _launch_spec(cv, sched, kernel_epi, groups)
                fold_dataflows.append(launch.dataflow)
                fold_rows_per_dot.append(launch.rows_per_dot)
            layer_schedules.append((nd.name, sched))
            po, qo = epilogue_out_hw(nd.epilogue, cv.p, cv.q)
            shapes[nd.name] = (n_, nf, po, qo)
            plan_steps.append(("conv", nd.name, nd.all_inputs(),
                               (sched, epi, nd.stride, nd.pad, nd.param,
                                demoted_pool, groups, nd.bn_param,
                                x_scale, shards)))
        elif nd.op == "bias":
            _need4d(nd, s_in)
            shapes[nd.name] = s_in
            plan_steps.append(("bias", nd.name, nd.inputs, nd.param))
        elif nd.op == "batchnorm":
            _need4d(nd, s_in)
            shapes[nd.name] = s_in
            plan_steps.append(("batchnorm", nd.name, nd.inputs, nd.param))
        elif nd.op == "relu":
            shapes[nd.name] = s_in
            plan_steps.append(("relu", nd.name, nd.inputs, None))
        elif nd.op == "relu6":
            shapes[nd.name] = s_in
            plan_steps.append(("relu6", nd.name, nd.inputs, None))
        elif nd.op == "global_avgpool":
            _need4d(nd, s_in)
            shapes[nd.name] = (s_in[0], s_in[1], 1, 1)
            plan_steps.append(("global_avgpool", nd.name, nd.inputs, None))
        elif nd.op == "maxpool2":
            _need4d(nd, s_in)
            n_, chan, h, w_ = s_in
            shapes[nd.name] = (n_, chan, h // 2, w_ // 2)
            plan_steps.append(("maxpool2", nd.name, nd.inputs, None))
        elif nd.op == "residual_add":
            a, b = (shapes[i] for i in nd.inputs)
            if tuple(a) != tuple(b):
                raise GraphError(f"{nd.name}: residual_add operands differ "
                                 f"in shape: {a} vs {b}")
            shapes[nd.name] = a
            plan_steps.append(("residual_add", nd.name, nd.inputs, None))
        elif nd.op == "flatten":
            shapes[nd.name] = (s_in[0], int(math.prod(s_in[1:])))
            plan_steps.append(("flatten", nd.name, nd.inputs, None))
        elif nd.op == "dense":
            din, dout = (int(d) for d in params[nd.param]["w"].shape)
            if len(s_in) != 2 or s_in[1] != din:
                raise GraphError(f"{nd.name}: dense expects (N, {din}), "
                                 f"got {s_in}")
            shapes[nd.name] = (s_in[0], dout)
            plan_steps.append(("dense", nd.name, nd.inputs, nd.param))
        else:  # pragma: no cover — construction validates ops
            raise GraphError(f"{nd.name}: cannot lower op {nd.op!r}")

    steps = tuple(plan_steps)
    out_name = g.output

    def forward(p: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
        # Schedules are baked in: tracing binds the cached kernels and
        # never re-plans (no cache lookups on the hot path).
        env: Dict[str, jnp.ndarray] = {g.input: x}
        for op, out, ins, info in steps:
            if op == "conv":
                (sched, epi, stride, pad, pname, demoted_pool, groups,
                 bn_param, x_scale, shards) = info
                w = p[pname]["w"]
                b = p[pname]["b"] if epi is not None and epi.bias else None
                scale = shift = None
                if epi is not None and epi.scale:
                    # fold the BN statistics to the flush-time affine at
                    # trace time (compile-time constants per call)
                    scale, shift = bn_scale_shift(p[bn_param])
                res = (env[ins[1]] if epi is not None and epi.residual
                       else None)
                conv = functools.partial(
                    _conv_step, sched=sched, epi=epi, stride=stride,
                    pad=pad, groups=groups, mode=mode, interpret=interpret,
                    precision=precision, x_scale=x_scale)
                if shards is not None:
                    conv = shards.wrap(conv, mesh)
                y = conv(env[ins[0]], w, b, scale, shift, res)
                env[out] = maxpool2x2(y) if demoted_pool else y
            elif op == "bias":
                env[out] = (env[ins[0]]
                            + p[info]["b"][None, :, None, None])
            elif op == "batchnorm":
                scale, shift = bn_scale_shift(p[info])
                env[out] = (env[ins[0]] * scale[None, :, None, None]
                            + shift[None, :, None, None])
            elif op == "relu":
                env[out] = jax.nn.relu(env[ins[0]])
            elif op == "relu6":
                env[out] = jnp.clip(env[ins[0]], 0.0, 6.0)
            elif op == "global_avgpool":
                env[out] = env[ins[0]].mean(axis=(2, 3), keepdims=True)
            elif op == "maxpool2":
                env[out] = maxpool2x2(env[ins[0]])
            elif op == "residual_add":
                env[out] = env[ins[0]] + env[ins[1]]
            elif op == "flatten":
                v = env[ins[0]]
                env[out] = v.reshape(v.shape[0], -1)
            else:                                 # dense
                env[out] = _dense(env[ins[0]], p[info]["w"], p[info]["b"])
        y = env[out_name]
        return head(p, y) if head is not None else y

    if autotune and tuning_path:
        cache.save_tuning(tuning_path)
    build_stats = CacheStats(
        hits=cache.stats.hits - stats_before.hits,
        misses=cache.stats.misses - stats_before.misses,
        replans=cache.stats.replans - stats_before.replans)
    apply = jax.jit(forward) if jit else forward
    if tracer is not None:
        tracer.add_span("compile_network", "compile", 3, _tc0,
                        float(tracer.clock()) - _tc0, mode=mode,
                        batch=int(input_shape[0]),
                        conv_layers=len(layer_schedules),
                        distinct_schedules=len(
                            {s.key for _, s in layer_schedules}))
    return CompiledNetwork(apply=apply,
                           layer_schedules=tuple(layer_schedules),
                           build_stats=build_stats, cache=cache,
                           mode=mode, interpret=interpret,
                           fused=fused, autotuned=autotune, graph=g,
                           precision=precision, quant=quant,
                           fold_dataflows=tuple(fold_dataflows),
                           fold_rows_per_dot=tuple(fold_rows_per_dot))


# --------------------------------------------------------------------------
# Per-bucket compiled-forward cache (the serving engine's compile surface)
# --------------------------------------------------------------------------

class BucketCompiler:
    """Memoized ``compile_network`` per batch width, one shared
    ``ScheduleCache``.

    ``graph`` is any ``StreamGraph`` (or legacy conv-spec sequence) —
    the compiler is model-agnostic.  Continuous-batching serving pads
    request batches to a small set of *bucket* widths so each width is
    one stable jitted forward.  Because ``ScheduleKey`` deliberately
    excludes the batch axis (the batch only changes how many image folds
    stream through a schedule), the first bucket's compile populates
    every filter-fold schedule — measuring them when ``autotune`` is set —
    and every later bucket compiles with 100% schedule-cache hits:
    planning and tuning are pay-once across buckets, only the XLA trace
    is per-bucket.  With ``tuning_path`` the measured winners round-trip
    through one JSON shared by all buckets (and by later sessions).

    ``mesh``/``mesh_plan`` run the fold kernels per shard
    (``compile_network``).

    ``precision="int8"``: one ``QuantRecipe`` is calibrated eagerly here
    (or supplied via ``quant``) and shared by every bucket, so all bucket
    widths bake in bitwise-identical scales — a request's logits cannot
    depend on which bucket its batch padded to.
    """

    def __init__(self, params: Dict[str, Any], graph,
                 img: int, *, chan: int = 3, policy: str = "auto",
                 cache: Optional[ScheduleCache] = None,
                 head: Optional[Callable] = None, jit: bool = True,
                 fuse_epilogues: bool = True, autotune: bool = False,
                 tuning_path: Optional[str] = None,
                 autotune_reps: int = 3,
                 autotune_timer: Optional[Callable] = None,
                 verify: bool = True, tracer=None,
                 precision: str = "fp32", quant=None, mesh=None,
                 mesh_plan=None):
        from repro.core.quant import (check_precision, default_calib_batch,
                                      quantize_graph)
        check_precision(precision)
        self.params = params
        self.graph = as_graph(graph)
        self.img = int(img)
        self.chan = int(chan)
        self.policy = policy
        self.precision = precision
        if precision == "int8" and quant is None:
            quant = quantize_graph(
                self.graph, params,
                default_calib_batch((4, self.chan, self.img, self.img)))
        self.quant = quant
        self.cache = cache if cache is not None else ScheduleCache()
        self.head = head
        self.jit = jit
        self.fuse_epilogues = fuse_epilogues
        self.autotune = autotune
        self.tuning_path = tuning_path
        self.autotune_reps = autotune_reps
        self.autotune_timer = autotune_timer
        self.verify = verify
        self.tracer = tracer          # duck-typed obs tracer (or None)
        self.mesh, self.mesh_plan = mesh, mesh_plan
        self._nets: Dict[int, CompiledNetwork] = {}

    @property
    def buckets(self) -> List[int]:
        """Bucket widths compiled so far, ascending."""
        return sorted(self._nets)

    def __contains__(self, batch: int) -> bool:
        return int(batch) in self._nets

    def network_for(self, batch: int) -> CompiledNetwork:
        """The compiled forward for one bucket width (compiling on first
        use; schedules come from the shared cache)."""
        batch = int(batch)
        if batch < 1:
            raise ValueError(f"bucket width must be >= 1, got {batch}")
        net = self._nets.get(batch)
        if net is None:
            net = compile_network(
                self.params, self.graph,
                (batch, self.chan, self.img, self.img),
                policy=self.policy, cache=self.cache, head=self.head,
                jit=self.jit, fuse_epilogues=self.fuse_epilogues,
                autotune=self.autotune, tuning_path=self.tuning_path,
                autotune_reps=self.autotune_reps,
                autotune_timer=self.autotune_timer, verify=self.verify,
                tracer=self.tracer, precision=self.precision,
                quant=self.quant, mesh=self.mesh, mesh_plan=self.mesh_plan)
            self._nets[batch] = net
        return net

    def stats(self) -> dict:
        """Aggregate compile-surface stats: buckets built + the shared
        schedule cache's fold-reuse counters."""
        d = {"buckets": self.buckets,
             "distinct_schedules": self.cache.distinct}
        d.update(self.cache.stats.as_dict())
        return d
