"""Spatial-Map / Temporal-Map directive algebra (paper Fig 6b) and its
binding to TPU constructs.

The paper expresses its dataflow with two data-centric directives:

  Spatial Map (tile, tile) dim   -- distribute a loop dim across hardware
  Temporal Map (1, 1) dim        -- serialize a loop dim in time

On TPU these become, respectively:

  * across chips  : a mesh axis in a ``PartitionSpec`` (GSPMD/jit)
  * within a chip : a Pallas grid dimension with a ``BlockSpec`` index-map
    (spatial over the MXU lanes, temporal over the grid's streaming dims)

``MappingPlan`` carries a set of directives for a named loop nest and can
emit either form.  The LM framework's sharding rules
(``repro/distributed/sharding.py``) are built from the same algebra, which is
how the paper's conv-mapping discipline generalizes to the assigned
transformer architectures (GEMM = 3-D nest, attention = 5-D nest).

``plan_conv_blocks`` solves the fold-geometry equations (1)-(2) with the
TPU's constraints (MXU tile 128, VMEM capacity) instead of MAVeC's
(R_P, C_P): the filter fold becomes the weight block resident in VMEM, the
image folds become the streamed input blocks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

from jax.sharding import PartitionSpec

from repro.core.loopnest import ConvLoopNest

__all__ = [
    "SpatialMap",
    "TemporalMap",
    "Directive",
    "MappingPlan",
    "ConvBlockPlan",
    "conv_working_set",
    "largest_divisor_le",
    "plan_conv_blocks",
    "serving_conv_plan",
    "tiled_bytes",
    "vmem_request_bytes",
    "VMEM_LIMIT_BYTES",
    "WS_ACC_BYTES_LIMIT",
]

# Ceiling for the weight-stationary kernel's full-height VMEM accumulator
# (nf_block x P x Q fp32).  Conservative physical-VMEM bound: beyond it the
# kernel falls back to psum staging (or output-stationary when an epilogue
# is fused) instead of allocating an uncompilable scratch, and the engine's
# cost model prices the same fallback (engine.dataflow_traffic_bytes).
WS_ACC_BYTES_LIMIT = 16 * 1024 * 1024

# The most VMEM one fold kernel may request (``vmem_limit_bytes``): TPU
# v5e and v6e cores have 128 MiB of VMEM; the rest is left to Mosaic's
# internal scratch.  The planner sizes folds against half of it, and
# foldlint's ``plan.vmem-overflow`` holds every kernel's real blocks
# (``FoldKernelSpec.vmem_bytes``) to all of it.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# Mosaic's default scoped-VMEM limit on v5e (what a kernel gets without
# ``vmem_limit_bytes``), and the headroom requested on top of the blocks
# for in-kernel values (row accumulators, tap windows, pool selectors).
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_VMEM_HEADROOM = 4 * 1024 * 1024

_SUBLANES = {4: 8, 2: 16, 1: 32}         # sublane tile per element width


def tiled_bytes(shape: Sequence[int], itemsize: int = 4) -> int:
    """VMEM bytes of a block once Mosaic pads its last two dims to the
    (sublane, 128-lane) tile of its element width."""
    dims = [int(d) for d in shape]
    if len(dims) == 1:
        dims = [1] + dims
    sub = _SUBLANES[itemsize]
    lead = math.prod(dims[:-2])
    return (lead * _round_up(dims[-2], sub) * _round_up(dims[-1], 128)
            * itemsize)


def vmem_request_bytes(working_set: int) -> int:
    """The ``vmem_limit_bytes`` a fold kernel asks for: its blocks plus
    headroom, never below Mosaic's default scoped limit.  Working sets
    above ``VMEM_LIMIT_BYTES`` are requested as they are, and the chip's
    compiler refuses them — foldlint reports them first."""
    return max(_DEFAULT_SCOPED_VMEM,
               _round_up(working_set + _VMEM_HEADROOM, 1024 * 1024))


@dataclasses.dataclass(frozen=True)
class SpatialMap:
    """Distribute ``dim`` across the hardware axis ``axis``."""
    dim: str
    axis: str            # mesh axis name ("data", "model", "pod") or "mxu"

    def __str__(self) -> str:
        return f"SpatialMap({self.dim} -> {self.axis})"


@dataclasses.dataclass(frozen=True)
class TemporalMap:
    """Serialize ``dim`` in time (streaming order = declaration order)."""
    dim: str
    tile: int = 1        # streaming tile size along the dim

    def __str__(self) -> str:
        return f"TemporalMap({self.dim}, tile={self.tile})"


Directive = Union[SpatialMap, TemporalMap]


@dataclasses.dataclass(frozen=True)
class MappingPlan:
    """A complete binding of a loop nest's dims to space and time."""
    name: str
    dims: Dict[str, int]                      # loop extents
    directives: Tuple[Directive, ...]         # Spatial/Temporal maps, ordered

    def spatial(self) -> List[SpatialMap]:
        return [d for d in self.directives if isinstance(d, SpatialMap)]

    def temporal(self) -> List[TemporalMap]:
        return [d for d in self.directives if isinstance(d, TemporalMap)]

    def validate(self) -> None:
        seen = set()
        for d in self.directives:
            if d.dim not in self.dims:
                raise ValueError(f"{d}: unknown dim (have {list(self.dims)})")
            if d.dim in seen:
                raise ValueError(f"{d}: dim bound twice")
            seen.add(d.dim)

    def partition_spec(self, tensor_dims: Sequence[Optional[str]]
                       ) -> PartitionSpec:
        """Emit a PartitionSpec for a tensor whose axes are named by loop
        dims (None = not a loop dim / replicated)."""
        by_dim = {d.dim: d.axis for d in self.spatial() if d.axis != "mxu"}
        return PartitionSpec(*[by_dim.get(d) if d else None
                               for d in tensor_dims])

    def grid(self) -> Tuple[int, ...]:
        """Pallas grid extents for the temporal dims, in order."""
        return tuple(math.ceil(self.dims[t.dim] / t.tile)
                     for t in self.temporal())

    def __str__(self) -> str:
        body = "; ".join(str(d) for d in self.directives)
        return f"MappingPlan[{self.name}]({body})"


# --------------------------------------------------------------------------
# Conv block-shape solver for the Pallas kernel (TPU fold geometry)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvBlockPlan:
    """Block shapes for the weight-stationary Pallas conv kernel.

    weight block (nf_b, c_b*r*s) stays resident in VMEM across the image
    stream (the Filter Fold); image blocks (c_b, rows, y) stream through
    (the Image Folds); partial sums accumulate in VMEM across the c grid
    dim (the reserved-column reduction, done by the accumulator instead of
    dedicated PE columns -- TPU adaptation, see DESIGN.md §3).
    """
    nf_block: int        # filters per fold  (R_P analogue; MXU-lane aligned)
    c_block: int         # channels per fold (eq (2) analogue; per-group
    #                      when groups > 1)
    p_block: int         # output rows computed per grid step
    grid: Tuple[int, int, int]           # (nf folds, c folds, p folds)
    vmem_bytes: int      # estimated working set
    groups: int = 1      # channel groups G the blocks were solved within:
    #                      nf_block divides N_F/G and c_block divides C/G,
    #                      so no fold ever straddles a group boundary

    @property
    def total_folds(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]

    def clamped(self, nf: int, c: int, p: int) -> "ConvBlockPlan":
        """Clamp block shapes to a layer's actual dims and re-derive the
        grid.  This is what makes a cached schedule reusable across layers
        that share filter-fold geometry but differ spatially (the engine's
        fold reuse): blocks planned for the largest extent shrink exactly
        to any smaller one.  Layers sharing a ``ScheduleKey`` share
        ``(nf, c, groups)``, so only the spatial P clamp ever varies for
        grouped plans and the group-divisibility invariants survive."""
        dw = self.groups > 1 and self.groups == c == nf   # depthwise
        # depthwise channels are independent — the channel block spans the
        # global C axis; grouped blocks live within one group's C/G slice
        c_span = c if dw else c // self.groups
        nf_b = max(1, min(self.nf_block, nf))
        c_b = max(1, min(self.c_block, c_span))
        p_b = max(1, min(self.p_block, p))
        if dw:
            nf_b = c_b                       # filters ride the channel block
            grid = (1, math.ceil(c / c_b), math.ceil(p / p_b))
        else:
            grid = (math.ceil(nf / nf_b), math.ceil(c_span / c_b),
                    math.ceil(p / p_b))
        if (nf_b, c_b, p_b, grid) == (self.nf_block, self.c_block,
                                      self.p_block, self.grid):
            return self
        return dataclasses.replace(self, nf_block=nf_b, c_block=c_b,
                                   p_block=p_b, grid=grid)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def conv_working_set(conv: ConvLoopNest, nf_block: int, c_block: int,
                     p_block: int, bytes_per_elem: int = 4, *,
                     dataflow: Optional[str] = None,
                     epilogue=None) -> int:
    """VMEM bytes the fold kernel really holds for these blocks: the
    full-height input block, the weight fold, the per-filter vectors, the
    output block and any residual block — each double-buffered by the
    Pallas pipeline — plus the accumulator scratch (full-height for
    weight-stationary), all padded to the TPU's tiles.  Computed from the
    launch geometry itself (``kernels/conv2d_ws.py:FoldKernelSpec``), so
    the planner, the autotuner's candidates, foldlint and the kernel's
    ``vmem_limit_bytes`` share one figure.

    ``dataflow=None`` prices the larger of the dataflows the nest can run
    (the planner sizes folds before the dataflow is chosen);
    ``bytes_per_elem`` is the streamed x/w element width."""
    # lazy: the kernel module imports this one
    from repro.kernels.conv2d_ws import fold_kernel_spec
    plan = ConvBlockPlan(nf_block=nf_block, c_block=c_block,
                         p_block=p_block, grid=(1, 1, 1), vmem_bytes=0,
                         groups=conv.groups)
    if dataflow is not None:
        flows: Tuple[str, ...] = (dataflow,)
    elif conv.depthwise:
        flows = ("depthwise",)
    else:
        flows = ("weight_stationary", "output_stationary")
    return max(fold_kernel_spec(
        (conv.n, conv.c, conv.padded_x, conv.padded_y),
        (conv.nf, conv.cg, conv.r, conv.s), stride=conv.stride, plan=plan,
        dataflow=df, epilogue=epilogue, groups=conv.groups
    ).vmem_bytes(bytes_per_elem) for df in flows)


def _p_block(conv: ConvLoopNest) -> int:
    """Output rows per image fold: about 512 output positions, and — when
    that leaves more than one fold — a multiple of 16, so the per-fold
    output block (halved by a fused 2x2 pool) keeps the TPU's 8-row
    sublane tiling.  A single fold spans the whole height (the block is
    then the full extent, which is always legal)."""
    want = max(1, 512 // max(conv.q, 1))
    if want >= conv.p:
        return conv.p
    return min(conv.p, _round_up(want, 16))


def _dw_channel_block(c: int) -> int:
    """Channels per depthwise fold: all of them up to 128, else the
    largest 8-aligned divisor of C within 128 (128 when none divides).
    Mosaic keeps each tap's (c_b, q) window, broadcast filter column and
    partial product live at once — some 44 (c_b, 128-lane) values for a
    3x3 — which past 128 channels outgrows the kernel's VMEM headroom
    (``vmem_request_bytes``)."""
    if c <= 128:
        return _round_up(c, 8)
    return next((d for d in range(128, 7, -8) if c % d == 0), 128)


def largest_divisor_le(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1).  Group-blocked
    axes must tile exactly — a fold straddling a group boundary would mix
    channels from two independent reductions."""
    cap = max(1, min(cap, n))
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def plan_conv_blocks(conv: ConvLoopNest,
                     vmem_limit: int = VMEM_LIMIT_BYTES,
                     mxu: int = 128,
                     bytes_per_elem: int = 4) -> ConvBlockPlan:
    """Solve eqs (1)-(2) under TPU constraints.

    R_P -> nf_block: min(N_F, 2*mxu) rounded to the MXU lane width so the
           filter dim fills the systolic array.
    C_P -> c_block:  largest channel count whose real kernel blocks
           (``conv_working_set``: double-buffered full-height input,
           weight fold, output, accumulator) fit in half of
           ``vmem_limit``.  A block smaller than C stays a multiple of 128
           (the weight fold's lane dim), so only multiples of 256 halve.
    P   -> p_block:  ~512 output positions, 16-row aligned (``_p_block``).

    Grouped nests (``conv.groups > 1``) solve the same equations *within
    one group*: ``nf_block`` divides N_F/G and ``c_block`` divides C/G
    exactly (no fold straddles a group boundary), and the nf grid axis
    spans all G groups' filter folds.  A depthwise nest (G == C == N_F)
    has no depth folds at all — the channel block doubles as the filter
    block and the grid's c axis walks the channels.
    """
    p_block = _p_block(conv)

    def working_set(nf_b: int, c_b: int) -> int:
        return conv_working_set(conv, nf_b, c_b, p_block, bytes_per_elem)

    if conv.depthwise:
        # one filter per channel: block the channel axis only (channels are
        # independent, so any 8-aligned block is legal)
        c_block = _dw_channel_block(conv.c)
        while c_block > 8 and working_set(c_block, c_block) > vmem_limit // 2:
            c_block = _round_up(c_block // 2, 8)
        grid = (1, math.ceil(conv.c / c_block), math.ceil(conv.p / p_block))
        return ConvBlockPlan(nf_block=c_block, c_block=c_block,
                             p_block=p_block, grid=grid,
                             vmem_bytes=working_set(c_block, c_block),
                             groups=conv.groups)

    if conv.groups > 1:
        nfg, cg = conv.nfg, conv.cg
        want_nf = min(_round_up(nfg, 8), 2 * mxu)
        nf_block = largest_divisor_le(nfg, want_nf)
        c_block = largest_divisor_le(cg, 512)
        while (c_block > 1
               and working_set(nf_block, c_block) > vmem_limit // 2):
            c_block = largest_divisor_le(cg, c_block - 1)
        grid = (conv.groups * (nfg // nf_block), cg // c_block,
                math.ceil(conv.p / p_block))
        return ConvBlockPlan(nf_block=nf_block, c_block=c_block,
                             p_block=p_block, grid=grid,
                             vmem_bytes=working_set(nf_block, c_block),
                             groups=conv.groups)

    nf_block = min(_round_up(conv.nf, 8), 2 * mxu)
    c_block = min(conv.c, 512)
    while (c_block % 256 == 0
           and working_set(nf_block, c_block) > vmem_limit // 2):
        c_block //= 2
    grid = (math.ceil(conv.nf / nf_block),
            math.ceil(conv.c / c_block),
            math.ceil(conv.p / p_block))
    return ConvBlockPlan(nf_block=nf_block, c_block=c_block, p_block=p_block,
                         grid=grid, vmem_bytes=working_set(nf_block, c_block))


# --------------------------------------------------------------------------
# Canonical plans (Fig 6) -- used by docs/tests and the distributed layer
# --------------------------------------------------------------------------

def weight_stationary_conv_plan(conv: ConvLoopNest) -> MappingPlan:
    """Fig 6(b): FF spatial, IF/IB temporal, PS reduced."""
    plan = MappingPlan(
        name=f"ws-conv[{conv}]",
        dims=conv.dims(),
        directives=(
            SpatialMap("N_F", "mxu"),       # filters across PE rows
            SpatialMap("R", "mxu"),         # flattened filter cols
            SpatialMap("S", "mxu"),
            TemporalMap("C", 1),            # image blocks (depth)
            TemporalMap("N", 1),            # image folds
            TemporalMap("P", 1),
            TemporalMap("Q", 1),            # shift cycles
        ),
    )
    plan.validate()
    return plan


def serving_conv_plan(batch: int, nf: int, *, data_axis: str = "data",
                      model_axis: str = "model") -> MappingPlan:
    """The Spatial-Map directive set for batched conv serving: the batch
    (image-fold streaming) axis distributes across the ``data`` mesh axis
    and the N_F (filter-fold stationary) axis across ``model`` — the same
    two bindings Fig 6 assigns on-fabric, lifted one level to the mesh.

    ``partition_spec`` on this plan is how the serving engine emits its
    shardings: activations are ``("N", None, None, None)`` (NCHW), conv
    weights ``("N_F", None, None, None)`` (OIHW), biases ``("N_F",)`` —
    see ``distributed/sharding.py:vision_shardings``.
    """
    plan = MappingPlan(
        name=f"serve-conv[n={batch},nf={nf}]",
        dims={"N": batch, "N_F": nf},
        directives=(
            SpatialMap("N", data_axis),      # image folds -> DP
            SpatialMap("N_F", model_axis),   # filter folds -> TP
        ),
    )
    plan.validate()
    return plan


def lm_train_plan(batch: int, seq: int, d_model: int) -> MappingPlan:
    """The directive set behind the LM sharding rules: batch spatial on
    data (and pod), model dims spatial on model, sequence temporal."""
    plan = MappingPlan(
        name="lm-train",
        dims={"B": batch, "T": seq, "D": d_model},
        directives=(
            SpatialMap("B", "data"),
            SpatialMap("D", "model"),
            TemporalMap("T", seq),
        ),
    )
    plan.validate()
    return plan
