"""Fold-streamed convolution Pallas kernel (the paper's technique on TPU).

Two dataflows, selected by grid ordering — both derived from the paper's
Filter-Fold / Image-Fold / Image-Block decomposition (DESIGN.md §3), and
both reducing depth folds *in-kernel* (the paper's Fig 5 reserved-column
accumulation collapses into a VMEM accumulator; no partial-sum tensor is
ever materialized in HBM):

* ``weight_stationary`` (paper-faithful): grid (N, NF folds, C folds, P
  folds) with the P (image-fold) dimension innermost.  The weight block —
  the Filter Fold — has an index map that is constant along P, so Pallas
  keeps it resident in VMEM while image folds stream through.  Depth folds
  are accumulated into a full-height VMEM scratch (one slice per P fold);
  the output block's index map is constant along both C and P, so the
  finished output stays resident across the whole (C, P) sweep and is
  written to HBM exactly once per (N, NF-fold) — the partial-sum HBM
  write+read of the original formulation disappears.

* ``output_stationary`` (beyond-paper optimized): grid (N, NF folds, P
  folds, C folds) with the depth dimension innermost; partial sums stay in
  a block-sized VMEM accumulator and the output is written exactly once.
  This trades weight re-fetch (x P folds) for a block-sized (rather than
  full-height) accumulator; ``core/engine.py:dataflow_costs`` prices the
  trade and ``autotune_schedule`` can measure it.

Both kernels flush an optional fused **epilogue** (bias add, ResNet-style
residual shortcut add, ReLU, 2x2/2 max-pool — ``core/epilogue.py``) at the
moment the last depth fold finishes, so a conv→bias(→+shortcut)→ReLU(→pool)
chain is one ``pallas_call`` and the pre-activation tensor never leaves
VMEM.

``weight_stationary_psum`` keeps the original PR-1 formulation — each
depth fold emits a partial-sum fold to HBM, reduced afterwards with XLA —
as a benchmarking baseline only (``benchmarks/kernel_bench.py`` reports
the bytes-moved delta); the engine never selects it.

**Grouped convolution** (``groups > 1``) reuses both dataflows unchanged:
the block plan solves the fold geometry *within one group* (``nf_block``
divides N_F/G, ``c_block`` divides C/G — ``core/mapping.py``), the nf
grid axis spans all G groups' filter folds, and only the input BlockSpec
index map changes — it offsets the streamed channel block by the group
the current filter fold belongs to.  The kernel bodies never learn about
groups.  **Depthwise** (G == C == N_F) is the degenerate case with no
depth folds at all, served by a dedicated kernel (``_dw_kernel``): grid
(N, channel folds, P folds), one filter tap column per resident channel,
the VPU doing per-channel multiply-accumulate with no reduction and the
epilogue flushing every grid step (there is nothing to wait for).

The in-kernel compute realizes the fold interaction of Fig 4: for each of
the R*S filter taps, a strided window of the resident image rows is
multiplied against the stationary tap column and accumulated — the MXU
plays the PE array (filters x channels lanes), the VPU shift plays the
stride right-shift.

**Row packing.**  A tap's dot costs about the same for every 128-lane
tile of its window, however few of the lanes are used, so a stride-1
WS/OS launch with narrow rows contracts each tap against ``rows_per_dot``
(k) consecutive output rows at once.  The kernel lays a row group's k +
R - 1 padded input rows end to end on the lane axis, ``wph`` lanes
apart, so every tap's window over the group's k output rows is one run
of lanes at a static offset; the ``wph - q`` lanes of each row that fall
on the padding are computed and dropped.  The launch shape alone
sets k (``_rows_per_dot``); at 1 — strided launches, rows too wide to
save a lane tile, 1x1 launches of one 128-deep pass, the psum baseline,
depthwise — one dot covers one row.

Inputs are NCHW, weights OIHW (matching the paper's tensors).  Caller
pre-pads spatially (``ops.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue, epilogue_out_hw
from repro.core.loopnest import ConvLoopNest
from repro.core.mapping import (WS_ACC_BYTES_LIMIT, ConvBlockPlan,
                                _round_up, plan_conv_blocks, tiled_bytes,
                                vmem_request_bytes)

__all__ = ["conv2d_folded", "default_plan", "DATAFLOWS",
           "OperandSpec", "FoldKernelSpec", "fold_kernel_spec"]

DATAFLOWS = ("weight_stationary", "output_stationary", "depthwise")


_HIGHEST = jax.lax.Precision.HIGHEST

# Elements of one row group's largest value — a tap's (channels, lanes)
# window, or the (filters, lanes) partial sum the flush rolls — in a
# packed launch: 1024 lanes at 64 channels, 128 at 512.  Past it the
# kernel's values outgrow the VMEM the launch requests, and the flush
# rolls more lanes than the packing saves.
DOT_WINDOW = 512 * 128

_LANES = 128                            # lanes of one MXU / vreg tile


def _rows_per_dot(dataflow: str, stride: int, r: int, s: int, c_b: int,
                  nf_b: int, p_block: int, wph: int, q: int,
                  pooled: bool) -> int:
    """Output rows per dot (k) of a launch: the most rows whose ``k *
    wph`` lanes (``wph``, the padded row width), in 128-lane tiles over the larger of ``c_b`` channels
    and ``nf_b`` filters, fit ``DOT_WINDOW``; that divide the P fold (so
    one loop body walks its row groups); and — under a fused 2x2 pool —
    even, so pool pairs never straddle a group.  1, one dot per row, for
    the psum baseline, depthwise and strided launches; where a row's taps
    make a single 128-deep MXU pass (a 1x1 conv over at most 128
    channels), whose dot costs less than a packed row's flush; and where
    packing saves no 128-lane tile of MXU work (rows as wide as VGG's 112
    and 224 px)."""
    if (dataflow not in ("weight_stationary", "output_stationary")
            or stride != 1 or r * s * -(-c_b // _LANES) < 2):
        return 1
    lanes = DOT_WINDOW // _round_up(max(c_b, nf_b), 8) // _LANES * _LANES
    k = next((k for k in range(min(lanes // wph, p_block), 1, -1)
              if p_block % k == 0 and not (pooled and k % 2)), 1)
    tiles = (p_block // k) * -(-k * wph // _LANES)
    return k if tiles < p_block * -(-q // _LANES) else 1


def _tap_lane(si: int, stride: int, wph: int) -> int:
    """First lane of filter column ``si``'s window in the stride-phase
    input layout (``_phase_split``): column ``si + stride*j`` of the padded
    row lives at lane ``(si % stride) * wph + si // stride + j``, so every
    tap reads ``q`` *contiguous* lanes — no strided lane access."""
    return (si % stride) * wph + si // stride


def _tap_dot(tap, win, acc_dtype):
    """One tap's (nf_b, c_b) tile against its (c_b, lanes) window, in
    ``acc_dtype`` — fp32 for the fp32 path (contracted at
    ``Precision.HIGHEST``: full fp32 products, fp32 accumulation), int32
    for int8 streams (the MXU contracts the int8 operands directly; the
    int32 depth-fold accumulation is exact, see ``core/quant.py``)."""
    if acc_dtype == jnp.float32:
        return jnp.dot(tap.astype(jnp.float32), win.astype(jnp.float32),
                       preferred_element_type=acc_dtype, precision=_HIGHEST)
    return jnp.dot(tap, win, preferred_element_type=acc_dtype)


def _row_taps(x_ref, w_ref, xrow, *, r: int, s: int, stride: int, wph: int,
              q: int, acc_dtype):
    """One output row of one fold interaction (Fig 4): the R*S stationary
    taps, each an (nf_b, c_b) filter-fold column, against the (c_b, q)
    image-row window under it.  ``xrow`` is the first input row.  Returns
    (nf_b, q) in ``acc_dtype`` (``_tap_dot``)."""
    acc = None
    for ri in range(r):
        line = x_ref[0, :, xrow + ri, :]             # (c_b, stride * wph)
        for si in range(s):
            lane0 = _tap_lane(si, stride, wph)
            win = line[:, lane0:lane0 + q]                      # (c_b, q)
            part = _tap_dot(w_ref[ri * s + si], win, acc_dtype)
            acc = part if acc is None else acc + part
    return acc


def _group_taps(x_ref, w_ref, xrow, *, r: int, s: int, wph: int,
                rows: int, lanes: int, acc_dtype):
    """``rows`` (k) consecutive output rows of a stride-1 fold in one dot
    per tap.  For tap row ri the input rows ``xrow`` + ri .. + ri + k - 1
    are laid end to end on the lane axis, so output row u, column c of
    tap (ri, si) reads lane u * wph + si + c: the window under a tap is
    one static slice of ``lanes`` lanes (k * wph in whole 128-lane
    tiles; the lanes past the rows, zero-filled, reach only columns that
    are never flushed).  Returns (nf_b, lanes) in ``acc_dtype``; the taps
    accumulate in the order of ``_row_taps``."""
    acc = None
    for ri in range(r):
        line = [x_ref[0, :, xrow + ri + u, :] for u in range(rows)]
        tail = lanes - rows * wph + s - 1
        if tail:
            line.append(jnp.zeros((line[0].shape[0], tail), line[0].dtype))
        line = jnp.concatenate(line, axis=1)
        for si in range(s):
            part = _tap_dot(w_ref[ri * s + si], line[:, si:si + lanes],
                            acc_dtype)
            acc = part if acc is None else acc + part
    return acc


def _epilogue_row(v, b_ref, epi: Epilogue, res=None):
    """Apply the fused epilogue (bar the pool) to one finished fp32 output
    row (nf_b, q).  ``b_ref`` is the (nf_b, 3) per-filter vector block:
    column 0 the bias, columns 1-2 the folded batch-norm scale/shift
    (``Epilogue.scale``) — unused columns are never read."""
    if epi.bias:
        v = v + b_ref[:, 0:1].astype(jnp.float32)
    if epi.scale:                            # inference BN: y*scale + shift
        v = (v * b_ref[:, 1:2].astype(jnp.float32)
             + b_ref[:, 2:3].astype(jnp.float32))
    if epi.residual:
        v = v + res.astype(jnp.float32)      # ResNet shortcut, pre-ReLU
    if epi.relu:
        v = jnp.maximum(v, 0.0)
    if epi.relu6:
        v = jnp.clip(v, 0.0, 6.0)            # MobileNet activation
    return v


def _pool_pair(top, bottom):
    """2x2/2 max-pool of two finished output rows (nf_b, q) -> (nf_b, q//2).

    The vertical max is elementwise; the horizontal pairs are compacted
    with two 0/1 selection matmuls (even / odd columns), because Mosaic
    lowers neither a lane-strided slice nor a lane-splitting reshape.  At
    ``Precision.HIGHEST`` a product with 1.0 plus exact zeros reproduces
    each value bit for bit, so the pool stays exact."""
    m = jnp.maximum(top, bottom)
    q = m.shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q // 2), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q // 2), 1)

    def pick(offset):
        sel = (rows == 2 * cols + offset).astype(jnp.float32)
        return jnp.dot(m, sel, preferred_element_type=jnp.float32,
                       precision=_HIGHEST)
    return jnp.maximum(pick(0), pick(1))


def _flush_rows(rows, k, b_ref, res_ref, out_ref, epi: Epilogue, *,
                res_row0, out_row0):
    """Flush one row group — two rows when the 2x2 pool is fused, else one
    — of finished accumulator values.  ``rows`` is [(local_row, value)];
    ``k`` indexes the group within the fold."""
    vals = []
    for j, v in rows:
        res = (res_ref[0, :, res_row0 + j, :] if epi.residual else None)
        vals.append(_epilogue_row(v.astype(jnp.float32), b_ref, epi, res))
    if epi.pool == "max2":
        out_ref[0, :, out_row0 + k, :] = _pool_pair(*vals).astype(
            out_ref.dtype)
    else:
        out_ref[0, :, out_row0 + k, :] = vals[0].astype(out_ref.dtype)


def _fold_rows(x_ref, w_ref, b_ref, res_ref, out_ref, acc_ref, *, i_c, i_p,
               n_c: int, resident, r: int, s: int, stride: int, wph: int,
               p_block: int, q: int, epi: Epilogue, acc_dtype,
               rows_per_dot: int):
    """The shared fold body of the WS and OS kernels: walk the P fold's
    output rows, accumulate their depth-fold partials into ``acc_ref``
    (first depth fold writes, later ones add), and flush the epilogue row
    by row on the last depth fold.  At ``rows_per_dot`` 1 a loop takes
    one row (a pool pair when the pool is fused) per step and ``acc_ref``
    holds (nf_b, rows, q); above it a loop takes one row group, a dot per
    tap (``_group_taps``), and ``acc_ref`` holds the groups packed as
    they leave the MXU, (groups, nf_b, lanes), each row rolled down to
    lane 0 for its flush.  The dataflows differ only in ``resident``, the
    P fold's index into the accumulator, residual and output blocks (the
    WS kernel's are full-height, so ``i_p``; the OS kernel's are one fold,
    so 0), and in the grid order that decides which operand stays
    resident."""
    group = 2 if epi.pool == "max2" else 1   # p_block is even when pooled
    row0 = resident * p_block

    def flush(rows, g):
        _flush_rows(rows, g, b_ref, res_ref, out_ref, epi, res_row0=row0,
                    out_row0=resident * (p_block // group))

    if rows_per_dot == 1:
        def body(k, carry):
            rows = []
            for u in range(group):
                j = k * group + u                       # row in the fold
                part = _row_taps(x_ref, w_ref, (i_p * p_block + j) * stride,
                                 r=r, s=s, stride=stride, wph=wph, q=q,
                                 acc_dtype=acc_dtype)
                acc = jnp.where(i_c == 0, part,
                                acc_ref[:, row0 + j, :] + part)
                acc_ref[:, row0 + j, :] = acc
                rows.append((j, acc))
            pl.when(i_c == n_c - 1)(lambda: flush(rows, k))
            return carry

        jax.lax.fori_loop(0, p_block // group, body, 0)
        return

    k, n_groups = rows_per_dot, p_block // rows_per_dot
    lanes = acc_ref.shape[-1]

    def group_body(t, carry):
        part = _group_taps(x_ref, w_ref, i_p * p_block + t * k, r=r, s=s,
                           wph=wph, rows=k, lanes=lanes,
                           acc_dtype=acc_dtype)
        ga = resident * n_groups + t
        acc_ref[ga] = jnp.where(i_c == 0, part, acc_ref[ga] + part)

        @pl.when(i_c == n_c - 1)
        def _flush():
            def row_body(v, c):
                # the v-th row (pool pair) of the group, moved to lane 0
                top = pltpu.roll(acc_ref[ga], jax.lax.rem(
                    lanes - v * (group * wph), lanes), 1)
                j = t * k + v * group
                flush([(j + u, top[:, u * wph:u * wph + q])
                       for u in range(group)], t * (k // group) + v)
                return c

            jax.lax.fori_loop(0, k // group, row_body, 0)
        return carry

    jax.lax.fori_loop(0, n_groups, group_body, 0)


def _ws_kernel(x_ref, w_ref, b_ref, *refs, n_c: int, epi: Epilogue,
               acc_dtype=jnp.float32, **geometry):
    """Weight-stationary with in-kernel depth reduction.

    Grid: (N, nf, c, p); p fastest.  ``acc_ref`` holds the full output
    height for this (N, nf-fold) — the software form of the paper's
    reserved-column partial sums staged on-fabric.  The output block is
    revisited contiguously across the whole (c, p) sweep and flushed (with
    the epilogue) as each P slice finishes its last depth fold.  With
    ``epi.residual`` an extra shortcut input rides along (full-height,
    resident like the output) and is added at flush time.  Int8 streams
    accumulate in an int32 ``acc_ref``; the flush-time cast to fp32 is
    where the requant affine (folded into the scale/shift slot) applies.
    """
    res_ref, (out_ref, acc_ref) = (refs[0] if epi.residual else None,
                                   refs[-2:])
    i_p = pl.program_id(3)
    _fold_rows(x_ref, w_ref, b_ref, res_ref, out_ref, acc_ref,
               i_c=pl.program_id(2), i_p=i_p, n_c=n_c, resident=i_p,
               epi=epi, acc_dtype=acc_dtype, **geometry)


def _os_kernel(x_ref, w_ref, b_ref, *refs, n_c: int, epi: Epilogue,
               acc_dtype=jnp.float32, **geometry):
    """Output-stationary variant. Grid: (N, nf, p, c); c fastest — the
    block-sized accumulator and the output block stay resident across the
    depth sweep."""
    res_ref, (out_ref, acc_ref) = (refs[0] if epi.residual else None,
                                   refs[-2:])
    _fold_rows(x_ref, w_ref, b_ref, res_ref, out_ref, acc_ref,
               i_c=pl.program_id(3), i_p=pl.program_id(2), n_c=n_c,
               resident=0, epi=epi, acc_dtype=acc_dtype, **geometry)


def _dw_kernel(x_ref, w_ref, b_ref, *refs, r: int, s: int, stride: int,
               wph: int, p_block: int, q: int, epi: Epilogue,
               acc_dtype=jnp.float32):
    """Depthwise kernel: grid (N, c folds, p folds) — **no depth-fold
    reduction exists**.  Each channel owns exactly one filter, so an
    output row (c_b, q) is finished the moment its R*S taps have
    accumulated: each tap's (c_b, 1) filter column multiplies the resident
    channel rows elementwise on the VPU (no MXU contraction — there is no
    channel sum), and the epilogue flushes immediately.  Int8 streams
    widen each operand to int32 *before* the elementwise product (int8 x
    int8 would wrap) and accumulate the R*S taps exactly.
    """
    res_ref, out_ref = (refs[0] if epi.residual else None, refs[-1])
    i_p = pl.program_id(2)
    group = 2 if epi.pool == "max2" else 1

    def body(k, carry):
        rows = []
        for u in range(group):
            j = k * group + u
            xrow = (i_p * p_block + j) * stride
            acc = None
            for ri in range(r):
                line = x_ref[0, :, xrow + ri, :]
                for si in range(s):
                    lane0 = _tap_lane(si, stride, wph)
                    win = line[:, lane0:lane0 + q]           # (c_b, q)
                    tap = w_ref[ri * s + si]                 # (c_b, 1)
                    part = win.astype(acc_dtype) * tap.astype(acc_dtype)
                    acc = part if acc is None else acc + part
            rows.append((j, acc))
        _flush_rows(rows, k, b_ref, res_ref, out_ref, epi, res_row0=0,
                    out_row0=0)
        return carry

    jax.lax.fori_loop(0, p_block // group, body, 0)


def _ws_psum_kernel(x_ref, w_ref, out_ref, *, r: int, s: int, stride: int,
                    wph: int, p_block: int, q: int):
    """PR-1 weight-stationary formulation: each depth fold emits a
    partial-sum fold to HBM (benchmarking baseline only)."""
    i_p = pl.program_id(3)

    def body(j, carry):
        part = _row_taps(x_ref, w_ref, (i_p * p_block + j) * stride, r=r,
                         s=s, stride=stride, wph=wph, q=q,
                         acc_dtype=jnp.float32)
        out_ref[0, 0, :, j, :] = part.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, p_block, body, 0)


def default_plan(conv: ConvLoopNest, **kw) -> ConvBlockPlan:
    return plan_conv_blocks(conv, **kw)


def _vector_block(nf: int, nf_pad: int, epi: Epilogue, bias, scale, shift
                  ) -> jnp.ndarray:
    """The (nf_pad, 3) per-filter vector block every fold kernel carries:
    column 0 the bias, columns 1-2 the folded-BN scale/shift.  Columns the
    epilogue doesn't enable are zeros and never read in-kernel."""
    zero = jnp.zeros((nf,), jnp.float32)
    cols = [bias.astype(jnp.float32) if epi.bias else zero,
            scale.astype(jnp.float32) if epi.scale else zero,
            shift.astype(jnp.float32) if epi.scale else zero]
    out = jnp.stack(cols, axis=1)
    if nf_pad != nf:
        out = jnp.pad(out, ((0, nf_pad - nf), (0, 0)))
    return out


# --------------------------------------------------------------------------
# Index maps as inspectable data
# --------------------------------------------------------------------------
# Every BlockSpec index map below is a *named module-level function* (bound
# with ``functools.partial`` where group geometry applies) rather than an
# inline closure, so the static analyzer (``repro/analysis/index_check.py``)
# can enumerate grid x index-map products and prove coverage / race freedom
# on the exact callables the kernel binds.  Grid argument orders:
#   weight_stationary / psum : (b, f, cc, pp)   -- grid (N, nf, c, p)
#   output_stationary        : (b, f, pp, cc)   -- grid (N, nf, p, c)
#   depthwise                : (b, cc, pp)      -- grid (N, c, p)

def _ix_ws_x(b, f, cc, pp, *, nfg_folds: int, cg_folds: int):
    """Streamed input block: channel fold ``cc`` within the group the
    current filter fold ``f`` belongs to.  Dense layers are the G=1 case
    (``nfg_folds`` = all nf folds, so the group index is always 0)."""
    return (b, (f // nfg_folds) * cg_folds + cc, 0, 0)


def _ix_ws_w(b, f, cc, pp):
    """Weight fold (all R*S taps): globally filter-indexed, per-group
    channel-indexed."""
    return (0, f, cc)


def _ix_ws_vec(b, f, cc, pp):
    return (f, 0)


def _ix_ws_res(b, f, cc, pp):
    """Residual rides full-height, resident like the WS accumulator."""
    return (b, f, 0, 0)


def _ix_ws_out(b, f, cc, pp):
    """Constant along (c, p): the finished output stays resident in VMEM
    for the whole sweep and hits HBM exactly once.  P-fold revisits write
    disjoint in-block row slices (``inner_sliced_axes``)."""
    return (b, f, 0, 0)


def _ix_os_x(b, f, pp, cc, *, nfg_folds: int, cg_folds: int):
    return (b, (f // nfg_folds) * cg_folds + cc, 0, 0)


def _ix_os_w(b, f, pp, cc):
    return (0, f, cc)


def _ix_os_vec(b, f, pp, cc):
    return (f, 0)


def _ix_os_res(b, f, pp, cc):
    return (b, f, pp, 0)


def _ix_os_out(b, f, pp, cc):
    """Constant along c only: the depth sweep accumulates into the
    block-sized scratch and writes the block once."""
    return (b, f, pp, 0)


def _ix_dw_x(b, cc, pp):
    return (b, cc, 0, 0)


def _ix_dw_w(b, cc, pp):
    return (0, cc, 0)


def _ix_dw_vec(b, cc, pp):
    return (cc, 0)


def _ix_dw_res(b, cc, pp):
    return (b, cc, pp, 0)


def _ix_dw_out(b, cc, pp):
    return (b, cc, pp, 0)


def _ix_psum_out(b, f, cc, pp):
    """One partial-sum fold per depth fold: cc addresses a leading psum
    axis, so every grid point owns a distinct output block (no revisits)."""
    return (cc, b, f, pp, 0)


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One pallas_call operand: its block shape, the (padded) array shape
    the kernel binds, and the BlockSpec index map as an inspectable
    callable.  ``role`` is one of x | w | vec | residual | out."""
    role: str
    block: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]

    def block_spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.block, self.index_map)


@dataclasses.dataclass(frozen=True)
class FoldKernelSpec:
    """The complete static description of one fold-streamed conv kernel
    launch: resolved dataflow, grid, and every operand's BlockSpec geometry
    as data.  ``conv2d_folded`` consumes it to bind the pallas_call;
    ``repro/analysis`` consumes it to prove coverage, in-bounds access, and
    single-writer discipline without tracing anything.

    ``reduction_axis`` is the depth-fold grid axis (the only axis allowed
    to revisit the accumulator/output block); ``inner_sliced_axes`` are
    grid axes whose output revisits are *disjoint in-block sub-slices*
    (the WS kernel's per-P-fold output rows), not races.

    Operand layouts (what the kernel binds, prepared by ``conv2d_folded``):
    x is NCHW with its width split into ``stride`` phases of ``wph`` lanes
    (``_phase_split``; the identity at stride 1); w is tap-major (R*S, N_F, C/G) — (R*S, C, 1) for depthwise — so each
    tap is one 2-D (filters x channels) tile.  ``scratch`` is the
    accumulator's shape (None when the kernel keeps none): per row,
    (nf_b, rows, q), or — where ``rows_per_dot`` (k) > 1 — per row group
    as the dots leave the MXU, (groups, nf_b, lanes), k * wph rounded
    up to 128-lane tiles.
    """
    dataflow: str                       # resolved (post-fallback)
    requested: str                      # dataflow as requested by caller
    grid: Tuple[int, ...]
    grid_axes: Tuple[str, ...]          # loop-nest name per grid axis
    reduction_axis: Optional[int]
    inner_sliced_axes: Tuple[int, ...]
    inputs: Tuple[OperandSpec, ...]
    output: OperandSpec
    scratch: Optional[Tuple[int, ...]]
    epilogue: Epilogue
    plan: ConvBlockPlan                 # clamped to this layer's dims
    groups: int
    nfg_folds: int                      # nf folds per group (g_nf / G)
    cg_folds: int                       # c folds per group (= depth folds)
    nf: int
    c: int
    p: int
    q: int
    r: int
    s: int
    stride: int
    nf_pad: int
    c_pad: int
    p_pad: int
    x_rows: int                         # padded input rows the kernel sees
    wph: int                            # lanes per stride phase of x
    p_block: int                        # post pool-even bump
    p_valid: int
    q_valid: int
    rows_per_dot: int                   # output rows per tap dot (k)

    def vmem_bytes(self, stream_bytes: int = 4) -> int:
        """VMEM the launch really holds: every operand block twice (the
        Pallas pipeline double-buffers inputs and the output) plus the
        accumulator scratch, each padded to the TPU's (sublane, 128)
        tiles.  The x and w blocks stream at ``stream_bytes`` per element
        (1 for int8); the vector, residual, output and accumulator blocks
        are 4 bytes wide."""
        total = 0
        for op in (*self.inputs, self.output):
            itemsize = stream_bytes if op.role in ("x", "w") else 4
            total += 2 * tiled_bytes(op.block, itemsize)
        if self.scratch is not None:
            total += tiled_bytes(self.scratch, 4)
        return total


def fold_kernel_spec(x_shape: Tuple[int, int, int, int],
                     w_shape: Tuple[int, int, int, int], *,
                     stride: int = 1,
                     plan: Optional[ConvBlockPlan] = None,
                     dataflow: str = "weight_stationary",
                     epilogue: Optional[Epilogue] = None,
                     groups: int = 1) -> FoldKernelSpec:
    """Solve the complete launch geometry for a fold-streamed conv — block
    clamping, the pool-even P bump, padding, and the WS->psum/OS VMEM
    fallback — and return it as inspectable data.  Pure shape arithmetic:
    no arrays are touched, so the analyzer can call it on any layer.
    ``x_shape`` is the pre-padded NCHW input, ``w_shape`` OIHW."""
    n, c, xp_, yp_ = x_shape
    nf, cw, r, s = w_shape
    assert c == cw * groups, (c, cw, groups)
    assert nf % groups == 0, (nf, groups)
    p = (xp_ - r) // stride + 1
    q = (yp_ - s) // stride + 1
    wph = -(-yp_ // stride)                 # lanes per stride phase
    epi = epilogue or Epilogue()
    if epi.pool == "max2" and (p < 2 or q < 2):
        raise ValueError(f"cannot fuse 2x2 pool into a {p}x{q} output")
    requested = dataflow
    if dataflow == "depthwise" and not (groups > 1 and groups == c == nf):
        raise ValueError("dataflow='depthwise' needs groups == C == N_F, "
                         f"got groups={groups}, C={c}, N_F={nf}")
    if dataflow not in DATAFLOWS + ("weight_stationary_psum",):
        raise ValueError(f"unknown dataflow {dataflow!r}")
    if dataflow == "weight_stationary_psum":
        if not epi.identity:
            raise ValueError("the legacy psum dataflow has no fused epilogue")
        if groups > 1:
            raise ValueError("the legacy psum dataflow predates grouped "
                             "convolution")
    if plan is None or plan.groups != groups:
        # a plan solved for a different group structure cannot tile this
        # layer (divisibility invariants differ) — re-solve
        cv = ConvLoopNest(n=n, nf=nf, c=c, r=r, s=s,
                          x=xp_, y=yp_, stride=stride, pad=0, groups=groups)
        plan = plan_conv_blocks(cv)
    plan = plan.clamped(nf, c, p)
    nf_b, c_b, p_b = plan.nf_block, plan.c_block, plan.p_block
    g_nf, g_c, g_p = plan.grid
    pooled = epi.pool == "max2"
    if pooled and p_b % 2:
        # pool windows must not straddle P-fold boundaries
        p_b += 1
        g_p = -(-p // p_b)
    p_valid, q_valid = epilogue_out_hw(epi, p, q)
    q_o = q // 2 if pooled else q
    rs = r * s

    def common(rows_per_dot=1, **kw):
        return FoldKernelSpec(
            requested=requested, epilogue=epi, plan=plan, groups=groups,
            nf=nf, c=c, p=p, q=q, r=r, s=s, stride=stride, wph=wph,
            p_block=p_b, p_valid=p_valid, q_valid=q_valid,
            rows_per_dot=rows_per_dot, **kw)

    if dataflow == "depthwise":
        c_pad, p_pad = g_c * c_b, g_p * p_b
        x_rows = max(xp_, (p_pad - 1) * stride + r)
        p_b_o = p_b // 2 if pooled else p_b
        p_o_pad = p_pad // 2 if pooled else p_pad
        inputs = [
            OperandSpec("x", (1, c_b, x_rows, stride * wph),
                        (n, c_pad, x_rows, stride * wph), _ix_dw_x),
            OperandSpec("w", (rs, c_b, 1), (rs, c_pad, 1), _ix_dw_w),
            OperandSpec("vec", (c_b, 3), (c_pad, 3), _ix_dw_vec),
        ]
        if epi.residual:
            inputs.append(OperandSpec("residual", (1, c_b, p_b, q),
                                      (n, c_pad, p_pad, q), _ix_dw_res))
        out = OperandSpec("out", (1, c_b, p_b_o, q_o),
                          (n, c_pad, p_o_pad, q_o), _ix_dw_out)
        return common(
            dataflow="depthwise", grid=(n, g_c, g_p),
            grid_axes=("n", "c", "p"), reduction_axis=None,
            inner_sliced_axes=(), inputs=tuple(inputs), output=out,
            scratch=None, nfg_folds=1, cg_folds=g_c, nf_pad=c_pad,
            c_pad=c_pad, p_pad=p_pad, x_rows=x_rows)

    # Pad every tiled dim to an exact block multiple: zero channels/filters
    # contribute nothing to the accumulation, and extra bottom rows only
    # produce out-of-range outputs that are sliced away.  This keeps every
    # in-kernel row window in bounds (fold geometry stays exact).
    # Aligned layers skip the pads entirely (no copy).  Grouped layers are
    # exactly tiled by construction (blocks divide the per-group extents),
    # so only the bottom-row pad can apply.
    if groups > 1:
        nf_pad, c_pad = nf, c
        g_nfg = g_nf // groups            # nf folds per group
    else:
        nf_pad, c_pad = g_nf * nf_b, g_c * c_b
        g_nfg = g_nf
    p_pad = g_p * p_b
    x_rows = max(xp_, (p_pad - 1) * stride + r)

    # a fused residual rides along full-height, resident like the
    # accumulator — it doubles the WS footprint the spill check must price
    ws_resident = nf_b * p_pad * q * 4 * (2 if epi.residual else 1)
    if (dataflow == "weight_stationary"
            and ws_resident > WS_ACC_BYTES_LIMIT):
        # the full-height fp32 accumulator (+ resident residual) would not
        # fit VMEM: fall back to psum staging (or to the block-accumulator
        # OS kernel when an epilogue must flush in-kernel, and always for
        # grouped layers — the psum formulation predates groups) —
        # mirrored by the spill price in
        # ``core/engine.py:dataflow_traffic_bytes``
        dataflow = ("weight_stationary_psum"
                    if epi.identity and groups == 1
                    else "output_stationary")

    ws_like = dataflow in ("weight_stationary", "weight_stationary_psum")
    k = _rows_per_dot(dataflow, stride, r, s, c_b, nf_b, p_b, wph, q,
                      pooled)
    lanes = _round_up(k * wph, _LANES)      # a row group's dot width
    ix_x, ix_w, ix_vec = ((_ix_ws_x, _ix_ws_w, _ix_ws_vec) if ws_like
                          else (_ix_os_x, _ix_os_w, _ix_os_vec))
    inputs = [
        OperandSpec("x", (1, c_b, x_rows, stride * wph),
                    (n, c_pad, x_rows, stride * wph),
                    functools.partial(ix_x, nfg_folds=g_nfg, cg_folds=g_c)),
        OperandSpec("w", (rs, nf_b, c_b), (rs, nf_pad, c_pad // groups),
                    ix_w),
    ]
    folds = dict(nfg_folds=g_nfg, cg_folds=g_c, nf_pad=nf_pad, c_pad=c_pad,
                 p_pad=p_pad, x_rows=x_rows, rows_per_dot=k)

    if dataflow == "weight_stationary_psum":
        # out: one partial-sum fold per depth fold (paper Fig 5, staged in
        # HBM — the formulation the in-kernel reduction replaces)
        out = OperandSpec("out", (1, 1, nf_b, p_b, q),
                          (g_c, n, nf_pad, p_pad, q), _ix_psum_out)
        return common(
            dataflow="weight_stationary_psum", grid=(n, g_nf, g_c, g_p),
            grid_axes=("n", "nf", "c", "p"), reduction_axis=None,
            inner_sliced_axes=(), inputs=tuple(inputs), output=out,
            scratch=None, **folds)

    inputs.append(OperandSpec("vec", (nf_b, 3), (nf_pad, 3), ix_vec))
    p_o_pad = p_pad // 2 if pooled else p_pad
    if dataflow == "weight_stationary":
        if epi.residual:
            # resident like the output: constant along (c, p)
            inputs.append(OperandSpec("residual", (1, nf_b, p_pad, q),
                                      (n, nf_pad, p_pad, q), _ix_ws_res))
        out = OperandSpec("out", (1, nf_b, p_o_pad, q_o),
                          (n, nf_pad, p_o_pad, q_o), _ix_ws_out)
        # full-height accumulator: the paper's reserved-column partial
        # sums (int32 for int8 streams — same 4 bytes/elem footprint)
        return common(
            dataflow="weight_stationary", grid=(n, g_nf, g_c, g_p),
            grid_axes=("n", "nf", "c", "p"), reduction_axis=2,
            inner_sliced_axes=(3,), inputs=tuple(inputs), output=out,
            scratch=((p_pad // k, nf_b, lanes) if k > 1
                     else (nf_b, p_pad, q)), **folds)

    # output_stationary
    p_b_o = p_b // 2 if pooled else p_b
    if epi.residual:
        inputs.append(OperandSpec("residual", (1, nf_b, p_b, q),
                                  (n, nf_pad, p_pad, q), _ix_os_res))
    out = OperandSpec("out", (1, nf_b, p_b_o, q_o),
                      (n, nf_pad, p_o_pad, q_o), _ix_os_out)
    return common(
        dataflow="output_stationary", grid=(n, g_nf, g_p, g_c),
        grid_axes=("n", "nf", "p", "c"), reduction_axis=3,
        inner_sliced_axes=(), inputs=tuple(inputs), output=out,
        scratch=(p_b // k, nf_b, lanes) if k > 1 else (nf_b, p_b, q),
        **folds)


_DATAFLOW_TAGS = {"weight_stationary": "ws", "output_stationary": "os",
                  "depthwise": "dw", "weight_stationary_psum": "wspsum"}


def kernel_name(spec: FoldKernelSpec) -> str:
    """The launch's stable name, from its resolved dataflow and window
    geometry (``fold_ws_r3s3_st1``), with ``_k<rows_per_dot>`` where rows
    are packed (``fold_os_r3s3_st1_k4``): the Mosaic custom call's
    ``kernel_name``, by which a profiler trace finds the kernel."""
    name = (f"fold_{_DATAFLOW_TAGS[spec.dataflow]}_r{spec.r}s{spec.s}"
            f"_st{spec.stride}")
    return name + (f"_k{spec.rows_per_dot}" if spec.rows_per_dot > 1
                   else "")


def _pad_to(arr: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Zero-pad ``arr`` up to ``shape`` (no-op when already aligned)."""
    pads = tuple((0, t - d) for d, t in zip(arr.shape, shape))
    if any(hi for _, hi in pads):
        return jnp.pad(arr, pads)
    return arr


def _phase_split(x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Reorder the (already padded to ``stride * wph``) width of an NCHW
    input into ``stride`` contiguous phases: lane ``ph * wph + j`` holds
    column ``ph + stride * j``.  A strided conv's taps then read
    contiguous lanes (``_tap_lane``).  Identity at stride 1."""
    if stride == 1:
        return x
    n, c, h, w = x.shape
    return (x.reshape(n, c, h, w // stride, stride)
            .transpose(0, 1, 2, 4, 3).reshape(n, c, h, w))


def _tap_major(w: jnp.ndarray) -> jnp.ndarray:
    """OIHW weights -> (R*S, O, I): one 2-D filter-fold tile per tap."""
    o, i, r, s = w.shape
    return w.transpose(2, 3, 0, 1).reshape(r * s, o, i)


def conv2d_folded(x_padded: jnp.ndarray, w: jnp.ndarray, *,
                  stride: int = 1,
                  plan: Optional[ConvBlockPlan] = None,
                  dataflow: str = "weight_stationary",
                  interpret: Optional[bool] = None,
                  out_dtype=None,
                  bias: Optional[jnp.ndarray] = None,
                  epilogue: Optional[Epilogue] = None,
                  residual: Optional[jnp.ndarray] = None,
                  scale: Optional[jnp.ndarray] = None,
                  shift: Optional[jnp.ndarray] = None,
                  groups: int = 1) -> jnp.ndarray:
    """Run the fold-streamed conv kernel on a PRE-PADDED input.

    x_padded: (N, C, Xp, Yp)   w: (NF, C/groups, R, S)   -> (N, NF, P', Q')
    where (P', Q') = (P, Q) or (P//2, Q//2) when ``epilogue.pool`` fuses
    the 2x2/2 max-pool.

    ``plan`` may come from the engine's schedule cache and describe a
    *larger* geometry sharing this layer's filter-fold key; it is clamped
    to the actual dims here, which is what makes schedule reuse exact.
    ``interpret=None`` resolves via the engine's backend policy (real
    lowering on TPU, interpreter elsewhere).  ``epilogue`` (with ``bias``
    when ``epilogue.bias``, ``scale``/``shift`` — the folded batch-norm
    vectors — when ``epilogue.scale``, and ``residual`` — an (N, NF, P, Q)
    shortcut — when ``epilogue.residual``) is flushed in-kernel — see
    ``core/epilogue.py``.  ``groups > 1`` streams per-group depth folds
    (``dataflow="depthwise"`` selects the dedicated no-reduction kernel
    for the G == C == N_F case).

    An **int8 x** (with int8 ``w``) selects the quantized stream: depth
    folds accumulate in an int32 VMEM scratch and the output defaults to
    fp32 — the caller bakes the combined dequant into the scale/shift
    vectors (``core/quant.py:requant_affine``; ``kernels/ops.conv2d_int8``
    is the packaged entry point).  The legacy psum dataflow stages raw
    accumulator folds through HBM with no flush hook to dequantize at, so
    it rejects int8 (unreachable from the engine anyway: the requant
    epilogue is never identity, which psum requires).

    The launch requests exactly the VMEM its blocks hold
    (``FoldKernelSpec.vmem_bytes``, the figure the planner and foldlint's
    ``plan.vmem-overflow`` check use) plus headroom for in-kernel values.
    """
    n, c, xp_, yp_ = x_padded.shape
    nf, cw, r, s = w.shape
    assert c == cw * groups, (c, cw, groups)
    assert nf % groups == 0, (nf, groups)
    p = (xp_ - r) // stride + 1
    q = (yp_ - s) // stride + 1
    quantized = x_padded.dtype == jnp.int8
    if quantized:
        if w.dtype != jnp.int8:
            raise ValueError(f"int8 activations need int8 weights, got "
                             f"w dtype {w.dtype}")
        if dataflow == "weight_stationary_psum":
            raise ValueError("the legacy psum dataflow cannot stream int8 "
                             "(its HBM-staged partial sums have no flush "
                             "hook to apply the dequant scale at)")
        acc_dtype = jnp.int32
        out_dtype = out_dtype or jnp.float32
    else:
        acc_dtype = jnp.float32
        out_dtype = out_dtype or x_padded.dtype
    epi = epilogue or Epilogue()
    if epi.bias and bias is None:
        raise ValueError("epilogue.bias=True needs a bias vector")
    if epi.scale and (scale is None or shift is None):
        raise ValueError("epilogue.scale=True needs scale and shift "
                         "vectors")
    if epi.residual:
        if residual is None:
            raise ValueError("epilogue.residual=True needs a residual "
                             "tensor")
        if tuple(residual.shape) != (n, nf, p, q):
            raise ValueError(f"residual shape {tuple(residual.shape)} != "
                             f"conv output {(n, nf, p, q)}")
    if interpret is None:
        from repro.core.engine import pallas_interpret_default
        interpret = pallas_interpret_default()

    spec = fold_kernel_spec(tuple(x_padded.shape), tuple(w.shape),
                            stride=stride, plan=plan, dataflow=dataflow,
                            epilogue=epi, groups=groups)
    if quantized and spec.dataflow == "weight_stationary_psum":
        # the WS VMEM-spill fallback can land here only for an identity
        # epilogue — which an int8 stream never has (requant is an affine)
        raise ValueError("int8 weight_stationary spilled to psum staging, "
                         "which cannot dequantize; use output_stationary")

    arrays = {"w": _tap_major(w), "residual": residual}
    args = []
    for op in spec.inputs:
        if op.role == "vec":
            args.append(_vector_block(nf, op.array_shape[0],
                                      epi, bias, scale, shift))
        elif op.role == "x":
            args.append(_phase_split(_pad_to(x_padded, op.array_shape),
                                     stride))
        else:
            args.append(_pad_to(arrays[op.role], op.array_shape))
    kw = dict(r=r, s=s, stride=stride, wph=spec.wph, p_block=spec.p_block,
              q=q)
    if spec.dataflow == "depthwise":
        kern = functools.partial(_dw_kernel, epi=epi, acc_dtype=acc_dtype,
                                 **kw)
    elif spec.dataflow == "weight_stationary_psum":
        kern = functools.partial(_ws_psum_kernel, **kw)
    else:
        body = (_ws_kernel if spec.dataflow == "weight_stationary"
                else _os_kernel)
        kern = functools.partial(body, n_c=spec.cg_folds, epi=epi,
                                 acc_dtype=acc_dtype,
                                 rows_per_dot=spec.rows_per_dot, **kw)
    scratch = ([] if spec.scratch is None
               else [pltpu.VMEM(spec.scratch, acc_dtype)])
    stream_bytes = jnp.dtype(x_padded.dtype).itemsize
    out = pl.pallas_call(
        kern, grid=spec.grid, name=kernel_name(spec),
        in_specs=[op.block_spec() for op in spec.inputs],
        out_specs=spec.output.block_spec(),
        out_shape=jax.ShapeDtypeStruct(spec.output.array_shape, out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_request_bytes(
                spec.vmem_bytes(stream_bytes))),
        interpret=interpret,
    )(*args)
    if spec.dataflow == "weight_stationary_psum":
        # multi-depth reduce of the partial-sum folds, paid through HBM
        return out.sum(axis=0)[:, :nf, :p].astype(out_dtype)
    return out[:, :nf, :spec.p_valid, :spec.q_valid]
