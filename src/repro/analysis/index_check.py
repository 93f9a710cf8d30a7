"""Symbolic index-map coverage and race analyzer.

A ``FoldKernelSpec`` (``kernels/conv2d_ws.py:fold_kernel_spec``) exposes a
kernel launch's grid and every operand's BlockSpec index map as data.
This module enumerates the grid x index-map product — no tracing, no
arrays — and proves the mapping discipline the paper's loop-nest
decomposition assumes:

  index.rank          an index map returns the wrong number of indices
  index.block-align   an operand's array shape is not an exact multiple
                      of its block (a partial edge tile would clamp)
  index.oob           a grid point addresses a block beyond the (padded)
                      array bounds
  index.rows-window   the in-kernel row window of the last P fold runs
                      past the padded input rows
  index.group-offset  a WS/OS input or weight block is not addressed by
                      the group of the current filter fold
  index.dw-offset     a depthwise input/weight block is not addressed by
                      the grid's channel fold
  index.write-race    two grid points alias the same output block while
                      differing on an axis that is neither the depth-fold
                      (reduction) axis nor a disjoint in-block sub-slice
                      axis — on TPU the second visit clobbers the first
  index.coverage      the set of output tiles written differs from the
                      exact tiling of the padded output (missed or
                      duplicated tiles)

Exactly-once output writes follow from ``write-race`` + ``coverage``:
every tile is visited, and revisits happen only along axes that
accumulate into (or sub-slice) the same resident block.
"""
from __future__ import annotations

import itertools
import math
from typing import Dict, Iterable, Iterator, Set, Tuple

from repro.analysis.report import Report
from repro.kernels.conv2d_ws import FoldKernelSpec, OperandSpec

__all__ = ["check_kernel_spec", "MAX_POINTS"]

# full enumeration cap; past it each grid axis is sampled at its
# boundary/middle strata (races found in a sample are still real — only
# the coverage proof needs exhaustiveness and is skipped)
MAX_POINTS = 200_000

GridPoint = Tuple[int, ...]


def _axis_samples(extent: int) -> Iterable[int]:
    if extent <= 6:
        return range(extent)
    return sorted({0, 1, extent // 2, extent - 2, extent - 1})


def _grid_points(grid: Tuple[int, ...]) -> Tuple[Iterator[GridPoint], bool]:
    total = math.prod(grid)
    if total <= MAX_POINTS:
        return itertools.product(*(range(g) for g in grid)), True
    return itertools.product(*(_axis_samples(g) for g in grid)), False


def _eval_map(op: OperandSpec, pt: GridPoint) -> Tuple[int, ...]:
    return tuple(int(i) for i in op.index_map(*pt))


def check_kernel_spec(spec: FoldKernelSpec, where: str = "kernel") -> Report:
    """Prove in-bounds reads, correct group offsets, write-race freedom,
    and exactly-once output coverage for one kernel launch."""
    rep = Report()
    axes = {name: i for i, name in enumerate(spec.grid_axes)}
    operands = (*spec.inputs, spec.output)

    # static block geometry first — a malformed operand poisons the rest
    for op in operands:
        loc = f"{where}:{op.role}"
        if len(op.block) != len(op.array_shape):
            rep.add("index.rank", loc,
                    f"block rank {len(op.block)} != array rank "
                    f"{len(op.array_shape)}")
            return rep
        for d, (b, a) in enumerate(zip(op.block, op.array_shape)):
            if b < 1 or a % b:
                rep.add("index.block-align", loc,
                        f"dim {d}: block {b} does not tile array extent "
                        f"{a} exactly — an edge tile would clamp and "
                        f"break the fold geometry")

    # the in-kernel row reads of the last P fold must stay inside the
    # padded rows: row0 + (p_block-1)*stride + R <= x_rows
    g_p = spec.grid[axes["p"]]
    rows_top = ((g_p - 1) * spec.p_block * spec.stride
                + (spec.p_block - 1) * spec.stride + spec.r)
    if rows_top > spec.x_rows:
        rep.add("index.rows-window", f"{where}:x",
                f"last P fold reads input rows up to {rows_top} but the "
                f"padded input has {spec.x_rows} rows")
    if not rep.ok:
        return rep

    points, exhaustive = _grid_points(spec.grid)
    allowed: Set[int] = set(spec.inner_sliced_axes)
    if spec.reduction_axis is not None:
        allowed.add(spec.reduction_axis)
    writers: Dict[Tuple[int, ...], GridPoint] = {}
    reported: Set[Tuple[str, str]] = set()   # (code, operand) dedupe

    def add_once(code: str, role: str, message: str) -> None:
        if (code, role) not in reported:
            reported.add((code, role))
            rep.add(code, f"{where}:{role}", message)

    dw = spec.dataflow == "depthwise"
    for pt in points:
        for op in operands:
            try:
                idx = _eval_map(op, pt)
            except TypeError:
                add_once("index.rank", op.role,
                         f"index map rejects the {len(spec.grid)}-d grid "
                         f"point {pt} (wrong arity)")
                return rep
            if len(idx) != len(op.block):
                add_once("index.rank", op.role,
                         f"index map returned {len(idx)} indices for a "
                         f"rank-{len(op.block)} block at grid {pt}")
                continue
            for d, (i, b, a) in enumerate(zip(idx, op.block,
                                              op.array_shape)):
                if i < 0 or (i + 1) * b > a:
                    add_once("index.oob", op.role,
                             f"grid {pt} -> block index {idx}: dim {d} "
                             f"addresses elements [{i * b}, {(i + 1) * b})"
                             f" of an extent-{a} array")
            # per-group offset discipline (paper: a depth fold streams
            # channels of the group its filter fold belongs to)
            if dw:
                cc = pt[axes["c"]]
                if op.role == "x" and idx[1] != cc:
                    add_once("index.dw-offset", op.role,
                             f"grid {pt}: depthwise input reads channel "
                             f"fold {idx[1]}, not the grid's fold {cc}")
                if op.role == "w" and idx[1] != cc:      # (R*S, C, 1)
                    add_once("index.dw-offset", op.role,
                             f"grid {pt}: depthwise weights read filter "
                             f"fold {idx[1]}, not the grid's fold {cc}")
            else:
                f, cc = pt[axes["nf"]], pt[axes["c"]]
                if op.role == "x":
                    want = (f // spec.nfg_folds) * spec.cg_folds + cc
                    if idx[1] != want:
                        add_once("index.group-offset", op.role,
                                 f"grid {pt}: input reads channel fold "
                                 f"{idx[1]} but filter fold {f} lives in "
                                 f"group {f // spec.nfg_folds} (want "
                                 f"fold {want})")
                # tap-major weights (R*S, N_F, C/G): every tap rides along
                if op.role == "w" and idx[1:] != (f, cc):
                    add_once("index.group-offset", op.role,
                             f"grid {pt}: weight block {idx[1:]} != the "
                             f"grid's (filter, depth) folds ({f}, {cc})")
        out_idx = _eval_map(spec.output, pt)
        first = writers.setdefault(out_idx, pt)
        if first is not pt:
            diff = {d for d in range(len(pt)) if pt[d] != first[d]}
            if not diff <= allowed:
                bad = sorted(diff - allowed)
                names = ", ".join(spec.grid_axes[d] for d in bad)
                add_once("index.write-race", "out",
                         f"grid points {first} and {pt} both write output "
                         f"block {out_idx} but differ on non-reduction "
                         f"axis ({names}): the later visit clobbers the "
                         f"earlier one")

    if exhaustive:
        tiles = tuple(a // b for a, b in zip(spec.output.array_shape,
                                             spec.output.block))
        expect = math.prod(tiles)
        if len(writers) != expect:
            missing = expect - len(writers)
            example = next((t for t in itertools.product(
                *(range(t) for t in tiles)) if t not in writers), None)
            rep.add("index.coverage", f"{where}:out",
                    f"{len(writers)} of {expect} output tiles written "
                    f"({missing} {'missed' if missing > 0 else 'extra'}"
                    f"{f', e.g. {example}' if example else ''}): the "
                    f"padded output is not tiled exactly once")
    return rep
