"""Fold decomposition: Table 3 exact reproduction + geometric invariants."""
import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.core.folds import PEArray, decompose
from repro.core.loopnest import ConvLoopNest, synthetic_suite

# Table 3 of the paper, all 12 rows: (workload idx, PE dim) -> fold count
TABLE3 = {
    (0, 16): 256, (1, 16): 1024, (2, 16): 4096, (3, 16): 16384,
    (0, 32): 64, (1, 32): 256, (2, 32): 1024, (3, 32): 4096,
    (0, 64): 13, (1, 64): 52, (2, 64): 208, (3, 64): 824,
}


@pytest.mark.parametrize("key,want", sorted(TABLE3.items()))
def test_table3_fold_counts(key, want):
    idx, pe = key
    plan = decompose(synthetic_suite()[idx], PEArray(pe, pe))
    assert plan.total_filter_folds == want


def test_block_length_and_shifts_56x56():
    plan = decompose(synthetic_suite()[0], PEArray(16, 16))
    assert plan.image_folds_per_block == 56      # P*N, Table 3
    assert plan.shifts_per_fold == 56            # Q


@pytest.mark.parametrize("pe,lo,hi", [(16, 74, 76), (32, 74, 76),
                                      (64, 92, 94)])
def test_utilization_bands(pe, lo, hi):
    """Fig 7a: flat 75% on 16/32, >92% on 64x64."""
    for cv in synthetic_suite():
        u = decompose(cv, PEArray(pe, pe)).avg_utilization()
        assert lo <= u <= hi, (pe, str(cv), u)


def test_paper_worked_example():
    """Fig 3: 4 filters, C=4, 3x3 on a 4x24 array -> 2 folds of 2 channels."""
    cv = ConvLoopNest(n=1, nf=4, c=4, r=3, s=3, x=5, y=5, stride=1, pad=1)
    plan = decompose(cv, PEArray(4, 24))
    assert plan.slice_width == 12                # R*(S+1)
    assert plan.c_transformed == 48              # C*R*(S+1)
    assert plan.channels_per_fold == 2
    assert plan.fold_cols == 24
    assert plan.total_filter_folds == 2
    assert plan.image_folds_per_block == 5       # P*N
    folds = plan.image_folds()
    # paper Fig 3b is 1-indexed {3,2,1}; we index from 0 -> {2,1,0}
    assert folds[0].new_cols == (2, 1, 0)        # first fold: S fresh columns
    assert all(len(f.new_cols) == 1 for f in folds[1:])  # dedup: stride new


@given(nf=st.integers(1, 64), c=st.integers(1, 64),
       rs=st.sampled_from([1, 3, 5, 7]), x=st.integers(7, 40),
       pe=st.sampled_from([8, 16, 32]), stride=st.sampled_from([1, 2]))
@settings(max_examples=60, deadline=None)
def test_fold_invariants(nf, c, rs, x, pe, stride):
    cv = ConvLoopNest(n=1, nf=nf, c=c, r=rs, s=rs, x=x, y=x,
                      stride=stride, pad=rs // 2)
    if pe < rs + 1:
        return
    plan = decompose(cv, PEArray(pe, pe))
    # every filter and channel is covered by exactly one (row, col) split
    assert plan.n_row_splits == math.ceil(nf / pe)
    assert plan.total_filter_folds == plan.n_row_splits * plan.n_col_splits
    assert plan.total_image_blocks == plan.total_filter_folds  # eq (4)
    # utilization never exceeds 100 and is positive
    u = plan.avg_utilization()
    assert 0 < u <= 100.0
    # the dedup rule streams every padded input column at most once
    streamed = plan.streamed_cols_per_block()
    assert streamed <= cv.padded_y
    # folds jointly cover all P output columns
    folds = plan.image_folds()
    assert len(folds) == cv.p


@given(idx=st.integers(0, 3), pe=st.sampled_from([16, 32, 64]))
@settings(max_examples=12, deadline=None)
def test_fold_count_matches_closed_form(idx, pe):
    """eq (3) == enumeration length."""
    plan = decompose(synthetic_suite()[idx], PEArray(pe, pe))
    assert len(list(plan.filter_folds())) == plan.total_filter_folds
