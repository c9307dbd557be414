"""Rectangular SpMV plans: property tests against the scipy oracle,
square bit-identity against the committed golden fixture, multi-device
conformance (subprocess), and the up-front validation error paths.

The tentpole contract of PR 10: ``build_spmv_plan`` accepts any
rectangular CSR — row partitioning keys the output slot layout, a
separate column-space partition keys ownership and halo — and square
inputs with no column-space override reduce **bit-identically** to the
pre-refactor plans (``tests/golden_square_hashes.json`` was generated at
the pre-refactor HEAD).
"""
import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

from _hypothesis_compat import given, settings, st
from conftest import run_subprocess
from repro.core import build_spmv_plan, from_dist, make_spmv, to_dist
from repro.sparse.csr import CSRMatrix
from repro.util import make_mesh_compat

HERE = os.path.dirname(os.path.abspath(__file__))


def _mesh11():
    return make_mesh_compat((1, 1), ("node", "core"))


def _random_rect(n_rows: int, n_cols: int, seed: int,
                 per_row: int = 4) -> CSRMatrix:
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), per_row)
    cols = rng.integers(0, n_cols, size=rows.size)
    vals = rng.standard_normal(rows.size)
    if n_rows == n_cols:
        # square draws are operators the Jacobi setup must invert: give
        # them a nonzero diagonal (build_spmv_plan rightly refuses a zero)
        diag = np.arange(n_rows, dtype=np.int64)
        rows = np.concatenate([rows, diag])
        cols = np.concatenate([cols, diag])
        vals = np.concatenate([vals, np.full(n_rows, float(per_row + 1))])
    return CSRMatrix.from_coo(rows, cols, vals, (n_rows, n_cols))


def _scipy_matvec(A: CSRMatrix, x: np.ndarray) -> np.ndarray:
    S = sp.csr_matrix((A.data, A.indices, A.indptr), shape=A.shape)
    return S @ x


# --------------------------------------------------------------------- #
# property: random rectangular CSR -> make_spmv == scipy oracle
# (single-device in-process; the halo regimes run in the 8-device
# subprocess sweep below)
# --------------------------------------------------------------------- #
@settings(deadline=None, max_examples=12)
@given(n_rows=st.integers(min_value=3, max_value=60),
       n_cols=st.integers(min_value=3, max_value=60),
       seed=st.integers(min_value=0, max_value=2**16))
def test_rect_spmv_matches_scipy_oracle(n_rows, n_cols, seed):
    A = _random_rect(n_rows, n_cols, seed)
    for fmt in ("ell", "sell"):
        plan, layout = build_spmv_plan(A, 1, 1, format=fmt)
        assert plan.n == n_rows and plan.n_cols == n_cols
        x = np.random.default_rng(seed + 1).normal(size=n_cols)
        xd = to_dist(x, layout, plan, space="col")
        y = np.asarray(from_dist(make_spmv(plan, _mesh11())(xd),
                                 layout, plan, space="row"))
        ref = _scipy_matvec(A, x)
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)


def test_rect_layout_exports_both_spaces():
    A = _random_rect(24, 40, seed=0)
    plan, layout = build_spmv_plan(A, 1, 1, format="ell")
    for space, count in (("row_space", 24), ("col_space", 40)):
        s = layout[space]
        assert int(np.asarray(s["node_bounds"])[-1]) == count
        assert s["pad"] >= 1
    # square plans alias the column structures onto the row space
    # (square needs a nonzero diagonal — the Jacobi guard still applies)
    rng = np.random.default_rng(0)
    rows = np.concatenate([np.repeat(np.arange(24, dtype=np.int64), 3),
                           np.arange(24, dtype=np.int64)])
    cols = np.concatenate([rng.integers(0, 24, size=72),
                           np.arange(24, dtype=np.int64)])
    vals = np.concatenate([rng.standard_normal(72), np.full(24, 8.0)])
    B = CSRMatrix.from_coo(rows, cols, vals, (24, 24))
    planb, layoutb = build_spmv_plan(B, 1, 1, format="ell")
    assert planb.cc_pad == planb.rc_pad
    assert planb.mask_col is planb.mask


def test_rect_to_from_dist_round_trips_both_spaces():
    A = _random_rect(30, 18, seed=2)
    plan, layout = build_spmv_plan(A, 1, 1, format="ell")
    rng = np.random.default_rng(3)
    x, y = rng.normal(size=18), rng.normal(size=30)
    np.testing.assert_array_equal(
        np.asarray(from_dist(to_dist(x, layout, plan, space="col"),
                             layout, plan, space="col"), np.float64),
        x.astype(np.float32).astype(np.float64))
    np.testing.assert_array_equal(
        np.asarray(from_dist(to_dist(y, layout, plan, space="row"),
                             layout, plan, space="row"), np.float64),
        y.astype(np.float32).astype(np.float64))


# --------------------------------------------------------------------- #
# square bit-identity: the committed fixture was generated at the
# pre-refactor HEAD; the current tree must reproduce it exactly
# --------------------------------------------------------------------- #
def test_square_plans_bit_identical_to_prerefactor_golden():
    fixture = os.path.join(HERE, "golden_square_hashes.json")
    with open(fixture) as f:
        doc = json.load(f)
    assert len(doc["entries"]) >= 8   # ell+sell x 4 transports
    r = run_subprocess(["-m", "repro.testing.square_golden",
                        "--check", fixture])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FAIL" not in r.stdout, r.stdout


# --------------------------------------------------------------------- #
# multi-device conformance: tall/fat/aggregation shapes x ell+sell x
# every transport x uniform + non-uniform partitions vs A.matvec,
# plus transport cross-identity and the row/col-space pin round-trip
# --------------------------------------------------------------------- #
def test_multidevice_rect_conformance_sweep():
    r = run_subprocess(["-m", "repro.testing.rect_check",
                        "--n-node", "4", "--n-core", "2"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "BAD" not in r.stdout
    for kind in ("tall", "fat", "agg"):
        assert f"KIND {kind}" in r.stdout
    assert "PART nnz" in r.stdout and "PART rows" in r.stdout


# --------------------------------------------------------------------- #
# up-front validation: bad shapes fail at build time with a named
# error, never at pack/trace time inside shard_map
# --------------------------------------------------------------------- #
def test_empty_row_space_rejected():
    A = CSRMatrix(indptr=np.zeros(1, np.int64),
                  indices=np.zeros(0, np.int64),
                  data=np.zeros(0), shape=(0, 5))
    with pytest.raises(ValueError, match="empty row space"):
        build_spmv_plan(A, 1, 1)


def test_empty_column_space_rejected():
    A = CSRMatrix(indptr=np.zeros(4, np.int64),
                  indices=np.zeros(0, np.int64),
                  data=np.zeros(0), shape=(3, 0))
    with pytest.raises(ValueError, match="empty column space"):
        build_spmv_plan(A, 1, 1)


def test_out_of_range_column_index_rejected():
    A = CSRMatrix(indptr=np.array([0, 1, 1], np.int64),
                  indices=np.array([7], np.int64),
                  data=np.array([1.0]), shape=(2, 5))
    with pytest.raises(ValueError, match="column index out of range"):
        build_spmv_plan(A, 1, 1)


def test_inconsistent_row_space_pin_rejected():
    A = _random_rect(24, 40, seed=1)
    B = _random_rect(30, 40, seed=1)
    _, layout_b = build_spmv_plan(B, 1, 1)
    with pytest.raises(ValueError, match="row_space pin inconsistent"):
        build_spmv_plan(A, 1, 1, row_space=layout_b["row_space"])


def test_inconsistent_col_space_pin_rejected():
    A = _random_rect(24, 40, seed=1)
    B = _random_rect(24, 32, seed=1)
    _, layout_b = build_spmv_plan(B, 1, 1)
    with pytest.raises(ValueError, match="col_space pin inconsistent"):
        build_spmv_plan(A, 1, 1, col_space=layout_b["col_space"])


def test_too_small_pinned_pad_rejected():
    A = _random_rect(24, 40, seed=1)
    _, layout = build_spmv_plan(A, 1, 1)
    small = dict(layout["col_space"], pad=1)
    with pytest.raises(ValueError, match="smaller than the largest"):
        build_spmv_plan(A, 1, 1, col_space=small)
