"""Sparse substrate tests: CSR ops, diag/offdiag split, mesh generator."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.sparse import CSRMatrix, extruded_mesh_matrix, random_spd_matrix
from repro.sparse import csr as csr_mod
from repro.sparse.csr import ELLMatrix, ShardBlocks


def test_csr_roundtrip_dense():
    rng = np.random.default_rng(0)
    d = rng.normal(size=(20, 20)) * (rng.random((20, 20)) < 0.2)
    m = CSRMatrix.from_dense(d)
    np.testing.assert_allclose(m.to_dense(), d)


def test_csr_matvec_matches_dense():
    rng = np.random.default_rng(1)
    d = rng.normal(size=(30, 30)) * (rng.random((30, 30)) < 0.3)
    m = CSRMatrix.from_dense(d)
    x = rng.normal(size=30)
    np.testing.assert_allclose(m.matvec(x), d @ x, atol=1e-12)


def _walk(A, node_bounds, core_bounds):
    """Walk ``A``'s shard blocks, square column partition = row partition;
    returns the blocks, every chunk, and the walk's diagonal."""
    core_bounds = [np.asarray(cb) for cb in core_bounds]
    rows = [np.arange(cb[-1]) for cb in core_bounds]
    slots = [r - cb[np.searchsorted(cb, r, side="right") - 1]
             for r, cb in zip(rows, core_bounds)]
    blocks = ShardBlocks(A, node_bounds, node_bounds, core_bounds, slots)
    chunks = []
    diag = blocks.walk(chunks.append)
    return blocks, chunks, diag


def test_row_slice(monkeypatch):
    """The walk's chunks tile every node's rows once, inside core bins,
    cut by nonzeros; the diagonal read on the way is ``A.diagonal()``."""
    monkeypatch.setattr(csr_mod, "_CHUNK_NNZ", 40)
    A = random_spd_matrix(50, seed=0)
    blocks, chunks, diag = _walk(A, [0, 10, 30, 50],
                                 [[0, 4, 10], [0, 12, 20], [0, 7, 20]])
    np.testing.assert_array_equal(diag, A.diagonal())
    for i, cbs in enumerate(([0, 4, 10], [0, 12, 20], [0, 7, 20])):
        mine = sorted((ch.lo, ch.hi, ch.core) for ch in chunks
                      if ch.node == i)
        assert [lo for lo, _, _ in mine][0] == 0
        assert all(a[1] == b[0] for a, b in zip(mine, mine[1:]))
        assert mine[-1][1] == cbs[-1]
        for lo, hi, c in mine:
            assert cbs[c] <= lo < hi <= cbs[c + 1]
    assert len(chunks) > 6          # bounds inside the core bins


def test_col_split_reassembles(monkeypatch):
    """diag + offdiag (through the ghost map) must reproduce the block."""
    monkeypatch.setattr(csr_mod, "_CHUNK_NNZ", 25)
    A = random_spd_matrix(60, seed=2)
    lo, hi = 20, 40
    blocks, chunks, _ = _walk(A, [0, lo, hi, 60], [[0, 20], [0, 20],
                                                   [0, 20]])
    ghosts = blocks.ghost_cols[1]
    dense = np.zeros((hi - lo, A.n_cols))
    for ch in (ch for ch in chunks if ch.node == 1):
        for (rn, cols, vals), to_global in ((ch.diag, lambda c: c + lo),
                                            (ch.offd, lambda c: ghosts[c])):
            rows = np.repeat(np.arange(ch.lo, ch.hi), rn)
            np.add.at(dense, (rows, to_global(cols)), vals)
    np.testing.assert_allclose(dense, A.to_dense()[lo:hi])
    assert np.all(ghosts < A.n_cols)
    assert np.all((ghosts < lo) | (ghosts >= hi))


def test_extruded_mesh_is_spd_and_scales_with_layers():
    A1 = extruded_mesh_matrix(40, 3, seed=0)
    A2 = extruded_mesh_matrix(40, 6, seed=0)
    assert A2.n_rows == 2 * A1.n_rows  # quasi-linear workload scaling (Sec. 3)
    d = A1.to_dense()
    np.testing.assert_allclose(d, d.T, atol=1e-12)          # symmetric
    eigs = np.linalg.eigvalsh(d)
    assert eigs.min() > 0                                     # positive definite


def test_extruded_mesh_row_nnz_profile():
    A = extruded_mesh_matrix(60, 5, seed=1)
    rn = A.row_nnz
    assert 5 <= rn.mean() <= 30  # FEM-like stencil width (paper: ~27 nnz/row)
    assert rn.max() < 80


def test_ell_rejects_too_narrow():
    A = random_spd_matrix(20, seed=3)
    with pytest.raises(ValueError):
        ELLMatrix.from_csr(A, width=1)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 80), seed=st.integers(0, 100))
def test_csr_from_coo_sums_duplicates(n, seed):
    rng = np.random.default_rng(seed)
    k = rng.integers(1, 4 * n)
    rows = rng.integers(0, n, size=k)
    cols = rng.integers(0, n, size=k)
    vals = rng.normal(size=k)
    m = CSRMatrix.from_coo(rows, cols, vals, (n, n))
    dense = np.zeros((n, n))
    np.add.at(dense, (rows, cols), vals)
    np.testing.assert_allclose(m.to_dense(), dense, atol=1e-12)
