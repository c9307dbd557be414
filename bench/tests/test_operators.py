"""The benchmark's generators: each direct build against a plain one, and
the configurations' stated sizes."""
import json
import os

import numpy as np
import pytest
from scipy.sparse import coo_matrix, csr_matrix

from operators import build_operator, surface_mesh

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _plain_prism(n_surface, layers, seed, shift):
    """The extruded prism stencil entry by entry, summed by scipy."""
    edges, across, n2d = surface_mesh(n_surface, seed)
    w = np.random.default_rng(seed + 1).uniform(0.5, 1.5, size=len(edges))
    wa = np.random.default_rng(seed + 2).uniform(0.05, 0.15,
                                                 size=len(across))
    L, n = layers, n2d * layers
    pairs = []                                  # (row, col, weight)
    for (u, v), wt in zip(edges, w):
        for lu in range(L):
            for dl, scale in ((-1, 0.5), (0, 1.0), (1, 0.5)):
                if 0 <= lu + dl < L:
                    pairs.append((u * L + lu, v * L + lu + dl, scale * wt))
                    pairs.append((v * L + lu + dl, u * L + lu, scale * wt))
    for (u, v), wt in zip(across, wa):
        for lu in range(L):
            pairs.append((u * L + lu, v * L + lu, wt))
            pairs.append((v * L + lu, u * L + lu, wt))
    for v in range(n2d):
        for lu in range(L - 1):
            pairs.append((v * L + lu, v * L + lu + 1, 1.0))
            pairs.append((v * L + lu + 1, v * L + lu, 1.0))
    r, c, wt = (np.array(a) for a in zip(*pairs))
    W = coo_matrix((wt, (r, c)), shape=(n, n)).tocsr()
    D = np.asarray(W.sum(axis=1)).ravel() + shift
    return (coo_matrix((D, (np.arange(n), np.arange(n))), shape=(n, n))
            - W).tocsr()


@pytest.mark.parametrize("n_surface,layers,seed", [(60, 1, 0), (60, 2, 3),
                                                   (400, 5, 0)])
def test_prism_direct_build_matches_plain(n_surface, layers, seed):
    indptr, indices, data = build_operator(
        {"generator": "extruded_prism", "n_surface": n_surface,
         "layers": layers, "seed": seed, "shift": 1e-3})
    A = _plain_prism(n_surface, layers, seed, 1e-3)
    assert indptr.dtype == indices.dtype == np.int64
    assert np.array_equal(indptr, A.indptr)
    assert np.array_equal(indices, A.indices)
    assert np.allclose(data, A.data, rtol=1e-14, atol=0)
    n = len(indptr) - 1
    B = csr_matrix((data, indices, indptr), shape=(n, n))
    assert abs(B - B.T).max() == 0
    assert np.linalg.eigvalsh(B.toarray()).min() > 0


def test_fig3_stated_size():
    """Rows, nonzeros and the widest row of ``configs/fig3-13.5M.json``,
    counted from the surface mesh (the full operator is made on the chip
    host)."""
    cfg = _config("fig3-13.5M")
    op = cfg["operator"]
    edges, across, n2d = surface_mesh(op["n_surface"], op["seed"])
    deg = np.bincount(edges.ravel(), minlength=n2d)
    acr = np.bincount(across.ravel(), minlength=n2d)
    interior = 3 * (deg + 1) + acr          # layers 1 .. L-2
    L = op["layers"]
    assert n2d * L == cfg["rows"]
    assert int(interior.sum()) * L - 2 * int((deg + 1).sum()) == cfg["nnz"]
    assert int(interior.max()) == cfg["widest_row"]
    assert round(cfg["nnz"] / cfg["rows"], 2) == cfg["nnz_per_row"]


def _plain_p1(nc):
    """The unit cube's P1 stiffness, one tetrahedron at a time, with
    the Dirichlet rows and columns zeroed and 1 on their diagonal."""
    from generators_unit_cube import TETS, element_matrices
    nx, ny, nz = nc
    K = element_matrices((1 / nx, 1 / ny, 1 / nz))
    idx = lambda x, y, z: (z * (ny + 1) + y) * (nx + 1) + x  # noqa: E731
    n = (nx + 1) * (ny + 1) * (nz + 1)
    A = np.zeros((n, n))
    for z in range(nz):
        for y in range(ny):
            for x in range(nx):
                corner = [idx(x + (c & 1), y + ((c >> 1) & 1),
                              z + ((c >> 2) & 1)) for c in range(8)]
                for t, tet in enumerate(TETS):
                    v = [corner[i] for i in tet]
                    A[np.ix_(v, v)] += K[t]
    bc = np.zeros(n, bool)
    for z in range(nz + 1):
        for y in range(ny + 1):
            for x in range(nx + 1):
                bc[idx(x, y, z)] = (x in (0, nx) or y in (0, ny)
                                    or z in (0, nz))
    A[bc, :] = 0
    A[:, bc] = 0
    A[bc, bc] = 1
    return A


@pytest.fixture
def generators_unit_cube(monkeypatch):
    import importlib.util
    import sys
    spec = importlib.util.spec_from_file_location(
        "generators_unit_cube", os.path.join(HERE, "generators",
                                             "unit_cube_p1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setitem(sys.modules, "generators_unit_cube", mod)
    return mod


@pytest.mark.parametrize("target", [8, 30, 200])
def test_unit_cube_matches_plain_assembly(generators_unit_cube, target):
    nc = generators_unit_cube.cells_per_direction(target)
    indptr, indices, data = build_operator(
        {"generator": "unit_cube_p1", "target_dofs": target})
    n = len(indptr) - 1
    A = csr_matrix((data, indices, indptr), shape=(n, n))
    assert np.allclose(A.toarray(), _plain_p1(nc), rtol=0, atol=1e-14)
    assert abs(A - A.T).max() == 0
    assert np.linalg.eigvalsh(A.toarray()).min() > 0


def test_unit_cube_source_mesh(generators_unit_cube):
    """``create_mesh`` of the source grows one direction at a time."""
    f = generators_unit_cube.cells_per_direction
    assert f(200000) == (58, 58, 57)
    assert f(8) == (1, 1, 1) and f(9) == (2, 1, 1) and f(13) == (2, 2, 1)


def test_poisson_stated_size():
    cfg = _config("poisson-200k")
    indptr, indices, data = build_operator(cfg["operator"])
    row = np.diff(indptr)
    assert len(row) == cfg["rows"] and len(indices) == cfg["nnz"]
    assert row.max() == cfg["widest_row"] == 15
    nx, ny, nz = cfg["cells_per_direction"]
    assert len(row) == (nx + 1) * (ny + 1) * (nz + 1)
    # an interior vertex: 15 entries, and its row sums to zero (-Laplace)
    v = ((nz // 2) * (ny + 1) + ny // 2) * (nx + 1) + nx // 2
    s = slice(indptr[v], indptr[v + 1])
    assert row[v] == 15 and abs(data[s].sum()) < 1e-12
