"""The P1 Lagrange stiffness matrix of -Laplace on DOLFINx's unit cube,
with homogeneous Dirichlet conditions on the whole boundary.

As ``Problem8_HPC/poisson.py`` of the FEniCSx course builds it:

* the mesh is ``create_unit_cube(Nc[0], Nc[1], Nc[2])``, with ``Nc`` grown
  one direction at a time until ``prod(Nc + 1) >= target_dofs`` (its
  ``create_mesh``);
* each hexahedral cell is split into DOLFINx's six tetrahedra around the
  diagonal from its first to its last vertex;
* ``a = inner(grad(u), grad(v)) * dx`` is assembled from exact element
  matrices;
* the couplings along the cells' face diagonals cancel exactly (the
  tetrahedra's edges along the axes meet at right angles) and are kept as
  stored zeros, as assembly keeps them;
* a boundary vertex's row and column keep their entries as stored zeros
  and its diagonal is 1, as ``assemble_matrix(a, bcs)`` leaves them.

Vertex ``(ix, iy, iz)`` is row ``(iz * (Nc[1] + 1) + iy) * (Nc[0] + 1) +
ix``, the mesh's own lexicographic numbering: a banded order, where
DOLFINx reorders its degrees of freedom by a bandwidth-reducing graph
ordering.  An interior row holds 15 entries.
"""
from __future__ import annotations

import numpy as np

#: DOLFINx's split of a hexahedron with corners v0..v7 (v1 = v0 + x,
#: v2 = v0 + y, v4 = v0 + z) into six tetrahedra
TETS = ((0, 1, 3, 7), (0, 1, 7, 5), (0, 5, 7, 4), (0, 3, 2, 7),
        (0, 6, 4, 7), (0, 2, 6, 7))


def cells_per_direction(target_dofs: int) -> tuple[int, int, int]:
    """``create_mesh``'s cell counts for ``target_dofs`` vertices."""
    nc = [1, 1, 1]
    while np.prod(np.add(nc, 1)) < target_dofs:
        for i in range(3):
            nc[i] += 1
            if np.prod(np.add(nc, 1)) >= target_dofs:
                break
    return tuple(nc)


def element_matrices(h: tuple[float, float, float]) -> np.ndarray:
    """``(6, 4, 4)`` P1 stiffness matrices of the six tetrahedra of a cell
    with edges ``h``: ``vol * G G^T`` with ``G`` the gradients of the
    barycentric coordinates."""
    corner = np.array([[(c >> 0) & 1, (c >> 1) & 1, (c >> 2) & 1]
                       for c in range(8)], np.float64) * np.asarray(h)
    out = np.empty((len(TETS), 4, 4))
    for t, tet in enumerate(TETS):
        X = corner[list(tet)]
        M = np.hstack([np.ones((4, 1)), X])
        G = np.linalg.inv(M)[1:].T                  # (4 vertices, 3)
        vol = abs(np.linalg.det(M)) / 6.0
        out[t] = vol * G @ G.T
    return out


def build(target_dofs: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    from scipy.sparse import coo_matrix

    nx, ny, nz = cells_per_direction(target_dofs)
    sx, sy = nx + 1, (nx + 1) * (ny + 1)
    n = sy * (nz + 1)
    ix, iy, iz = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    v0 = (iz * sy + iy * sx + ix).ravel().astype(np.int64)
    corner = np.array([(c & 1) + ((c >> 1) & 1) * sx + ((c >> 2) & 1) * sy
                       for c in range(8)], np.int64)
    K = element_matrices((1.0 / nx, 1.0 / ny, 1.0 / nz))
    tets = np.asarray(TETS)
    verts = v0[:, None, None] + corner[tets][None]          # (cells, 6, 4)
    rows = np.broadcast_to(verts[..., :, None], verts.shape + (4,)).ravel()
    cols = np.broadcast_to(verts[..., None, :], verts.shape + (4,)).ravel()
    vals = np.broadcast_to(K[None], verts.shape + (4,)).ravel()
    A = coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
    A.sum_duplicates()
    A.sort_indices()
    # the face diagonals' couplings cancel to round-off: they are zero
    A.data[np.abs(A.data) < 1e-12 * np.abs(A.data).max()] = 0.0

    p = np.arange(n)
    x, y, z = p % sx, (p // sx) % (ny + 1), p // sy
    bc = (x == 0) | (x == nx) | (y == 0) | (y == ny) | (z == 0) | (z == nz)
    row = np.repeat(p, np.diff(A.indptr))
    data = A.data.copy()
    data[bc[row] | bc[A.indices]] = 0.0
    data[bc[row] & (A.indices == row)] = 1.0
    # the sums of an entry and its mirror may differ in the last bit: take
    # their mean, so the matrix is symmetric to the bit
    mirror = np.lexsort((row, A.indices))
    data = 0.5 * (data + data[mirror])
    return (A.indptr.astype(np.int64), A.indices.astype(np.int64), data)
