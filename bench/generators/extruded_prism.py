"""An extruded coastline mesh coupled as a prism P1 stencil plus the second
in-layer ring across each edge: the stand-in for Fluidity's pressure matrix.

The surface mesh (``operators.surface_mesh``) is extruded into ``layers``
layers of prisms; row ``v * layers + l`` is surface vertex ``v`` on layer
``l``.  It couples with

* every vertex of the prisms around it: its surface neighbours ``u`` on
  layers ``l - 1``, ``l`` and ``l + 1`` (weights ``w/2``, ``w``, ``w/2``,
  ``w`` drawn from [0.5, 1.5] per surface edge), and its own column on
  ``l - 1`` and ``l + 1`` (weight 1);
* the vertices that face it across each of its surface edges, on layer
  ``l`` (weights drawn from [0.05, 0.15] per pair).

An interior row of a vertex with six neighbours holds 21 + 6 = 27 entries.
The matrix is that weighted graph's Laplacian plus ``shift`` on the
diagonal: symmetric positive definite.  The rows are built directly in CSR
order from one template per surface vertex, so the Fig. 3 size is made in
seconds without sorting its entries.
"""
from __future__ import annotations

import numpy as np

from operators import surface_mesh

#: template entry kinds: a coupling at layer offset -1, 0 or +1, or the
#: diagonal
BELOW, SAME, ABOVE, DIAG = 0, 1, 2, 3


def build(n_surface: int, layers: int, seed: int = 0, shift: float = 1e-3
          ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    edges, across, n2d = surface_mesh(n_surface, seed)
    L = int(layers)
    w = np.random.default_rng(seed + 1).uniform(0.5, 1.5, size=len(edges))
    wa = np.random.default_rng(seed + 2).uniform(0.05, 0.15,
                                                 size=len(across))
    verts = np.arange(n2d, dtype=np.int64)

    # one template per surface vertex: (owner, column vertex, kind, weight)
    owner, col, kind, wt = [], [], [], []
    for i, j in ((0, 1), (1, 0)):
        for k, scale in ((BELOW, 0.5), (SAME, 1.0), (ABOVE, 0.5)):
            owner.append(edges[:, i])
            col.append(edges[:, j])
            kind.append(np.full(len(edges), k, np.int8))
            wt.append(scale * w)
        owner.append(across[:, i])
        col.append(across[:, j])
        kind.append(np.full(len(across), SAME, np.int8))
        wt.append(wa)
    for k in (BELOW, DIAG, ABOVE):
        owner.append(verts)
        col.append(verts)
        kind.append(np.full(n2d, k, np.int8))
        wt.append(np.ones(n2d) if k != DIAG else np.zeros(n2d))
    owner, col, kind, wt = (np.concatenate(a) for a in (owner, col, kind, wt))
    off = np.where(kind == DIAG, 0, kind.astype(np.int64) - 1)
    order = np.lexsort((off, col, owner))
    owner, col, kind, wt, off = (a[order] for a in (owner, col, kind, wt,
                                                    off))
    tlen = np.bincount(owner, minlength=n2d)
    tptr = np.concatenate([[0], np.cumsum(tlen)])
    n_below = np.bincount(owner[kind == BELOW], minlength=n2d)
    n_above = np.bincount(owner[kind == ABOVE], minlength=n2d)

    # each diagonal is its row's coupling weights plus the shift
    layer = np.arange(L, dtype=np.int64)
    sums = [np.bincount(owner, weights=np.where(kind == k, wt, 0.0),
                        minlength=n2d) for k in (BELOW, SAME, ABOVE)]
    diag = (sums[1][:, None] + sums[0][:, None] * (layer > 0)
            + sums[2][:, None] * (layer < L - 1) + shift)

    row_nnz = (tlen[:, None] - n_below[:, None] * (layer == 0)
               - n_above[:, None] * (layer == L - 1)).ravel()
    indptr = np.concatenate([[0], np.cumsum(row_nnz)])
    indices = np.empty(int(indptr[-1]), np.int64)
    data = np.empty(int(indptr[-1]), np.float64)

    # expand the templates over the layers as (vertices, layers, template)
    # blocks, one per template shape, a bounded number of vertices at once
    lv = layer[None, :, None]
    shapes = np.stack([tlen, n_below, n_above], axis=1)
    for g, nb, na in np.unique(shapes, axis=0):
        g, nb, na = int(g), int(nb), int(na)
        verts_g = np.flatnonzero((shapes == (g, nb, na)).all(axis=1))
        step = max(1, (1 << 23) // (L * g))
        for s in range(0, len(verts_g), step):
            vs = verts_g[s:s + step]
            t = tptr[vs][:, None] + np.arange(g)[None, :]
            kt = kind[t][:, None, :]
            keep = ~(((kt == BELOW) & (lv == 0))
                     | ((kt == ABOVE) & (lv == L - 1)))
            keep = np.broadcast_to(keep, (len(vs), L, g))
            cols = (col[t] * L + off[t])[:, None, :] + lv
            vals = np.where(kt == DIAG, diag[vs][:, :, None],
                            -wt[t][:, None, :])
            dst = indptr[vs * L][:, None] + np.arange(L * g - nb - na)[None, :]
            indices[dst] = cols[keep].reshape(len(vs), -1)
            data[dst] = vals[keep].reshape(len(vs), -1)
    return indptr, indices, data
