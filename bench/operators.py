"""The workload's input operator, made by the benchmark's own generators.

A configuration's ``operator`` block names a generator, a file
``generators/<name>.py`` whose ``build(**params)`` returns CSR
``(indptr, indices, data)`` with int64 indices and float64 values; the
rest of the block is its parameters.  A later change adds a generator as a
new file.  The operator is made anew in every run and never written to
disk, so the configuration file is the whole key.

This module also holds what the extruded generators share: the
pseudo-coastline surface mesh: a quasi-uniform point set, its Delaunay
triangulation and a reverse Cuthill-McKee numbering, written here so the
yardstick does not drift with the program's own generator
(``repro.sparse.mesh_gen``, whose uniformly random points give vertices
of up to 16 neighbours).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def build_operator(block: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The CSR operator of a configuration's ``operator`` block."""
    params = dict(block)
    name = params.pop("generator")
    path = os.path.join(HERE, "generators", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_generator_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(**params)


def _coastline_points(n_surface: int, seed: int) -> np.ndarray:
    """``n_surface`` points of a quasi-uniform mesh of a pseudo-coastline
    domain (an annulus-ish blob with a ragged boundary): a hexagonal
    lattice jittered by up to a fifth of its spacing, the points nearest
    the boundary dropped to leave exactly ``n_surface``, as a mesh
    generator's well-shaped triangles have nearly six neighbours a vertex."""
    rng = np.random.default_rng(seed)
    k = np.arange(1, 6)
    amp = rng.uniform(-0.08, 0.08, size=5)
    phase = rng.uniform(0, 2 * np.pi, size=5)

    def radius(theta):
        return 1.0 + (amp[None, :] * np.sin(np.outer(theta, k) + phase)).sum(-1)

    theta = np.linspace(0, 2 * np.pi, 4096, endpoint=False)
    area = 0.5 * np.mean(radius(theta) ** 2) * 2 * np.pi
    s = np.sqrt(area / (1.03 * n_surface) * 2 / np.sqrt(3))
    rows = int(np.ceil(2.4 / (s * np.sqrt(3) / 2))) + 1
    cols = int(np.ceil(2.4 / s)) + 2
    j, i = np.meshgrid(np.arange(rows), np.arange(cols), indexing="ij")
    pts = np.stack([-1.2 + s * (i + 0.5 * (j % 2)),
                    -1.2 + s * np.sqrt(3) / 2 * j], -1).reshape(-1, 2)
    pts = pts + rng.uniform(-0.2 * s, 0.2 * s, size=pts.shape)
    depth = np.linalg.norm(pts, axis=1) / radius(np.arctan2(pts[:, 1],
                                                            pts[:, 0]))
    keep = np.argsort(depth, kind="stable")[:n_surface]
    if depth[keep[-1]] > 1.0:
        raise ValueError(f"lattice too coarse for {n_surface} points")
    return pts[np.sort(keep)]


def surface_mesh(n_surface: int, seed: int = 0
                 ) -> tuple[np.ndarray, np.ndarray, int]:
    """Delaunay-triangulate the coastline cloud and renumber it by reverse
    Cuthill-McKee.  Returns ``(edges, across, n)``: the unique edges
    ``(i, j)``, ``i < j``, and the unique pairs ``(i, j)``, ``i < j``, of
    vertices facing each other across an interior edge (the far corners of
    the two triangles that share it), both sorted."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import reverse_cuthill_mckee
    from scipy.spatial import Delaunay

    pts = _coastline_points(n_surface, seed)
    tri = Delaunay(pts)
    # the triangulation of the convex hull bridges the coastline's bays
    # with long thin triangles: a mesh of the domain has none of them
    simp = tri.simplices
    length = np.linalg.norm(pts[simp] - pts[np.roll(simp, 1, axis=1)],
                            axis=-1).max(axis=1)
    inside = length <= 2.0 * np.median(length)
    nbr = np.where(tri.neighbors >= 0, tri.neighbors, 0)
    nbr = np.where((tri.neighbors >= 0) & inside[nbr], nbr, -1)
    nbr = np.where(inside[:, None], nbr, -1)
    kept = simp[inside]
    e = np.concatenate([kept[:, [0, 1]], kept[:, [1, 2]], kept[:, [0, 2]]],
                       axis=0)
    e.sort(axis=1)
    e = np.unique(e, axis=0)
    n = len(pts)
    adj = coo_matrix((np.ones(2 * len(e)),
                      (np.concatenate([e[:, 0], e[:, 1]]),
                       np.concatenate([e[:, 1], e[:, 0]]))),
                     shape=(n, n)).tocsr()
    perm = reverse_cuthill_mckee(adj, symmetric_mode=True)
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    e = inv[e]
    e.sort(axis=1)
    e = e[np.lexsort((e[:, 1], e[:, 0]))]

    # corner i of simplex s faces the corner of its neighbour across the
    # edge opposite i that is not on that edge
    s, i = np.nonzero(nbr >= 0)
    t = nbr[s, i]
    on_edge = (simp[t][:, :, None] == simp[s][:, None, :]).any(-1)
    far = simp[t][~on_edge]
    a = np.stack([inv[simp[s, i]], inv[far]], axis=1)
    a.sort(axis=1)
    a = np.unique(a, axis=0)
    # round a vertex of three neighbours, the far corner is a neighbour
    a = a[~np.isin(a[:, 0] * n + a[:, 1], e[:, 0] * n + e[:, 1])]
    return e, a, n
