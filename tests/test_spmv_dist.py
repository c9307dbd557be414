"""Distributed SpMV + CG: single-device in-process, multi-device subprocess.

The multi-device runs spawn a fresh interpreter with
``--xla_force_host_platform_device_count`` so this process keeps 1 device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro import spans
from repro.core import (build_spmv_plan, from_dist, make_cg, make_spmv,
                        to_dist)
from repro.sparse import (CSRMatrix, extruded_mesh_matrix, get_format,
                          random_spd_matrix)
from repro.sparse import csr as csr_mod
from repro.util import make_mesh_compat


def _mesh11():
    return make_mesh_compat((1, 1), ("node", "core"))


@pytest.mark.parametrize("mode", ["vector", "task", "balanced"])
def test_modes_agree_single_device(mode):
    A = extruded_mesh_matrix(50, 4, seed=0)
    x = np.random.default_rng(0).normal(size=A.n_rows)
    plan, layout = build_spmv_plan(A, 1, 1, mode=mode)
    y = from_dist(make_spmv(plan, _mesh11())(to_dist(x, layout, plan)),
                  layout, plan)
    np.testing.assert_allclose(y, A.matvec(x), rtol=2e-4, atol=1e-4)


def _lowered_text(program: str, layers: int) -> str:
    import jax

    from repro.solvers import make_resilient, make_solver
    A = extruded_mesh_matrix(30, layers, seed=0)
    plan, _ = build_spmv_plan(A, 1, 1)
    mesh = _mesh11()
    tol, mx = jnp.float32(1e-5), jnp.int32(10)
    if program == "spmv":
        return make_spmv(plan, mesh).lower(
            jnp.zeros(plan.x_shape)).as_text()
    if program == "solve":
        return make_solver(plan, mesh).jitted.lower(
            jnp.zeros(plan.cg_shape), tol, mx).as_text()
    rs = make_resilient(plan, mesh)
    b = jnp.zeros((1, 1, 2, plan.rc_pad))
    tols = jnp.full((2,), 1e-5, jnp.float32)
    state = rs.restart(b, tols, mx, b, jnp.zeros((2,), jnp.int32))
    jax.block_until_ready(state)
    return rs.chunk.lower(b, tols, mx, mx, *state).as_text()


@pytest.mark.parametrize("program", ["spmv", "solve", "chunk"])
def test_programs_take_the_operator_as_arguments(program):
    """The operator reaches every jitted program as arguments, never as
    closure constants: 8x the rows and nnz leave the lowered program the
    same size (constants would be inlined into its text)."""
    small, large = (len(_lowered_text(program, layers))
                    for layers in (4, 32))
    assert large < 1.02 * small, (small, large)


@pytest.mark.parametrize("dtype,expected", [
    (None, np.float32), (np.dtype(np.float16), np.float16),
    (jnp.bfloat16, jnp.bfloat16), (np.float64, np.float32)])
def test_host_array_keeps_the_requested_dtype(dtype, expected):
    """``host_array`` takes the dtype it is given, in canonical form (a
    numpy dtype instance is falsy, so it must not be tested for truth)."""
    from repro.util import host_array
    a = host_array(np.ones((2, 3), np.float64), dtype=dtype)
    assert isinstance(a, np.ndarray) and a.dtype == np.dtype(expected)


# --------------------------------------------------------------------- #
# the chunked pack against a plain per-row reference
# --------------------------------------------------------------------- #
def _ragged(n_rows, n_cols, seed):
    """Unsorted rows of 1-8 entries and one of 25.  Square: every row
    stores its diagonal twice (the first wins), and every 7th row holds
    nothing else; rectangular: every 7th row is empty."""
    rng = np.random.default_rng(seed)
    indptr, cols = [0], []
    for r in range(n_rows):
        c = [] if r % 7 == 3 else list(
            rng.integers(0, n_cols, 25 if r == 11 else rng.integers(1, 9)))
        if n_rows == n_cols:
            c += [r, r]
        rng.shuffle(c)
        cols += c
        indptr.append(len(cols))
    return CSRMatrix(np.array(indptr, np.int64), np.array(cols, np.int64),
                     rng.standard_normal(len(cols)), (n_rows, n_cols))


def _reference_plan(A, plan, layout):
    """The format's arrays, ``diag_a``, ``mask``, ``global_row_of`` and the
    ghost maps as a plain per-row loop builds them: each node's rows split
    by owned column, filled row-major, placed in their slots (transposed,
    for ELL), then cast to float32."""
    nb, cnb = layout["node_bounds"], layout["col_space"]["node_bounds"]
    shard = (plan.n_node, plan.n_core)
    blocks, ghosts, place = [], [], []
    for i in range(plan.n_node):
        clo, chi = cnb[i], cnb[i + 1]
        rows = []
        for g in range(nb[i], nb[i + 1]):
            ent = [(int(c), v) for c, v in zip(
                A.indices[A.indptr[g]:A.indptr[g + 1]],
                A.data[A.indptr[g]:A.indptr[g + 1]])]
            rows.append(([(c - clo, v) for c, v in ent if clo <= c < chi],
                         [(c, v) for c, v in ent if not clo <= c < chi]))
        ghost = sorted({c for _, offd in rows for c, _ in offd})
        blocks.append([(d, [(ghost.index(c), v) for c, v in o])
                       for d, o in rows])
        ghosts.append(np.array(ghost, dtype=np.int64))
        cb, lr = layout["core_bounds"][i], layout["row_space"]["lr"][i]
        place.append([(int(np.searchsorted(cb, r, side="right")) - 1,
                       int(lr[r])) for r in range(nb[i + 1] - nb[i])])
    fmt = get_format(plan.format)
    names = [fmt.fields[:2], fmt.fields[2:]] if fmt.name == "ell" else \
        [fmt.fields[:3], fmt.fields[3:]]
    ref = {}
    for p, part_names in enumerate(names):
        if fmt.name == "ell":
            w = max((len(row[p]) for rows in blocks for row in rows),
                    default=0)
            cols = np.zeros(shard + (w, plan.rc_pad), np.int32)
            vals = np.zeros(shard + (w, plan.rc_pad))
            for i, rows in enumerate(blocks):
                rm_cols = np.zeros((len(rows), w), np.int32)
                rm_vals = np.zeros((len(rows), w))
                for r, row in enumerate(rows):
                    for k, (c, v) in enumerate(row[p]):
                        rm_cols[r, k], rm_vals[r, k] = c, v
                for r, (c, q) in enumerate(place[i]):
                    cols[i, c, :, q], vals[i, c, :, q] = rm_cols[r], rm_vals[r]
            ref[part_names[0]] = cols
            ref[part_names[1]] = vals.astype(np.float32)
            continue
        C, bins = fmt.slice_height, {}
        for i, rows in enumerate(blocks):
            for c in range(plan.n_core):
                mine = [(q, r) for r, (cc, q) in enumerate(place[i]) if cc == c]
                width = [0] * -(-len(mine) // C)
                for q, r in mine:
                    width[q // C] = max(width[q // C], len(rows[r][p]))
                starts = np.concatenate([[0], np.cumsum(C * np.array(
                    width, dtype=np.int64))]).astype(np.int64)
                flat = np.zeros((3, starts[-1]))
                for q, r in mine:
                    for k, (col, v) in enumerate(rows[r][p]):
                        at = starts[q // C] + (q % C) * width[q // C] + k
                        flat[:, at] = v, col, q
                bins[i, c] = flat
        size = max(f.shape[1] for f in bins.values())
        pad = -(-size // fmt.nnz_align) * fmt.nnz_align
        out = np.zeros((3,) + shard + (pad,))
        for (i, c), flat in bins.items():
            out[:, i, c, :flat.shape[1]] = flat
        ref[part_names[0]] = out[0].astype(np.float32)
        ref[part_names[1]] = out[1].astype(np.int32)
        ref[part_names[2]] = out[2].astype(np.int32)
    diag_a = np.ones(shard + (plan.rc_pad,))
    mask = np.zeros(shard + (plan.rc_pad,))
    global_row_of = np.full(shard + (plan.rc_pad,), -1, np.int64)
    for i in range(plan.n_node):
        for r, (c, q) in enumerate(place[i]):
            g = nb[i] + r
            mask[i, c, q], global_row_of[i, c, q] = 1, g
            if A.n_rows == A.n_cols:
                row = A.indices[A.indptr[g]:A.indptr[g + 1]]
                diag_a[i, c, q] = A.data[A.indptr[g] + list(row).index(g)]
    ref["diag_a"] = diag_a.astype(np.float32)
    ref["mask"] = mask.astype(np.float32)
    return ref, ghosts, global_row_of


@pytest.mark.parametrize("shape", [(40, 40), (40, 57)])
@pytest.mark.parametrize("mesh", [(1, 1), (2, 1), (2, 2)])
@pytest.mark.parametrize("fmt", ["ell", "sell"])
def test_chunked_pack_matches_plain_reference(monkeypatch, fmt, mesh,
                                              shape):
    """Packed from the CSR in chunks of one nonempty row, on a pool of
    three threads, every plan array and layout map is what a plain
    per-row loop builds, bit for bit, and the same as a build in one chunk
    a core bin, inline; the walk counts its chunks and threads."""
    A = _ragged(*shape, seed=sum(shape) + mesh[0] * 3 + mesh[1])
    spans.reset()
    plan0, layout0 = build_spmv_plan(A, *mesh, format=fmt)
    t0 = spans.totals()
    monkeypatch.setattr(csr_mod, "_CHUNK_NNZ", 1)
    monkeypatch.setattr(csr_mod, "_POOL_MIN_NNZ", 0)
    monkeypatch.setattr(csr_mod, "_host_threads", lambda: 3)
    spans.reset()
    plan, layout = build_spmv_plan(A, *mesh, format=fmt)
    t = spans.totals()

    ref, ghosts, global_row_of = _reference_plan(A, plan, layout)
    got = dict(plan.fmt_data, diag_a=plan.diag_a, mask=plan.mask)
    assert sorted(got) == sorted(ref)
    for name, want in ref.items():
        assert got[name].dtype == want.dtype, name
        np.testing.assert_array_equal(got[name], want, err_msg=name)
    np.testing.assert_array_equal(layout["global_row_of"], global_row_of)
    for mine, want in zip(layout["halo"].ghost_cols, ghosts, strict=True):
        np.testing.assert_array_equal(mine, want)

    leaves0, tree0 = jax.tree_util.tree_flatten(plan0)
    leaves, tree = jax.tree_util.tree_flatten(plan)
    assert tree == tree0
    for a, b in zip(leaves, leaves0, strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    for key in ("global_row_of", "global_col_of", "pair_counts"):
        np.testing.assert_array_equal(layout[key], layout0[key])

    # one chunk per nonempty row (empty rows ride with a neighbour), so
    # chunk bounds fall inside every core bin
    bins = [(nb0 + cb[c], nb0 + cb[c + 1])
            for nb0, cb in zip(layout["node_bounds"], layout["core_bounds"])
            for c in range(mesh[1]) if cb[c + 1] > cb[c]]
    rn = A.row_nnz
    assert t["plan.pack.chunks"][0] == sum(
        max(1, int(np.count_nonzero(rn[lo:hi]))) for lo, hi in bins)
    assert t["plan.pack.threads"][0] == 3 and "plan.split" in t
    assert t0["plan.pack.chunks"][0] == len(bins)
    assert t0["plan.pack.threads"][0] == 1


def test_pallas_backend_matches_jnp():
    A = extruded_mesh_matrix(40, 4, seed=1)
    x = np.random.default_rng(1).normal(size=A.n_rows)
    plan, layout = build_spmv_plan(A, 1, 1, mode="balanced")
    mesh = _mesh11()
    y_j = from_dist(make_spmv(plan, mesh, backend="jnp")(to_dist(x, layout, plan)), layout, plan)
    y_p = from_dist(make_spmv(plan, mesh, backend="pallas")(to_dist(x, layout, plan)), layout, plan)
    np.testing.assert_allclose(y_p, y_j, rtol=1e-5, atol=1e-5)


def test_cg_solves_spd_system():
    A = random_spd_matrix(300, nnz_per_row=7, seed=5)
    b = np.random.default_rng(5).normal(size=300)
    plan, layout = build_spmv_plan(A, 1, 1, mode="balanced")
    solve = make_cg(plan, _mesh11())
    xd, iters, rel = solve(to_dist(b, layout, plan), tol=1e-7, maxiter=2000)
    x = from_dist(xd, layout, plan)
    resid = np.linalg.norm(A.matvec(x) - b) / np.linalg.norm(b)
    assert resid < 1e-4
    assert int(iters) < 2000


def test_jacobi_reduces_iterations():
    """Preconditioning sanity: Jacobi must not be slower than identity on an
    ill-scaled SPD matrix."""
    A = random_spd_matrix(200, nnz_per_row=5, seed=7)
    # scale rows/cols to create wild diagonal spread
    s = np.exp(np.random.default_rng(7).uniform(-3, 3, size=200))
    dense = (A.to_dense() * s).T * s
    from repro.sparse import CSRMatrix
    A2 = CSRMatrix.from_dense(dense)
    b = np.random.default_rng(8).normal(size=200)
    plan, layout = build_spmv_plan(A2, 1, 1, mode="task")
    mesh = _mesh11()
    solve = make_cg(plan, mesh)
    _, it_jac, _ = solve(to_dist(b, layout, plan), tol=1e-6, maxiter=4000)

    from repro.core.cg import cg_solve
    spmv = make_spmv(plan, mesh)
    ones = jnp.ones_like(plan.diag_a) * plan.mask
    _, it_id, _ = cg_solve(spmv, to_dist(b, layout, plan), ones, plan.mask,
                           jnp.asarray(1e-6, jnp.float32),
                           jnp.asarray(4000, jnp.int32))
    assert int(it_jac) <= int(it_id)


@pytest.mark.parametrize("n_node,n_core,mode", [
    (4, 2, "vector"),
    (4, 2, "task"),
    (4, 2, "balanced"),
    (2, 4, "balanced"),
    (8, 1, "task"),
])
def test_multidevice_spmv(n_node, n_core, mode):
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", str(n_node), "--n-core", str(n_core),
                        "--mode", mode])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_multidevice_cg():
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", "4", "--n-core", "2",
                        "--mode", "balanced", "--cg"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_multidevice_ring_transport():
    """Beyond-paper ring/neighbour halo transport must agree with the fused
    all_to_all VecScatter analogue."""
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", "4", "--n-core", "2",
                        "--mode", "balanced", "--transport", "ring"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_multidevice_ring_nonuniform_node_bounds():
    """Ring transport crossed with non-uniform node_bounds: the ppermute
    schedule must follow the two-level nnz node split on the graded
    matrix, not an assumed equal-rows block size."""
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", "4", "--n-core", "2",
                        "--mode", "balanced", "--transport", "ring",
                        "--node-partition", "nnz", "--matrix", "graded",
                        "--n-surface", "60", "--layers", "8"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_multidevice_ring_pallas_backend():
    """Ring transport crossed with the Pallas shard kernel — previously
    ring was only exercised with the jnp backend on uniform splits."""
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", "4", "--n-core", "2",
                        "--mode", "balanced", "--transport", "ring",
                        "--backend", "pallas",
                        "--n-surface", "40", "--layers", "4"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_multidevice_pallas_backend():
    r = run_subprocess(["-m", "repro.testing.dist_check",
                        "--n-node", "2", "--n-core", "2",
                        "--mode", "balanced", "--backend", "pallas",
                        "--n-surface", "40", "--layers", "4"])
    assert r.returncode == 0, r.stdout + r.stderr
