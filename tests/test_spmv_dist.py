"""Distributed SpMV + CG: single-device in-process, multi-device subprocess.

The multi-device runs spawn a fresh interpreter with
``--xla_force_host_platform_device_count`` so this process keeps 1 device.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import (build_spmv_plan, from_dist, make_cg, make_spmv,
                        to_dist)
from repro.sparse import extruded_mesh_matrix, random_spd_matrix
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
