"""Preconditioner registry conformance: the multi-device harness sweep,
its sensitivity to a broken registrant, option validation ordering, and
the two-level iteration-scaling regression.

In-process tests cover the registry/validation surface; everything that
needs the 8-device mesh spawns ``repro.testing.precond_check`` (see
conftest), which sweeps **every registered** preconditioner against its
numpy ``host_apply`` oracle plus symmetry/definiteness/static-collective
checks — so registering a preconditioner that breaks conformance is a
test failure, not a runtime surprise.
"""
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import build_spmv_plan
from repro.solvers import (FaultyPrecond, TwoLevelPrecond,
                           available_preconds, get_precond,
                           make_solver, register_precond,
                           unregister_precond)
from repro.sparse import graded_extruded_mesh_matrix
from repro.util import make_mesh_compat


def _mesh11():
    return make_mesh_compat((1, 1), ("node", "core"))


def _square_case():
    A = graded_extruded_mesh_matrix(24, 4, seed=0)
    plan, layout = build_spmv_plan(A, 1, 1, format="ell")
    return A, plan, layout


# --------------------------------------------------------------------- #
# registry & option validation (fails fast, before autotune/compile)
# --------------------------------------------------------------------- #
def test_registry_ships_two_level():
    assert "two_level" in available_preconds()
    pre = get_precond("two_level")
    assert pre.local_only is False
    assert pre.reductions_per_apply == 0


def test_unknown_precond_option_lists_valid_names():
    with pytest.raises(ValueError, match=r"agg_size.*smoother"):
        get_precond("two_level").validate_options({"bogus": 1})
    with pytest.raises(ValueError, match=r"\(none\)"):
        get_precond("jacobi").validate_options({"bogus": 1})


def test_two_level_option_types_validated():
    pre = get_precond("two_level")
    with pytest.raises(ValueError, match="int >= 2"):
        pre.validate_options({"agg_size": 1})
    with pytest.raises(ValueError, match="int >= 2"):
        pre.validate_options({"agg_size": "16"})
    with pytest.raises(ValueError, match="registered local"):
        pre.validate_options({"smoother": "two_level"})
    with pytest.raises(ValueError, match="registered local"):
        pre.validate_options({"smoother": "ilu"})


def test_make_solver_validates_precond_options_before_autotune(
        monkeypatch):
    """A bad two_level option must raise the naming ValueError BEFORE
    transport='auto' spends seconds timing candidate SpMVs."""
    A, plan, layout = _square_case()

    def boom(*a, **k):
        raise AssertionError("autotune ran before option validation")

    monkeypatch.setattr("repro.core.transport.autotune_transport", boom)
    with pytest.raises(ValueError, match=r"agg_size.*smoother"):
        make_solver(plan, _mesh11(), solver="cg", precond="two_level",
                    transport="auto", A=A, layout=layout,
                    precond_options={"bogus": 1})


def test_two_level_requires_matrix_and_layout():
    A, plan, layout = _square_case()
    with pytest.raises(ValueError, match="host matrix and layout"):
        get_precond("two_level").bind(plan)


def test_two_level_rejects_rectangular_plans():
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(20, dtype=np.int64), 3)
    from repro.sparse.csr import CSRMatrix
    R = CSRMatrix.from_coo(rows, rng.integers(0, 50, rows.size),
                           np.ones(rows.size), (20, 50))
    plan, layout = build_spmv_plan(R, 1, 1)
    with pytest.raises(ValueError, match="square"):
        get_precond("two_level").bind(plan, layout=layout, A=R)


def test_register_unregister_round_trip():
    register_precond(FaultyPrecond())
    try:
        assert "faulty" in available_preconds()
        with pytest.raises(ValueError, match="already registered"):
            register_precond(FaultyPrecond())
    finally:
        unregister_precond("faulty")
    assert "faulty" not in available_preconds()


# --------------------------------------------------------------------- #
# host-side two-level algebra: Galerkin coarse operator & aggregation
# --------------------------------------------------------------------- #
def test_galerkin_coarse_operator_matches_dense_triple_product():
    A, _, _ = _square_case()
    agg_of, nc = TwoLevelPrecond._aggregates(A.n_rows, 16)
    R = np.zeros((nc, A.n_rows))
    R[agg_of, np.arange(A.n_rows)] = 1.0
    Ac_ref = R @ A.to_dense() @ R.T
    ainv = TwoLevelPrecond._galerkin_inverse(A, agg_of, nc)
    np.testing.assert_allclose(np.linalg.inv(ainv), Ac_ref,
                               rtol=1e-10, atol=1e-10)


def test_two_level_host_apply_is_spd_and_beats_smoother_in_cg():
    A, plan, layout = _square_case()
    pre = get_precond("two_level")
    M = pre.host_apply(plan, layout, A)
    rng = np.random.default_rng(5)
    V = rng.normal(size=(A.n_rows, 4))
    MV = np.stack([M(V[:, j]) for j in range(4)], axis=1)
    G = V.T @ MV                       # Gram matrix of M^-1
    np.testing.assert_allclose(G, G.T, rtol=1e-12, atol=1e-12)
    assert np.all(np.linalg.eigvalsh((G + G.T) / 2) > 0)


# --------------------------------------------------------------------- #
# multi-device conformance (subprocess, 8 fake devices)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", ("graded", "single", "halofree"))
def test_multidevice_precond_conformance(case):
    r = run_subprocess(["-m", "repro.testing.precond_check",
                        "--n-node", "4", "--n-core", "2",
                        "--case", case])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "BAD" not in r.stdout
    for name in available_preconds():
        assert f"PRECOND {name}" in r.stdout, (name, r.stdout)
    assert "cross=" in r.stdout          # two_level decomposition ran


def test_conformance_harness_catches_the_faulty_precond():
    """Registering a broken preconditioner must FAIL the sweep (rc 1):
    the harness proves conformance, it does not trust declarations."""
    r = run_subprocess(["-m", "repro.testing.precond_check",
                        "--n-node", "4", "--n-core", "2",
                        "--case", "graded", "--formats", "ell",
                        "--include-faulty"])
    assert r.returncode != 0, r.stdout + r.stderr
    faulty = [ln for ln in r.stdout.splitlines()
              if ln.startswith("PRECOND faulty")]
    # indefinite AND host-inconsistent, caught on both checks...
    assert faulty and "host=" in faulty[0] and "BAD" in faulty[0]
    assert "spd=" in faulty[0]
    # ...while every genuine preconditioner still passes in the sweep
    for ln in r.stdout.splitlines():
        if ln.startswith("PRECOND") and not ln.startswith("PRECOND faulty"):
            assert "BAD" not in ln, ln


# --------------------------------------------------------------------- #
# iteration scaling: one-level block-Jacobi degrades with mesh growth,
# two-level stays flat (DESIGN §15) — the reason the coarse grid exists
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_two_level_iteration_scaling_regression():
    r = run_subprocess(["-m", "repro.testing.precond_check",
                        "--n-node", "4", "--n-core", "2", "--scaling"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "BAD" not in r.stdout
    import json
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("SCALING ")][0]
    data = json.loads(line[len("SCALING "):])
    bj, tl = data["block_jacobi"]["iters"], data["two_level"]["iters"]
    # block_jacobi's count at tol 1e-6 sits on an f32 residual plateau
    # (see precond_check.scaling), so it is held to growth end to end
    assert bj[-1] > bj[0]                             # growth
    assert max(tl) / min(tl) <= 1.3                   # flat
    assert all(t < b for t, b in zip(tl, bj))         # and cheaper
