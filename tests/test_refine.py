"""Mixed-precision iterative refinement (``repro.solvers.refine``).

The combinator wraps any registry solver in an f64 host outer loop so
lossy-wire (bf16/int8) solves reach tolerances below the f32 floor.
Single-device checks run in-process; the 8-device acceptance runs
(``repro.testing.refine_check``) spawn a fresh interpreter and hold every
solver x wire-dtype combination against the numpy f64 host-CG oracle.
"""
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import build_spmv_plan
from repro.solvers import RefineResult, make_refine, refine_solve
from repro.sparse import graded_extruded_mesh_matrix
from repro.util import make_mesh_compat


def test_make_refine_requires_host_matrix_and_layout():
    A = graded_extruded_mesh_matrix(20, 3, seed=0)
    plan, layout = build_spmv_plan(A, 1, 1)
    mesh = make_mesh_compat((1, 1), ("node", "core"))
    with pytest.raises(ValueError, match="needs A="):
        make_refine(plan, mesh, layout=layout)
    with pytest.raises(ValueError, match="needs A="):
        make_refine(plan, mesh, A=A)


def test_refine_rejects_batched_rhs():
    A = graded_extruded_mesh_matrix(20, 3, seed=0)
    B = np.random.default_rng(0).normal(size=(2, A.n_rows))
    with pytest.raises(ValueError, match="single global"):
        refine_solve(A, B)


def test_refine_single_device_below_f32_floor():
    """In-process, one device: refinement lands 2+ orders of magnitude
    below the f32 attainable-accuracy floor (~1e-4 on these problems)."""
    A = graded_extruded_mesh_matrix(24, 4, seed=0)
    b = np.random.default_rng(3).normal(size=A.n_rows)
    res = refine_solve(A, b, tol=1e-8, inner_tol=1e-5)
    assert isinstance(res, RefineResult)
    assert res.converged and res.rel <= 1e-8
    true_rel = float(np.linalg.norm(b - A.matvec(res.x))
                     / np.linalg.norm(b))
    assert true_rel <= 1e-7
    assert res.rel == true_rel      # the loop's residual, bit for bit
    assert res.cycles >= 2                    # one f32 solve can't get here
    assert res.history[-1] == (res.cycles, res.rel)
    assert res.solver == "cg" and res.wire_dtype == "f32"


def test_refine_exposes_the_compiled_inner_solver():
    A = graded_extruded_mesh_matrix(20, 3, seed=0)
    plan, layout = build_spmv_plan(A, 1, 1, wire_dtype="bf16")
    mesh = make_mesh_compat((1, 1), ("node", "core"))
    refine = make_refine(plan, mesh, A=A, layout=layout)
    assert refine.solve.wire_dtype == "bf16"  # follows the plan stamp
    assert refine.wire_dtype == "bf16" and refine.solver == "cg"


def test_multidevice_refine_cg_lossy_wire_vs_f64_oracle():
    """The headline acceptance: refine(inner=cg, wire_dtype=int8/bf16)
    converges to 1e-7 vs the f64 oracle on the 8-device mesh, and the
    codec-aware resilient guard runs an int8-wire chunked solve with
    ZERO rollbacks."""
    r = run_subprocess(["-m", "repro.testing.refine_check",
                        "--n-node", "4", "--n-core", "2",
                        "--solvers", "cg", "--wire-dtypes", "int8,bf16"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "BAD" not in r.stdout
    assert "ROLLBACKS 0" in r.stdout, r.stdout


@pytest.mark.slow
def test_multidevice_refine_all_solvers_all_wire_dtypes():
    r = run_subprocess(["-m", "repro.testing.refine_check",
                        "--n-node", "4", "--n-core", "2",
                        "--skip-resilient"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "BAD" not in r.stdout
    # every registered solver appears at every wire dtype
    for wd in ("f32", "bf16", "int8"):
        for name in ("cg", "pipelined_cg", "chebyshev"):
            assert f"REFINE {name} WIRE {wd}" in r.stdout, (name, wd)
