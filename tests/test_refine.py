"""Mixed-precision iterative refinement (``repro.solvers.refine``).

The combinator wraps any registry solver in an f64 host outer loop so
lossy-wire (bf16/int8) solves reach tolerances below the f32 floor.
Single-device checks run in-process; the 8-device acceptance runs
(``repro.testing.refine_check``) spawn a fresh interpreter and hold every
solver x wire-dtype combination against the numpy f64 host-CG oracle.
"""
import json
import os

import numpy as np
import pytest

from conftest import REPO, run_subprocess
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


#: the 2x2 refinement case, run in a process of 8 host devices: the P1
#: unit-cube Poisson operator of the benchmark's generator at 1000 DoF,
#: unpreconditioned CG under the float64 refinement, three seeded
#: right-hand sides, each answer against scipy's direct float64 solve
MESH_REFINE_CASE = """
import importlib.util, json, sys
import jax, numpy as np, scipy.sparse, scipy.sparse.linalg
from repro.core import build_spmv_plan
from repro.solvers import make_refine
from repro.sparse import CSRMatrix
spec = importlib.util.spec_from_file_location("unit_cube_p1", sys.argv[1])
gen = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gen)
indptr, indices, data = gen.build(target_dofs=1000)
n = len(indptr) - 1
A = CSRMatrix(indptr, indices, data, shape=(n, n))
plan, layout = build_spmv_plan(A, 2, 2)
mesh = jax.make_mesh((2, 2), ("node", "core"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2,
                     devices=jax.devices()[:4])
refine = make_refine(plan, mesh, solver="cg", precond="none", A=A,
                     layout=layout, maxiter_inner=10000, maxiter_static=10000)
A64 = scipy.sparse.csr_matrix((data, indices, indptr), shape=(n, n))
out = []
for seed in (1, 2, 3):
    b = np.random.default_rng(seed).standard_normal(n)
    res = refine(b, tol=1e-7)
    x_ref = scipy.sparse.linalg.spsolve(A64.tocsc(), b)
    out.append({"true_rel": float(np.linalg.norm(b - A64 @ res.x)
                                  / np.linalg.norm(b)),
                "x_rel": float(np.linalg.norm(res.x - x_ref)
                               / np.linalg.norm(x_ref)),
                "cycles": res.cycles, "devices": len(jax.devices())})
print(json.dumps({"n": n, "hs": plan.hs, "answers": out}))
"""


def test_mesh_refine_poisson_cube_vs_f64_reference():
    """``make_refine`` over unpreconditioned CG on a 2x2 mesh, as the
    benchmark's four-chip Poisson cell drives it: every answer meets a
    float64 true residual of 1e-7 and agrees with the float64 direct
    solution to 1e-6 relative."""
    gen = os.path.join(REPO, "bench", "generators", "unit_cube_p1.py")
    r = run_subprocess(["-c", MESH_REFINE_CASE, gen], device_count=8)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["n"] == 1000 and res["hs"] > 0     # a real node exchange
    for a in res["answers"]:
        assert a["devices"] == 8
        assert a["true_rel"] <= 1e-7, a
        assert a["x_rel"] <= 1e-6, a


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
