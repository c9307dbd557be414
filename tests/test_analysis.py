"""Static SPMD contract verifier (``repro.analysis``) — clean sweeps
pass, and every mutation class of a valid plan is flagged with the
right violation code.

The mutation tests are the analyzer's own conformance harness: start
from a *verified-clean* plan, apply one targeted corruption (duplicate
ghost writer, corrupted slot order, off-by-one partition bounds,
oversized index-stream entries), and require the exact code.  Hypothesis
(or the seeded fallback shim) drives *where* the corruption lands so the
checkers are exercised across nodes/slots, not at one hand-picked index.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from conftest import run_subprocess
from repro.analysis import (CODES, Report, Violation, check_kernel_streams,
                            check_plan, check_precond_static,
                            check_solver_static, check_spmv_static)
from repro.core.spmv import build_spmv_plan
from repro.core.transport import (FaultyTransport, available_transports,
                                  register_transport, unregister_transport)
from repro.solvers import available_preconds, available_solvers
from repro.sparse.formats import available_formats
from repro.sparse.mesh_gen import graded_extruded_mesh_matrix

N_NODE, N_CORE = 4, 2
_CACHE = {}


def _case(fmt="sell"):
    """(A, plan, layout) for one format — built once, mutated via
    dataclasses.replace (never in place)."""
    if fmt not in _CACHE:
        A = graded_extruded_mesh_matrix(32, 4, seed=0)
        plan, layout = build_spmv_plan(A, n_node=N_NODE, n_core=N_CORE,
                                       format=fmt)
        _CACHE[fmt] = (A, plan, layout)
    return _CACHE[fmt]


def _codes(report: Report) -> set:
    return set(report.summary())


# --------------------------------------------------------------------- #
# clean sweeps: every registered combo passes the static gate
# --------------------------------------------------------------------- #
def test_clean_plans_pass_all_layers():
    for fmt in available_formats():
        A, plan, layout = _case(fmt)
        rep = check_plan(plan, layout)
        assert not rep.errors, [str(v) for v in rep.errors]
        rep = check_kernel_streams(plan)
        assert not rep.errors, [str(v) for v in rep.errors]


def test_clean_spmv_every_transport_zero_allreduce():
    _, plan, _ = _case("ell")
    for tname in available_transports():
        rep = check_spmv_static(plan, tname)
        assert not rep.errors, (tname, [str(v) for v in rep.errors])


def test_clean_solver_reduction_contracts():
    from repro.testing.analyze import DEFAULT_SOLVER_OPTIONS
    A, plan, layout = _case("sell")
    for sname in available_solvers():
        for pname in available_preconds():
            rep = check_solver_static(
                plan, sname, pname, A=A, layout=layout,
                options=DEFAULT_SOLVER_OPTIONS.get(sname))
            assert not rep.errors, (sname, pname,
                                    [str(v) for v in rep.errors])


def test_clean_preconds_local_only():
    A, plan, layout = _case("ell")
    for pname in available_preconds():
        rep = check_precond_static(plan, pname, A=A, layout=layout)
        assert not rep.errors, (pname, [str(v) for v in rep.errors])


def test_verify_hook_accepts_clean_plan():
    A = graded_extruded_mesh_matrix(24, 3, seed=1)
    build_spmv_plan(A, n_node=2, n_core=2, format="ell", verify=True)


# --------------------------------------------------------------------- #
# mutations: each corruption class -> its violation code
# --------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(pick=st.integers(min_value=0, max_value=10_000))
def test_duplicate_ghost_writer_flagged(pick):
    _, plan, layout = _case("sell")
    recv = np.asarray(plan.recv_own).copy()
    real = np.argwhere(recv < plan.g_pad)    # rows (dst, core, src, k)
    # writers are counted per destination node: the second writer is
    # drawn from a's own node, among the nodes that receive two or more
    nodes, n_real = np.unique(real[:, 0], return_counts=True)
    real = real[np.isin(real[:, 0], nodes[n_real >= 2])]
    assert len(real) >= 2
    a = real[pick % len(real)]
    same = real[(real[:, 0] == a[0]) & (real != a).any(axis=1)]
    b = same[pick % len(same)]
    recv[tuple(b)] = recv[tuple(a)]          # second writer for a's slot
    mut = dataclasses.replace(plan, recv_own=jnp.asarray(recv))
    assert "P_GHOST_MULTI_WRITER" in _codes(check_plan(mut, layout))


@settings(max_examples=10, deadline=None)
@given(pick=st.integers(min_value=1, max_value=10_000))
def test_corrupted_slot_order_flagged(pick):
    _, plan, layout = _case("sell")
    xg = np.asarray(plan.x_gather).copy()
    node = pick % plan.n_node
    nl = int(np.asarray(plan.mask)[node].sum())
    row = 1 + (pick % (nl - 1))
    xg[node, :, row] = xg[node, :, 0]        # two rows -> same slot
    mut = dataclasses.replace(plan, x_gather=jnp.asarray(xg))
    assert "P_SLOT_PERM" in _codes(check_plan(mut, layout))


@settings(max_examples=10, deadline=None)
@given(pick=st.integers(min_value=0, max_value=10_000))
def test_node_bounds_off_by_one_flagged(pick):
    _, plan, layout = _case("ell")
    nb = np.asarray(layout["node_bounds"]).copy()
    nb[1 + pick % (plan.n_node - 1)] += 1 if pick % 2 else -1
    mut_layout = {**layout, "node_bounds": nb}
    assert "P_NODE_BOUNDS" in _codes(check_plan(plan, mut_layout))


@settings(max_examples=10, deadline=None)
@given(pick=st.integers(min_value=0, max_value=10_000))
def test_oversized_sell_slot_index_flagged(pick):
    _, plan, _ = _case("sell")
    fd = dict(plan.fmt_data)
    cols = np.asarray(fd["sell_ocols"]).copy()
    nz = np.argwhere(np.asarray(fd["sell_ovals"]) != 0)
    cols[tuple(nz[pick % len(nz)])] = plan.g_pad + 1 + pick % 7
    fd["sell_ocols"] = jnp.asarray(cols)
    mut = dataclasses.replace(plan, fmt_data=fd)
    assert "K_INDEX_OOB" in _codes(check_kernel_streams(mut))


@settings(max_examples=10, deadline=None)
@given(pick=st.integers(min_value=0, max_value=10_000))
def test_oversized_sell_row_slot_flagged(pick):
    _, plan, _ = _case("sell")
    fd = dict(plan.fmt_data)
    rows = np.asarray(fd["sell_drows"]).copy()
    rows.flat[pick % rows.size] = plan.rc_pad + pick % 3
    fd["sell_drows"] = jnp.asarray(rows)
    mut = dataclasses.replace(plan, fmt_data=fd)
    assert "K_ROW_OOB" in _codes(check_kernel_streams(mut))


def test_faulty_transport_caught_statically():
    """The corrupting transport is flagged from its *trace*, before any
    device program runs — as an instance and via the registry."""
    _, plan, _ = _case("ell")
    rep = check_spmv_static(plan, FaultyTransport())
    assert any(v.code == "J_PAYLOAD_TRANSFORM" for v in rep.errors)

    tr = register_transport(FaultyTransport(), overwrite=True)
    try:
        rep = check_spmv_static(plan, "faulty")
        assert any(v.code == "J_PAYLOAD_TRANSFORM" for v in rep.errors)
    finally:
        unregister_transport(tr.name)


def test_wrong_reduction_declaration_flagged():
    from repro.solvers.base import get_solver
    _, plan, _ = _case("ell")
    sol = get_solver("cg")
    old = sol.reductions_per_iter
    try:
        sol.reductions_per_iter = 3
        rep = check_solver_static(plan, "cg", "jacobi")
        assert any(v.code == "J_SOLVER_REDUCTIONS" for v in rep.errors)
    finally:
        sol.reductions_per_iter = old


# --------------------------------------------------------------------- #
# satellites: up-front name validation, closed code vocabulary, CLI
# --------------------------------------------------------------------- #
def test_make_solver_validates_names_before_any_work():
    from repro.solvers import make_solver
    _, plan, _ = _case("ell")
    with pytest.raises(ValueError) as e:
        make_solver(plan, None, solver="nope")
    assert "cg" in str(e.value)              # lists what IS registered
    with pytest.raises(ValueError) as e:
        make_solver(plan, None, precond="nope")
    assert "jacobi" in str(e.value)


def test_violation_vocabulary_is_closed():
    with pytest.raises(ValueError):
        Violation("NOT_A_CODE", "nope")
    v = Violation("P_SLOT_PERM", "msg", {"node": 1})
    assert v.layer == "plan" and v.severity == "error"
    assert all(sev in ("error", "warning") for _, sev, _ in CODES.values())


def test_analyze_cli_clean_and_faulty():
    import json
    r = run_subprocess(
        ["-m", "repro.testing.analyze", "--n-surface", "24",
         "--layers", "3", "--formats", "ell", "--transports", "a2a",
         "--solvers", "cg", "--preconds", "none"],
        device_count=N_NODE * N_CORE)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["errors"] == 0 and out["checks"] > 0

    r = run_subprocess(
        ["-m", "repro.testing.analyze", "--n-surface", "24",
         "--layers", "3", "--formats", "ell", "--solvers", "cg",
         "--preconds", "none", "--include-faulty"],
        device_count=N_NODE * N_CORE)
    assert r.returncode == 1, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert not out["ok"] and out["summary"].get("J_PAYLOAD_TRANSFORM")
