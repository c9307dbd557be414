"""Host spans and counters inside the program (``repro.spans``), and the
device scopes of the solve loop (``jax.named_scope``).

Spans are timed into an in-process table and written to the profiler's
host plane; the plan build, the operator placement and each refinement
cycle record their own.  In the compiled solve, every gathering
instruction of the Krylov loop names the ``krylov`` and ``spmv`` scopes
in its ``op_name``.
"""
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import build_spmv_plan, to_dist
from repro.solvers import make_refine, make_solver
from repro.sparse import graded_extruded_mesh_matrix
from repro.util import make_mesh_compat


@pytest.fixture
def table():
    spans.reset()
    yield
    spans.reset()


@pytest.fixture(scope="module")
def problem():
    A = graded_extruded_mesh_matrix(24, 4, seed=0)
    plan, layout = build_spmv_plan(A, 1, 1)
    mesh = make_mesh_compat((1, 1), ("node", "core"))
    b = np.random.default_rng(3).normal(size=A.n_rows)
    return A, plan, layout, mesh, b


def test_nested_spans_add_count_and_seconds(table):
    for _ in range(2):
        with spans.span("outer", step=1):
            with spans.span("inner"):
                time.sleep(0.002)
    t = spans.totals()
    assert t["outer"][0] == 2 and t["inner"][0] == 2
    assert t["outer"][1] >= t["inner"][1] >= 0.004
    assert set(t) == {"outer", "inner"}


def test_count_totals_and_reset(table):
    spans.count("things")
    spans.count("things", 4)
    t = spans.totals()
    assert t == {"things": (5, 0.0)}
    t["things"] = (0, 0.0)          # a copy: the table is not touched
    assert spans.totals() == {"things": (5, 0.0)}
    spans.reset()
    assert spans.totals() == {}


def test_profiler_trace_nests_refine_cycles_in_solve(problem, table,
                                                     tmp_path):
    from jax.profiler import ProfileData
    A, plan, layout, mesh, b = problem
    refine = make_refine(plan, mesh, A=A, layout=layout)
    refine(b, tol=1e-8)                         # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = refine(b, tol=1e-8)
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    events = {"refine.solve": [], "refine.cycle": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in events:
                        events[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    solves, cycles = events["refine.solve"], events["refine.cycle"]
    assert len(solves) == 1 and len(cycles) == res.cycles >= 1
    (s0, s1), = solves
    assert all(s0 <= c0 <= c1 <= s1 for c0, c1 in cycles)


def test_refine_counters_match_result(problem, table):
    A, plan, layout, mesh, b = problem
    refine = make_refine(plan, mesh, A=A, layout=layout)
    spans.reset()
    res = refine(b, tol=1e-8)
    t = spans.totals()
    assert t["refine.cycles"][0] == res.cycles >= 2
    assert t["refine.inner_iters"][0] == res.inner_iters
    assert t["refine.solve"][0] == 1
    for name in ("cycle", "to_device", "inner", "from_device", "residual"):
        assert t[f"refine.{name}"][0] == res.cycles
    assert t["refine.solve"][1] >= t["refine.cycle"][1] >= t[
        "refine.inner"][1] > 0


def test_plan_build_records_pack_once_within_build(problem, table):
    A = problem[0]
    build_spmv_plan(A, 1, 1)
    t = spans.totals()
    assert t["plan.build"][0] == 1 and t["plan.pack"][0] == 1
    assert 0 < t["plan.pack"][1] <= t["plan.build"][1]


def test_place_bytes_equals_operand_nbytes(problem, table):
    _, plan, _, mesh, _ = problem
    solve = make_solver(plan, mesh, solver="cg", precond="jacobi")
    t = spans.totals()
    assert t["solver.place"][0] == 1 and t["solver.place"][1] > 0
    assert t["place.bytes"][0] == sum(op.nbytes
                                      for op in solve.jitted.operands) > 0


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")


def _computations(text: str) -> dict:
    """``{computation: [(opcode, line)]}`` of a compiled program's text."""
    comps, current = {}, None
    for line in text.splitlines():
        if current is None:
            m = HEADER.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
        elif line.strip() == "}":
            current = None
        else:
            m = INSTRUCTION.match(line)
            if m:
                current.append((m.group(2), line))
    return comps


def _scopes(op_name: str) -> list:
    """The scope names of an ``op_name``, with transforms such as
    ``vmap(spmv)`` read as the scope they wrap."""
    out = []
    for part in op_name.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", part)):
            part = m.group(1)
        out.append(part)
    return out


def test_loop_gathers_carry_krylov_and_spmv_scopes(problem):
    _, plan, layout, mesh, b = problem
    solve = make_solver(plan, mesh, solver="cg", precond="jacobi")
    bd = to_dist(b.astype(np.float32), layout, plan)
    text = solve.jitted.lower(bd, jnp.float32(1e-6),
                              jnp.int32(10)).compile().as_text()
    comps = _computations(text)

    def gathers(comp):
        return any(op == "gather" or (op == "fusion" and any(
            gathers(c) for c in re.findall(r"calls=%?([\w.\-]+)", line)))
            for op, line in comps.get(comp, ()))

    bodies = [c for insts in comps.values() for op, line in insts
              if op == "while" for c in re.findall(r"body=%?([\w.\-]+)",
                                                   line)]
    found = [line for c in bodies for op, line in comps[c]
             if op == "gather" or (op == "fusion" and gathers(
                 re.search(r"calls=%?([\w.\-]+)", line).group(1)))]
    assert found, "no gathering instruction in the loop body"
    for line in found:
        scopes = _scopes(re.search(r'op_name="([^"]*)"', line).group(1))
        assert "krylov" in scopes and "spmv" in scopes, line
        assert scopes.index("krylov") < scopes.index("spmv")
