"""Mixed-precision iterative refinement: f64 outer loop over any inner solver.

The compressed wire formats (``repro.core.transport`` ``wire_dtype=``)
perturb every SpMV by up to the codec's relative bound, so a plain Krylov
solve over bf16/int8 wire stalls at a true-residual floor well above f32
round-off.  Classical iterative refinement recovers the lost accuracy: the
*outer* loop lives on the host in f64 and only ever evaluates exact
residuals, while the *inner* solve — the expensive, communication-bound
part — runs on device at loose tolerance over the cheap lossy wire::

    r = b - A x                (host, f64, exact matvec)
    solve  A d ~= r / ||r||    (device, f32 + lossy wire, tol = inner_tol)
    x <- x + ||r|| d           (host, f64 accumulate)

Convergence: one cycle contracts the error by the inner solve's *attained*
relative accuracy ``rho`` (its true-residual floor under the codec
perturbation — bounded by ``kappa(A) * rel_bound`` for a backward-stable
inner method), so after k cycles ``||r_k|| / ||b|| <= rho**k`` until the
f64 outer recompute's own round-off.  As long as the inner solve makes
*any* progress (``rho < 1`` — true for bf16/int8 wire on reasonably
conditioned systems), refinement converges geometrically to tolerances far
below the f32 floor, paying one host matvec per cycle.  Normalising the
residual to unit norm before each inner solve keeps late-cycle residuals
(~1e-7 and below) well inside f32 range.

``make_refine`` compiles the inner solver ONCE (tol/maxiter are traced
arguments of ``make_solver``'s program, so every cycle hits the jit
cache) and returns a host-driven ``refine(b, tol, max_cycles)``.  Each
call is span ``refine.solve`` and each cycle ``refine.cycle``, split into
``refine.to_device``, ``refine.inner``, ``refine.from_device`` and
``refine.residual`` (``repro.spans``); counters ``refine.cycles`` and
``refine.inner_iters`` add up what the calls return.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np
import scipy.sparse

from repro.solvers.base import make_solver
from repro.spans import count, span

__all__ = ["RefineResult", "make_refine", "refine_solve"]


@dataclasses.dataclass
class RefineResult:
    """What a refinement solve hands back (host numpy, global ordering)."""

    x: np.ndarray           # (n,) solution
    cycles: int             # outer refinement cycles run
    inner_iters: int        # total inner Krylov iterations across cycles
    rel: float              # final TRUE relative residual (f64 host)
    converged: bool
    history: list           # [(cycle, rel)] after each outer recompute
    solver: str             # inner solver / precond / transport / wire
    precond: str
    transport: str
    wire_dtype: str


def make_refine(plan, mesh, *, solver="cg", precond="jacobi",
                A=None, layout: dict | None = None,
                inner_tol: float = 1e-4, maxiter_inner: int = 10_000,
                axis_names: tuple[str, str] = ("node", "core"),
                backend: str = "jnp", transport=None,
                neighbor_offsets=None, wire_dtype: str | None = None,
                maxiter_static: int = 10_000,
                options: dict | None = None,
                precond_options: dict | None = None):
    """Wrap a registry solver in the f64 iterative-refinement outer loop.

    ``A`` (the host ``CSRMatrix``) and ``layout`` (the dict
    ``build_spmv_plan`` returned) are required: the outer loop recomputes
    r = b − Ax in f64 on the host every cycle — that exact residual is
    what lets a lossy-wire inner solve reach tolerances below its own
    floor.  ``inner_tol`` is the per-cycle inner target; it should sit
    just above the inner solve's attainable floor for the chosen
    ``wire_dtype`` (1e-4 is a good default for bf16/int8).

    Returns ``refine(b, tol=1e-7, max_cycles=40) -> RefineResult`` for a
    single global ``(n,)`` RHS.  The inner program is compiled once and
    shared across cycles; exposed as ``refine.solve`` (with the usual
    ``.solver``/``.transport``/``.wire_dtype`` stamps).
    """
    if A is None or layout is None:
        raise ValueError("make_refine needs A= (the host CSRMatrix) "
                         "and layout= for the f64 outer residual recompute")
    from repro.core.spmv import from_dist, to_dist
    from repro.core.transport import get_codec, plan_wire_dtype
    from repro.solvers.base import get_solver

    codec = get_codec(wire_dtype if wire_dtype is not None
                      else plan_wire_dtype(plan))
    if not codec.exact:
        # solver-specific stability defaults for a quantised SpMV (e.g.
        # pipelined CG's tighter residual-replacement period); explicit
        # user options win
        options = {**get_solver(solver).lossy_wire_options(),
                   **(options or {})}

    solve = make_solver(plan, mesh, solver=solver, precond=precond,
                        axis_names=axis_names, backend=backend,
                        transport=transport,
                        neighbor_offsets=neighbor_offsets,
                        wire_dtype=wire_dtype,
                        maxiter_static=maxiter_static,
                        A=A, layout=layout, options=options,
                        precond_options=precond_options)

    # the f64 residual's matvec, built once: scipy's CSR kernel sums each
    # row in stored order, as CSRMatrix.matvec does, with no nnz-sized
    # temporaries for the allocator to map and page in again every cycle
    A_host = scipy.sparse.csr_matrix((A.data, A.indices, A.indptr),
                                     shape=A.shape)
    calls = itertools.count(1)

    def refine(b, tol: float = 1e-7,
               max_cycles: int = 40) -> RefineResult:
        b = np.asarray(b, np.float64)
        if b.ndim != 1:
            raise ValueError("refine expects a single global (n,) RHS")
        with span("refine.solve", call=next(calls)):
            bnorm = max(float(np.linalg.norm(b)), 1e-300)
            x = np.zeros_like(b)
            r = b.copy()
            rel = float(np.linalg.norm(r)) / bnorm
            history: list = []
            inner_total = 0
            cycles = 0
            stalled = 0
            while rel > tol and cycles < max_cycles:
                cycles += 1
                with span("refine.cycle", cycle=cycles):
                    with span("refine.to_device"):
                        rn = max(float(np.linalg.norm(r)), 1e-300)
                        # unit-norm residual: late cycles push ||r|| toward
                        # 1e-7 and below, where a raw f32 inner RHS would
                        # underflow its dots
                        rd = to_dist(np.asarray(r / rn, np.float32),
                                     layout, plan)
                    with span("refine.inner"):
                        dd, it, _ = solve(rd, tol=inner_tol,
                                          maxiter=maxiter_inner)
                        inner_total += int(it)      # waits for the solve
                    with span("refine.from_device"):
                        d = np.asarray(from_dist(dd, layout, plan),
                                       np.float64)
                    with span("refine.residual"):
                        x = x + rn * d
                        r = b - np.asarray(A_host @ x, np.float64)
                        prev, rel = rel, float(np.linalg.norm(r)) / bnorm
                history.append((cycles, rel))
                # a cycle that fails to contract means the inner solve is
                # at its floor for this system — further cycles cannot help
                stalled = stalled + 1 if rel > 0.5 * prev else 0
                if stalled >= 3:
                    break
        count("refine.cycles", cycles)
        count("refine.inner_iters", inner_total)
        return RefineResult(
            x=x, cycles=cycles, inner_iters=inner_total, rel=rel,
            converged=bool(rel <= tol), history=history,
            solver=solve.solver, precond=solve.precond,
            transport=solve.transport, wire_dtype=solve.wire_dtype)

    refine.solve = solve
    refine.solver = solve.solver
    refine.precond = solve.precond
    refine.transport = solve.transport
    refine.wire_dtype = solve.wire_dtype
    return refine


def refine_solve(A, b, *, n_node: int = 1, n_core: int = 1,
                 mode: str = "balanced", node_partition=None,
                 format: str = "ell", solver="cg", precond="jacobi",
                 axis_names: tuple[str, str] = ("node", "core"),
                 backend: str = "jnp", transport=None,
                 wire_dtype: str = "f32",
                 inner_tol: float = 1e-4, maxiter_inner: int = 10_000,
                 tol: float = 1e-7, max_cycles: int = 40,
                 mesh=None, options: dict | None = None) -> RefineResult:
    """One-shot convenience: build plan + mesh, refine, return the result
    (mirrors ``resilient_solve``'s matrix-in entry)."""
    from repro.core.spmv import build_spmv_plan
    from repro.util import make_mesh_compat

    plan, layout = build_spmv_plan(
        A, n_node, n_core, mode=mode, node_partition=node_partition,
        format=format,
        transport=transport if isinstance(transport, str) else "a2a",
        wire_dtype=wire_dtype)
    if mesh is None:
        mesh = make_mesh_compat((n_node, n_core), axis_names)
    refine = make_refine(plan, mesh, solver=solver, precond=precond,
                         A=A, layout=layout, inner_tol=inner_tol,
                         maxiter_inner=maxiter_inner,
                         axis_names=axis_names, backend=backend,
                         transport=transport,
                         neighbor_offsets=layout["neighbor_offsets"],
                         options=options)
    return refine(b, tol=tol, max_cycles=max_cycles)
