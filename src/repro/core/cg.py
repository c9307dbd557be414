"""Distributed Conjugate Gradient with Jacobi preconditioning.

The paper's benchmark (Sec. 3): pressure matrices "solved using the Conjugate
Gradient method with a Jacobi preconditioner and the number of iterations was
limited to 10,000".  SpMV dominates the iteration cost; the vector updates
and reductions run as plain jnp ops on the distributed "CG layout"
(n_node, n_core, rc_pad) — XLA inserts the cross-shard psums for the dot
products automatically, which is exactly PETSc's ``VecDot``/``VecAXPY``
split between local work and a tiny ``MPI_Allreduce``.

This module keeps the *unfused* baseline solver (``cg_solve`` re-enters the
sharded SpMV every iteration — the per-iteration synchronisation cost the
fused solvers remove) plus the historical ``make_cg`` entry point.  The
fused, registry-based solvers live in ``repro.solvers``; ``jacobi_inverse``
moved to ``repro.solvers.precond`` and is re-exported here for
compatibility.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.spmv import SpMVPlan, make_spmv
from repro.solvers.base import local_dot
# compat re-export: moved into the solver subsystem
from repro.solvers.precond import jacobi_inverse
from repro.util import BoundJit, place_operands

__all__ = ["cg_solve", "make_cg", "jacobi_inverse"]


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Full-array f32 dot on CG-layout vectors (any shape) -> scalar."""
    return local_dot(a.reshape(-1), b.reshape(-1))


@partial(jax.jit, static_argnames=("spmv", "maxiter_static"))
def cg_solve(spmv: Callable, b: jax.Array, m_inv: jax.Array,
             mask: jax.Array, tol: jax.Array,
             maxiter: jax.Array, maxiter_static: int = 10_000):
    """Preconditioned CG.  All vectors live in CG layout.

    Returns (x, iters, rel_residual).  ``maxiter_static`` bounds the
    while_loop trip count for the compiler; ``maxiter`` is the dynamic cap
    (paper: 10,000).
    """
    b = b * mask
    bnorm = jnp.sqrt(_dot(b, b))
    tol2 = (tol * jnp.maximum(bnorm, 1e-30)) ** 2

    x0 = jnp.zeros_like(b)
    r0 = b
    z0 = m_inv * r0
    p0 = z0
    rz0 = _dot(r0, z0)
    rr0 = _dot(r0, r0)

    def cond(state):
        k, _, _, _, _, rr = state
        return (k < jnp.minimum(maxiter, maxiter_static)) & (rr > tol2)

    def body(state):
        k, x, r, p, rz, _ = state
        ap = spmv(p)
        alpha = rz / _dot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = m_inv * r
        rz_new = _dot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        return (k + 1, x, r, p, rz_new, _dot(r, r))

    state = (jnp.asarray(0, jnp.int32), x0, r0, p0, rz0, rr0)
    k, x, r, p, rz, rr = jax.lax.while_loop(cond, body, state)
    rel = jnp.sqrt(rr) / jnp.maximum(bnorm, 1e-30)
    return x, k, rel


def make_cg(plan: SpMVPlan, mesh, axis_names=("node", "core"),
            backend: str = "jnp", maxiter_static: int = 10_000,
            fused: bool = False, transport: str | None = None,
            neighbor_offsets=None):
    """Bundle a plan + mesh into ``solve(b, tol=..., maxiter=...)``.

    ``fused=True`` returns the fully-sharded solver instead (the whole CG
    ``while_loop`` inside one shard_map region — the registry ``cg`` solver
    with the ``jacobi`` preconditioner; see ``repro.solvers.make_solver``
    for other solvers, preconditioners and batched RHS) — same return
    contract.
    """
    if fused:
        from repro.solvers.base import make_solver
        return make_solver(plan, mesh, solver="cg", precond="jacobi",
                           axis_names=axis_names, backend=backend,
                           transport=transport,
                           neighbor_offsets=neighbor_offsets,
                           maxiter_static=maxiter_static)
    spmv = make_spmv(plan, mesh, axis_names=axis_names, backend=backend,
                     transport=transport, neighbor_offsets=neighbor_offsets)
    m_inv = jacobi_inverse(plan.diag_a, plan.mask)

    n_ops = len(spmv.operands)

    def program(*args):
        ops, (m_inv, mask, b, tol, maxiter) = args[:n_ops], args[n_ops:]
        return cg_solve(lambda v: spmv.program(*ops, v), b, m_inv, mask,
                        tol, maxiter, maxiter_static=maxiter_static)

    jitted = BoundJit(program, spmv.operands + place_operands(
        (m_inv, plan.mask), mesh, axis_names))

    def solve(b: jax.Array, tol: float = 1e-8, maxiter: int = 10_000):
        return jitted(b, jnp.asarray(tol, jnp.float32),
                      jnp.asarray(maxiter, jnp.int32))

    solve.spmv = spmv
    solve.jitted = jitted
    solve.transport = spmv.transport
    return solve
