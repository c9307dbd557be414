"""The shipped Krylov solvers: ``cg``, ``pipelined_cg``, ``chebyshev``.

Three points on the synchronisation-cost axis (arXiv:1307.4567 measures
solver-level allreduces as the dominant strong-scaling cost once SpMV is
optimised; arXiv:1106.5908 shows overlap is the remedy):

``cg``           the PR 1 fused CG, ported verbatim onto the registry:
                 2 stacked scalar psums per iteration (p·Ap, then
                 [r·z, r·r] fused), both on the critical path.
``pipelined_cg`` Ghysels–Vanroose reordering: every dot the iteration needs
                 ([γ=r·u, δ=w·u, r·r]) is fused into **one** stacked psum
                 issued before the SpMV it is data-independent of, so the
                 allreduce latency hides behind the halo exchange + local
                 matvec — the paper's task-based comm/compute overlap
                 applied to the Krylov layer instead of the SpMV.
``chebyshev``    the reduction-free extreme point: given eigenvalue bounds
                 of M⁻¹A the three-term Chebyshev recurrence needs **zero**
                 collectives beyond the SpMV itself.  Bounds come from
                 ``options={"lmin": .., "lmax": ..}`` or are estimated at
                 build time from a host-side PCG-Lanczos sweep
                 (:func:`estimate_eig_bounds`), which works for any
                 registered preconditioner through its ``host_apply``.

All three run on ``(nrhs, rc_pad)`` batches with per-RHS freezing (see
``repro.solvers.base``): a converged column's state is carried through
bit-unchanged while the rest iterate, so batched solves equal sequential
ones exactly.

All three implement the chunked-loop hook protocol (``loop_aux`` /
``loop_restart`` / ``loop_cond`` / ``loop_body`` / ``loop_finish``), so the
resilient driver (``repro.solvers.resilient``) can run them in bounded
chunks, checkpoint their state, and restart them from an arbitrary iterate.
The monolithic ``make_solver`` path composes the same hooks into one fused
``while_loop`` (``Solver.shard_loop``), so the two regimes share every
per-iteration op — and the per-iteration collective census (§9) is
identical under both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.solvers.base import (Solver, SolverCtx, pdot, pdot_stack,
                                register_solver)

__all__ = ["CGSolver", "PipelinedCGSolver", "ChebyshevSolver",
           "estimate_eig_bounds", "chebyshev_iters_for_tol"]


def _gate(active, new, old):
    """Freeze converged RHS columns: keep ``old`` where ``active`` is off."""
    a = active.reshape(active.shape + (1,) * (new.ndim - active.ndim))
    return jnp.where(a, new, old)


class CGSolver(Solver):
    """Preconditioned CG, the fused PR 1 loop (2 scalar psums/iteration).

    Chunked-loop state adds ``pap`` — the last p·Ap denominator, carried
    out of the psum the iteration already pays for — so the host guard
    can flag SPD breakdown (p·Ap ≤ 0 or r·z ≤ 0) with **zero** extra
    collectives inside the while body.
    """

    name = "cg"
    #: psum 1 (p·Ap) + psum 2 (the stacked [r·z, r·r]) — statically
    #: proven per iteration by repro.analysis.jaxpr_pass
    reductions_per_iter = 2
    positive_scalars = ("rz", "pap")

    def state_kinds(self):
        return {"k": "scalar", "x": "vector", "r": "vector", "p": "vector",
                "rz": "scalar", "rr": "scalar", "pap": "scalar"}

    def loop_aux(self, ctx: SolverCtx, b, tol, maxiter):
        cap = jnp.minimum(maxiter, ctx.maxiter_static)
        bnorm = jnp.sqrt(pdot(ctx.axes, b, b))
        tol2 = (tol * jnp.maximum(bnorm, 1e-30)) ** 2
        return {"cap": cap, "bnorm": bnorm, "tol2": tol2}

    def loop_setup(self, ctx: SolverCtx, b, tol, maxiter):
        cap = jnp.minimum(maxiter, ctx.maxiter_static)
        z0 = ctx.precond(b)
        s0 = pdot_stack(ctx.axes, (b, b), (b, z0))  # [b·b, r0·z0], one psum
        bnorm = jnp.sqrt(s0[0])
        tol2 = (tol * jnp.maximum(bnorm, 1e-30)) ** 2
        aux = {"cap": cap, "bnorm": bnorm, "tol2": tol2}
        nrhs = b.shape[0]
        state = {"k": jnp.zeros((nrhs,), jnp.int32), "x": jnp.zeros_like(b),
                 "r": b, "p": z0, "rz": s0[1], "rr": s0[0],
                 "pap": jnp.ones_like(s0[0])}
        return aux, state

    def loop_restart(self, ctx: SolverCtx, aux, b, x, k):
        # true-residual recompute + fresh direction (β-chain reset):
        # r = b − Ax, p = z = M⁻¹r.  From x = 0 this reproduces loop_setup
        # bit-for-bit (A·0 is exactly 0), so cold start, rollback, and
        # elastic resume are one code path.
        r = b - ctx.spmv(x)
        z = ctx.precond(r)
        s = pdot_stack(ctx.axes, (r, z), (r, r))
        return {"k": k, "x": x, "r": r, "p": z, "rz": s[0], "rr": s[1],
                "pap": jnp.ones_like(s[0])}

    def loop_active(self, ctx: SolverCtx, aux, state):
        return (state["k"] < aux["cap"]) & (state["rr"] > aux["tol2"])

    def loop_body(self, ctx: SolverCtx, aux, state):
        k, x, r, p = state["k"], state["x"], state["r"], state["p"]
        rz, rr = state["rz"], state["rr"]
        active = (k < aux["cap"]) & (rr > aux["tol2"])
        ap = ctx.spmv(p)                     # a2a + 2 core gathers
        den = pdot(ctx.axes, p, ap)          # psum 1
        alpha = rz / den
        x = _gate(active, x + alpha[:, None] * p, x)
        r = _gate(active, r - alpha[:, None] * ap, r)
        z = ctx.precond(r)
        s = pdot_stack(ctx.axes, (r, z), (r, r))  # psum 2: [r·z, r·r]
        beta = s[0] / rz
        p = _gate(active, z + beta[:, None] * p, p)
        return {"k": k + active.astype(k.dtype), "x": x, "r": r, "p": p,
                "rz": _gate(active, s[0], rz), "rr": _gate(active, s[1], rr),
                "pap": _gate(active, den, state["pap"])}

    def loop_finish(self, ctx: SolverCtx, aux, state):
        rel = jnp.sqrt(state["rr"]) / jnp.maximum(aux["bnorm"], 1e-30)
        return state["x"], state["k"], rel

    def guard_scalars(self, state):
        return {"rr": state["rr"], "rz": state["rz"], "pap": state["pap"]}


class PipelinedCGSolver(Solver):
    """Ghysels–Vanroose pipelined PCG — one stacked psum per iteration.

    The iteration's reductions ([r·u, w·u, r·r]) are issued first, then the
    preconditioner application and the SpMV ``n = A M⁻¹ w`` run with no
    data dependence on them: the compiled HLO has the all-reduce and the
    halo exchange + local matvec side by side for the latency-hiding
    scheduler.  Costs the classic pipelined-CG price — three extra vector
    recurrences (z, q, s) and a residual check that lags one iteration.

    The extra recurrences drift from their true values in finite precision
    (Ghysels & Vanroose §4); in f32 the drift both caps attainable accuracy
    well above plain CG's *and* lets the recurrence residual report
    convergence the true residual never reached.  The remedy ships enabled:
    every ``replace_every`` iterations (option, default 50) the residual
    system is *restarted* — r = b − Ax, u = M⁻¹r, w = Au recomputed from
    their definitions and the direction recurrences (z, q, s, p) reset, so
    the next step is a fresh first iteration from the current x.  A restart
    is 2 SpMVs + 1 preconditioner application and **no reductions**, so the
    one-allreduce-per-iteration census is untouched (~4% amortised SpMV
    overhead).  A full restart is deliberately chosen over the
    keep-the-β-chain replacement of Ghysels & Vanroose Alg. 4: in f32 the
    drifted scalar history (γ, α) poisons β after the vectors jump back to
    truth, and measured on the graded 8×2 problem Alg.-4 replacement
    stalls above 1e-3 while restart-50 grounds the recurrence residual and
    converges to the f32 floor (~6e-5 true).  The price is iteration count
    (~2× plain CG when the restart interval truncates the Krylov space);
    the restart interval must exceed the Krylov dimension the spectrum
    needs per segment — don't set it below ~25.

    ``loop_restart`` (the resilience entry point) is the same recovery
    idiom made external: γ_prev := +inf zeroes the next β, so the step
    after a rollback or elastic resume is a fresh first iteration from the
    restored x.
    """

    name = "pipelined_cg"
    #: the ONE stacked psum ([γ, δ, r·r]); the drift-correction restart
    #: branch is reduction-free by design, so the contract holds on
    #: every iteration, replaced or not
    reductions_per_iter = 1

    def lossy_wire_options(self):
        # a quantised halo (bf16/int8 wire) makes the SpMV a *different*
        # perturbed operator on every call; the vector recurrences
        # amplify that inconsistency far faster than f32 round-off.
        # Measured on the graded 8×2 problem over int8 wire under f64
        # refinement: restart-25 and restart-50 diverge; restart-10's
        # cycles contract erratically (0.72, 0.27, 5.6e-3, 6.9e-2, ...)
        # and the summation order of the ELL matvec (``_ell_rows_dot`` in
        # ``repro.sparse.formats``) stalls it at 2e-4; restart-5 contracts
        # on every cycle under either order; restart-3 stalls.  So this
        # constant is tuned to that order.  It costs bf16 wire, where
        # restart-10 also converges, 18 cycles / 18000 inner iterations
        # to 1e-7 against restart-10's 7 / 6520 (``refine_check``, graded
        # 8x2, CPU).  The ~25-iteration floor documented above is
        # a clean-wire economy argument (Krylov-space truncation costs
        # iterations); under a lossy codec stability, not iteration
        # count, binds.
        return {"replace_every": 5}

    def state_kinds(self):
        return {"t": "scalar", "k": "scalar",
                "x": "vector", "r": "vector", "u": "vector", "w": "vector",
                "z": "vector", "q": "vector", "s": "vector", "p": "vector",
                "g_prev": "scalar", "a_prev": "scalar", "rr": "scalar"}

    def loop_aux(self, ctx: SolverCtx, b, tol, maxiter):
        cap = jnp.minimum(maxiter, ctx.maxiter_static)
        bnorm = jnp.sqrt(pdot(ctx.axes, b, b))
        tol2 = (tol * jnp.maximum(bnorm, 1e-30)) ** 2
        # the replace closure inside loop_body needs b — carry it in aux
        return {"cap": cap, "bnorm": bnorm, "tol2": tol2, "b": b}

    def loop_setup(self, ctx: SolverCtx, b, tol, maxiter):
        cap = jnp.minimum(maxiter, ctx.maxiter_static)
        u0 = ctx.precond(b)                     # r0 = b  (x0 = 0)
        w0 = ctx.spmv(u0)
        rr0 = pdot(ctx.axes, b, b)
        bnorm = jnp.sqrt(rr0)
        tol2 = (tol * jnp.maximum(bnorm, 1e-30)) ** 2
        aux = {"cap": cap, "bnorm": bnorm, "tol2": tol2, "b": b}
        zeros = jnp.zeros_like(b)
        ones = jnp.ones_like(rr0)
        nrhs = b.shape[0]
        state = {"t": jnp.asarray(0, jnp.int32),
                 "k": jnp.zeros((nrhs,), jnp.int32),
                 "x": zeros, "r": b, "u": u0, "w": w0,
                 "z": zeros, "q": zeros, "s": zeros, "p": zeros,
                 "g_prev": ones, "a_prev": ones, "rr": rr0}
        return aux, state

    def loop_restart(self, ctx: SolverCtx, aux, b, x, k):
        # the drift-correction restart, parameterised by the entry iterate:
        # recompute r/u/w from their definitions, reset the direction
        # recurrences, and poison γ_prev so the next β is exactly 0.
        r = b - ctx.spmv(x)
        u = ctx.precond(r)
        w = ctx.spmv(u)
        rr = pdot(ctx.axes, r, r)
        zeros = jnp.zeros_like(x)
        return {"t": jnp.asarray(0, jnp.int32), "k": k, "x": x, "r": r,
                "u": u, "w": w, "z": zeros, "q": zeros, "s": zeros,
                "p": zeros, "g_prev": jnp.full_like(rr, jnp.inf),
                "a_prev": jnp.ones_like(rr), "rr": rr}

    def loop_active(self, ctx: SolverCtx, aux, state):
        return (state["k"] < aux["cap"]) & (state["rr"] > aux["tol2"])

    def loop_body(self, ctx: SolverCtx, aux, state):
        b = aux["b"]
        replace_every = int(ctx.options.get("replace_every", 50))
        t, k = state["t"], state["k"]
        x, r, u, w = state["x"], state["r"], state["u"], state["w"]
        z, q, s, p = state["z"], state["q"], state["s"], state["p"]
        g_prev, a_prev, rr = state["g_prev"], state["a_prev"], state["rr"]
        active = (k < aux["cap"]) & (rr > aux["tol2"])
        first = k == 0

        def replace(args):
            """Restart: recompute r/u/w from their definitions and reset the
            direction recurrences (2 SpMVs, 1 precond apply, 0 reductions).
            γ_prev := +inf makes the next step's β exactly 0, i.e. a fresh
            first iteration from the current x."""
            active, x, r, u, w, z, q, s, p, g_prev = args
            r_t = b - ctx.spmv(x)
            u_t = ctx.precond(r_t)
            w_t = ctx.spmv(u_t)
            zv = jnp.zeros_like(x)
            inf = jnp.full_like(g_prev, jnp.inf)
            return (active, x, _gate(active, r_t, r), _gate(active, u_t, u),
                    _gate(active, w_t, w), _gate(active, zv, z),
                    _gate(active, zv, q), _gate(active, zv, s),
                    _gate(active, zv, p), _gate(active, inf, g_prev))

        # periodic drift correction (t is the scalar trip counter; the
        # predicate is replicated, so every shard takes the same branch)
        do_replace = (t > 0) & (t % replace_every == 0)
        (_, x, r, u, w, z, q, s, p, g_prev) = jax.lax.cond(
            do_replace, replace, lambda a: a,
            (active, x, r, u, w, z, q, s, p, g_prev))
        # the ONE stacked reduction; everything until the scalar
        # recurrences below is independent of it, so the allreduce
        # overlaps the preconditioner + SpMV
        S = pdot_stack(ctx.axes, (r, u), (w, u), (r, r))  # [γ, δ, r·r]
        m = ctx.precond(w)
        n = ctx.spmv(m)
        gamma, delta = S[0], S[1]
        beta = jnp.where(first, 0.0, gamma / g_prev)
        alpha = jnp.where(first, gamma / delta,
                          gamma / (delta - beta * gamma / a_prev))
        z = _gate(active, n + beta[:, None] * z, z)
        q = _gate(active, m + beta[:, None] * q, q)
        s_v = _gate(active, w + beta[:, None] * s, s)
        p = _gate(active, u + beta[:, None] * p, p)
        x = _gate(active, x + alpha[:, None] * p, x)
        r = _gate(active, r - alpha[:, None] * s_v, r)
        u = _gate(active, u - alpha[:, None] * q, u)
        w = _gate(active, w - alpha[:, None] * z, w)
        return {"t": t + 1, "k": k + active.astype(k.dtype),
                "x": x, "r": r, "u": u, "w": w,
                "z": z, "q": q, "s": s_v, "p": p,
                "g_prev": _gate(active, gamma, g_prev),
                "a_prev": _gate(active, alpha, a_prev),
                "rr": _gate(active, S[2], rr)}

    def loop_finish(self, ctx: SolverCtx, aux, state):
        rr = pdot(ctx.axes, state["r"], state["r"])  # fresh ‖r‖, post-loop
        rel = jnp.sqrt(rr) / jnp.maximum(aux["bnorm"], 1e-30)
        return state["x"], state["k"], rel

    def guard_scalars(self, state):
        # g_prev is legitimately +inf right after a restart, so only the
        # recurrence residual is guard-checkable; the driver's true-residual
        # recompute covers the drifting vector recurrences.
        return {"rr": state["rr"]}


class ChebyshevSolver(Solver):
    """Three-term Chebyshev iteration — zero collectives per iteration.

    Needs eigenvalue bounds ``[lmin, lmax]`` of the preconditioned operator
    M⁻¹A (``prepare`` estimates them from ``A`` when not given).  With the
    bounds fixed, every iteration is SpMV + AXPYs: no dot products, no
    allreduces, nothing for 10k ranks to synchronise on.  The iteration
    count that meets ``tol`` is known *a priori* from the Chebyshev error
    bound, so the loop runs ``min(maxiter, iters_for_tol(tol))`` steps and
    measures the real residual once, after the loop.

    Restartability: the recurrence is residual-free — no scalar in the
    state ever reflects corruption, so :meth:`guard_scalars` is empty and
    the resilient driver's true-residual recompute is the *only* detector.
    The state carries ``kb``, the iteration of the last restart: the
    a-priori budget ``need`` counts from ``kb`` (a restarted Chebyshev
    needs a full fresh budget — its error bound knows nothing about the
    restored x being closer than b), and the β/α special-casing keys off
    ``k == kb`` instead of ``k == 0``.  With ``kb = 0`` this is exactly
    the historical loop.
    """

    name = "chebyshev"
    #: the reduction-free extreme point: the three-term recurrence needs
    #: no dot products, so the while body carries zero all-reduces
    reductions_per_iter = 0
    #: the error bound fixes the trip count up front, and the f32
    #: attainable floor usually sits above the guard's 10·tol stagnation
    #: threshold — a healthy run spends its whole tail "not improving",
    #: and a rollback would hand it a fresh budget (kb := k) forever.
    stagnation_guard = False

    #: safety margins on the Lanczos Ritz estimates (which sit inside the
    #: true spectrum): widen the interval so no eigenvalue escapes it.
    lmax_margin: float = 1.05
    lmin_margin: float = 0.9

    def prepare(self, plan, precond, pdata, A=None, layout=None,
                options=None):
        opts = dict(options or {})
        if "lmin" not in opts or "lmax" not in opts:
            if A is None:
                raise ValueError(
                    "chebyshev needs eigenvalue bounds: pass "
                    "options={'lmin': .., 'lmax': ..} or the host matrix "
                    "A= (plus layout= for block_jacobi) to estimate them")
            lmin, lmax = estimate_eig_bounds(
                A.matvec, precond.host_apply(plan, layout, A), A.n_rows)
            opts.setdefault("lmin", lmin * self.lmin_margin)
            opts.setdefault("lmax", lmax * self.lmax_margin)
        return opts

    def _coeffs(self, ctx: SolverCtx):
        lmin = float(ctx.options["lmin"])
        lmax = float(ctx.options["lmax"])
        return (lmax + lmin) / 2.0, (lmax - lmin) / 2.0

    def state_kinds(self):
        return {"k": "scalar", "x": "vector", "r": "vector", "p": "vector",
                "a_prev": "scalar", "kb": "scalar"}

    def loop_aux(self, ctx: SolverCtx, b, tol, maxiter):
        lmin = float(ctx.options["lmin"])
        lmax = float(ctx.options["lmax"])
        bnorm = jnp.sqrt(pdot(ctx.axes, b, b))
        # a-priori trip count from the Chebyshev error bound (static
        # convergence factor, dynamic tol) — no in-loop residual needed
        sigma = (math.sqrt(lmax / lmin) - 1.0) / (math.sqrt(lmax / lmin) + 1.0)
        need = jnp.ceil(jnp.log(jnp.maximum(2.0 / jnp.maximum(tol, 1e-30),
                                            1.0))
                        * (1.2 / -math.log(sigma))).astype(jnp.int32) + 5
        cap = jnp.minimum(maxiter, ctx.maxiter_static)
        return {"cap": cap, "need": need, "bnorm": bnorm}

    def loop_setup(self, ctx: SolverCtx, b, tol, maxiter):
        aux = self.loop_aux(ctx, b, tol, maxiter)
        d, _ = self._coeffs(ctx)
        nrhs = b.shape[0]
        state = {"k": jnp.zeros((nrhs,), jnp.int32), "x": jnp.zeros_like(b),
                 "r": b, "p": jnp.zeros_like(b),
                 "a_prev": jnp.full((nrhs,), 1.0 / d, jnp.float32),
                 "kb": jnp.zeros((nrhs,), jnp.int32)}
        return aux, state

    def loop_restart(self, ctx: SolverCtx, aux, b, x, k):
        d, _ = self._coeffs(ctx)
        r = b - ctx.spmv(x)
        nrhs = x.shape[0]
        return {"k": k, "x": x, "r": r, "p": jnp.zeros_like(x),
                "a_prev": jnp.full((nrhs,), 1.0 / d, jnp.float32), "kb": k}

    def loop_active(self, ctx: SolverCtx, aux, state):
        k, kb = state["k"], state["kb"]
        return (k < aux["cap"]) & ((k - kb) < aux["need"])

    def loop_body(self, ctx: SolverCtx, aux, state):
        d, c = self._coeffs(ctx)
        k, x, r, p = state["k"], state["x"], state["r"], state["p"]
        a_prev, kb = state["a_prev"], state["kb"]
        # freezing matters here only when columns carry *different* budgets
        # (per-RHS tol, or kb offsets from a serving splice): a column past
        # its budget must hold its state bit-for-bit while fresher columns
        # iterate.  With a shared budget every column is active in lockstep
        # and each gate is where(True, new, old) == new, bitwise.
        active = (k < aux["cap"]) & ((k - kb) < aux["need"])
        z = ctx.precond(r)
        beta = jnp.where(k == kb, 0.0, (c * a_prev / 2.0) ** 2)
        alpha = jnp.where(k == kb, 1.0 / d, 1.0 / (d - beta / a_prev))
        p = _gate(active, z + beta[:, None] * p, p)
        x = _gate(active, x + alpha[:, None] * p, x)
        r = _gate(active, r - alpha[:, None] * ctx.spmv(p),
                  r)                           # the only collectives
        return {"k": k + active.astype(k.dtype), "x": x, "r": r, "p": p,
                "a_prev": _gate(active, alpha, a_prev), "kb": kb}

    def loop_finish(self, ctx: SolverCtx, aux, state):
        rr = pdot(ctx.axes, state["r"], state["r"])  # one psum, post-loop
        rel = jnp.sqrt(rr) / jnp.maximum(aux["bnorm"], 1e-30)
        return state["x"], state["k"], rel


def chebyshev_iters_for_tol(lmin: float, lmax: float, tol: float) -> int:
    """Iterations the Chebyshev error bound needs for a relative ``tol``."""
    sigma = (math.sqrt(lmax / lmin) - 1.0) / (math.sqrt(lmax / lmin) + 1.0)
    return int(math.ceil(math.log(2.0 / tol) * (1.2 / -math.log(sigma)))) + 5


def estimate_eig_bounds(matvec, precond_apply, n: int,
                        iters: int = 96, seed: int = 0
                        ) -> tuple[float, float]:
    """Extremal eigenvalue estimates of M⁻¹A via host PCG-Lanczos (f64).

    Runs preconditioned CG on a random RHS and diagonalises the Lanczos
    tridiagonal its α/β coefficients define — the standard matrix-free
    estimator (PETSc's ``KSPChebyshevEstEig``), valid for any SPD ``M``
    given only its application.  Ritz values sit inside the true spectrum,
    so callers should widen the interval (``ChebyshevSolver`` applies its
    ``lmin_margin``/``lmax_margin``).
    """
    rng = np.random.default_rng(seed)
    r = rng.normal(size=n)
    z = np.asarray(precond_apply(r), dtype=np.float64)
    p = z.copy()
    rz = float(r @ z)
    alphas: list[float] = []
    betas: list[float] = []
    for _ in range(min(iters, n - 1)):
        ap = np.asarray(matvec(p), dtype=np.float64)
        pap = float(p @ ap)
        if pap <= 0 or rz <= 0:
            break
        alpha = rz / pap
        r = r - alpha * ap
        z = np.asarray(precond_apply(r), dtype=np.float64)
        rz_new = float(r @ z)
        alphas.append(alpha)
        betas.append(rz_new / rz)
        if rz_new < 1e-28:
            break
        p = z + (rz_new / rz) * p
        rz = rz_new
    m = len(alphas)
    if m == 0:
        raise ValueError("eigenvalue estimation failed: operator or "
                         "preconditioner is not SPD on the probe vector")
    T = np.zeros((m, m))
    for j in range(m):
        T[j, j] = 1.0 / alphas[j] + (betas[j - 1] / alphas[j - 1] if j else 0.0)
        if j + 1 < m:
            T[j, j + 1] = T[j + 1, j] = math.sqrt(betas[j]) / alphas[j]
    ev = np.linalg.eigvalsh(T)
    return float(ev[0]), float(ev[-1])


register_solver(CGSolver())
register_solver(PipelinedCGSolver())
register_solver(ChebyshevSolver())
