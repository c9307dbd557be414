"""Resilient solves: chunked Krylov execution + host guard + elastic restart.

The registry solvers (``repro.solvers.krylov``) run as one fused, unbounded
``lax.while_loop`` — unbeatable per-iteration, but the program is opaque to
the host until it returns: a NaN, an SPD breakdown, or a preemption kills
the whole solve.  At the node counts the paper targets, long solves outlive
a node's MTBF, so this module runs the *same* hooks (``loop_body`` /
``loop_cond`` — identical per-iteration ops, identical §9 collective
census) in bounded chunks of ``check_every`` iterations:

    restart ──> [ chunk ──> guard ──> checkpoint ] ──> finish
                   ^            │
                   └─ rollback ─┘   (bounded retries, then SolveFailure)

Between chunks a **host-side guard** (riding ``fault.Watchdog`` /
``fault.StepGuard``) validates the state: non-finite guard scalars or true
residual, SPD breakdown (CG's ``r·z ≤ 0`` / ``p·Ap ≤ 0``, carried out of
the psums the iteration already pays for), divergence against the recorded
convergence trajectory, recurrence-vs-true residual mismatch, and
stagnation.  A bad verdict rolls back to the last good state via the
solver's ``loop_restart`` — a true-residual recompute (r = b − Ax) with a
β-chain reset, the same recovery idiom pipelined CG uses for drift
control — and retries; ``max_retries`` consecutive failures raise a
structured :class:`SolveFailure`.

The guard adds **zero collectives inside the while body**: every check
reads state scalars the iteration already reduces, plus one SpMV + one
psum per *chunk* (the true-residual probe, outside the loop), amortised
1/check_every.

Checkpoints are **layout-independent**: ``Solver.state_to_global`` maps
the iterate to global row ordering through the existing
``from_dist``/``to_dist`` machinery, and ``checkpoint.store`` persists it.
A restore may land on a different mesh shape, node partition, shard
format, and transport — the caller rebuilds the plan (re-partition →
re-pack → re-autotune) and ``resilient_solve(..., resume_from=dir)``
re-enters through ``loop_restart`` at the checkpointed x/iteration instead
of from zero.

Fault injection for tests is deterministic
(``repro.runtime.fault.FaultInjector``): NaN into a named shard of a named
state vector, transport payload bit-flips via a chunk program built on
``repro.core.transport.FaultyTransport``, and SIGKILL preemption
mid-solve.  See ``repro.testing.resilience_check`` for the kill-and-resume
orchestration and DESIGN.md §11 for the protocol.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.runtime.fault import FaultInjector, StepGuard, Watchdog
from repro.solvers.base import (SolverCtx, from_dist_batch, get_solver, pdot,
                                to_dist_batch)
from repro.solvers.precond import get_precond
from repro.util import BoundJit, place_operands, shard_map_compat

__all__ = ["resilient_solve", "make_resilient", "ResilientResult",
           "SolveFailure"]

_log = logging.getLogger(__name__)


class SolveFailure(RuntimeError):
    """A solve the resilience layer could not save: ``max_retries``
    consecutive chunks failed the guard.  Carries the post-mortem."""

    def __init__(self, message: str, *, reason: str, iteration: int,
                 retries: int, trajectory: list):
        super().__init__(message)
        self.reason = reason
        self.iteration = iteration
        self.retries = retries
        self.trajectory = trajectory


@dataclasses.dataclass
class ResilientResult:
    """What a resilient solve hands back (host numpy, global ordering)."""

    x: np.ndarray               # (n,) or (nrhs, n) global solution
    iters: np.ndarray           # per-RHS iteration counts (scalar unbatched)
    rel: np.ndarray             # solver-reported relative residual
    true_rel: float             # final true relative residual (worst RHS)
    converged: bool
    chunks: int                 # chunk programs executed (incl. retried)
    rollbacks: int
    trajectory: list            # [(iteration, worst true_rel)] good chunks
    resumed_from: int | None    # checkpoint step we resumed at, if any
    checkpoint_dir: str | None


@dataclasses.dataclass
class _Programs:
    restart: Callable
    chunk: Callable
    finish: Callable
    transport: str
    wire_dtype: str


class _Resilient:
    """The compiled chunked-execution programs for one (plan, mesh, solver,
    precond) tuple — the resilient analogue of ``make_solver``'s closure."""

    def __init__(self, plan, mesh, layout, sol, pre, kinds, skeys, opts,
                 build, transport):
        self.plan, self.mesh, self.layout = plan, mesh, layout
        self.sol, self.pre = sol, pre
        self.kinds, self.skeys, self.opts = kinds, skeys, opts
        self._build = build
        self._clean = build(transport)
        self._faulty: _Programs | None = None
        self.transport = self._clean.transport
        self.wire_dtype = self._clean.wire_dtype

    @property
    def restart(self):
        return self._clean.restart

    @property
    def chunk(self):
        return self._clean.chunk

    @property
    def finish(self):
        return self._clean.finish

    def faulty_chunk(self):
        """The chunk program compiled on a corrupting transport wrapper —
        built lazily, used only for an armed ``bitflip`` chunk."""
        if self._faulty is None:
            from repro.core.transport import FaultyTransport, get_transport
            base = get_transport(self.transport)
            self._faulty = self._build(FaultyTransport(base=base))
        return self._faulty.chunk


def make_resilient(plan, mesh: jax.sharding.Mesh, *,
                   solver="cg", precond="jacobi",
                   axis_names: tuple[str, str] = ("node", "core"),
                   backend: str = "jnp", transport=None,
                   neighbor_offsets=None, wire_dtype: str | None = None,
                   maxiter_static: int = 10_000,
                   A=None, layout: dict | None = None,
                   options: dict | None = None,
                   precond_options: dict | None = None) -> _Resilient:
    """Compile the three chunked-execution programs for a registered
    solver/preconditioner pair (mirrors ``make_solver``'s plumbing):

    ``restart(b, tol, maxiter, x, k)``        -> state tuple
    ``chunk(b, tol, maxiter, steps, *state)``
        -> state + (done, true_rel, active)
    ``finish(b, tol, maxiter, *state)``       -> (x, iters, rel)

    The state crosses the shard_map boundary as a flat tuple in sorted-key
    order (``Solver.state_kinds``): vectors ride ``P(node, core)`` in CG
    layout, scalars are replicated.  ``chunk`` runs at most ``steps``
    iterations of the solver's ``loop_body`` and appends the chunk-level
    true-residual probe (1 SpMV + 1 psum, outside the while body — the §9
    census of the body itself is untouched).
    """
    from repro.core.spmv import (make_shard_body, plan_fields,
                                 plan_shard_arrays)

    transport = transport if transport is not None else plan.transport
    if transport == "auto":
        from repro.core.transport import autotune_transport
        transport = autotune_transport(
            plan, mesh, axis_names=axis_names, backend=backend,
            neighbor_offsets=neighbor_offsets,
            wire_dtype=wire_dtype).winner
    sol = get_solver(solver)
    pre = get_precond(precond)
    pre.validate_options(precond_options)
    kinds = sol.state_kinds()
    if "x" not in kinds or "k" not in kinds:
        raise ValueError(f"solver {sol.name!r} state_kinds() must include "
                         "'x' and 'k'")
    skeys = tuple(sorted(kinds))
    node_ax, core_ax = axis_names
    axes = tuple(axis_names)
    pdata, papply = pre.bind(plan, layout=layout, A=A,
                             axis_names=axis_names, backend=backend,
                             options=precond_options)
    pnames = tuple(pdata)
    opts = sol.prepare(plan, pre, pdata, A=A, layout=layout, options=options)
    spec = P(node_ax, core_ax)
    state_specs = tuple(spec if kinds[k] == "vector" else P()
                        for k in skeys)

    # the operator shared by every program, placed on the mesh once
    shared = place_operands(tuple(pdata[n] for n in pnames) + (plan.mask,),
                            mesh, axis_names)
    plan_arrays = place_operands(plan_shard_arrays(plan), mesh, axis_names)

    def build(tr) -> _Programs:
        body = make_shard_body(plan, axis_names=axis_names, backend=backend,
                               transport=tr,
                               neighbor_offsets=neighbor_offsets,
                               wire_dtype=wire_dtype)
        fields = plan_fields(plan) + tuple(body.extra)
        n_f, n_p = len(fields), len(pnames)
        n_consts = n_f + n_p + 1                # + mask
        operands = (plan_arrays
                    + place_operands(tuple(body.extra.values()), mesh,
                                     axis_names)
                    + shared)

        def mk_ctx(args):
            F = {k: v[0, 0] for k, v in zip(fields, args[:n_f])}
            Pd = {k: v[0, 0]
                  for k, v in zip(pnames, args[n_f:n_f + n_p])}
            mask = args[n_f + n_p][0, 0]
            ctx = SolverCtx(spmv=jax.vmap(lambda v: body(F, v)),
                            precond=lambda r: papply(Pd, r),
                            mask=mask, axes=axes,
                            maxiter_static=maxiter_static, options=opts)
            return ctx, mask, args[n_consts:]

        def strip_state(svals):
            return {k: (v[0, 0] if kinds[k] == "vector" else v)
                    for k, v in zip(skeys, svals)}

        def pack_state(state):
            return tuple(state[k][None, None] if kinds[k] == "vector"
                         else state[k] for k in skeys)

        def bind(shard_fn, tail_specs, out_specs):
            fn = shard_map_compat(
                shard_fn, mesh=mesh,
                in_specs=(spec,) * n_consts + tail_specs,
                out_specs=out_specs)
            return BoundJit(fn, operands, mesh, out_specs)

        def shard_restart(*args):
            ctx, mask, (b, tol, maxiter, x, k) = mk_ctx(args)
            b = b[0, 0] * mask
            aux = sol.loop_aux(ctx, b, tol, maxiter)
            return pack_state(sol.loop_restart(ctx, aux, b, x[0, 0] * mask,
                                               k))

        restart = bind(shard_restart, (spec, P(), P(), spec, P()),
                       state_specs)

        def shard_chunk(*args):
            ctx, mask, rest = mk_ctx(args)
            b, tol, maxiter, steps = rest[:4]
            b = b[0, 0] * mask
            state = strip_state(rest[4:])
            aux = sol.loop_aux(ctx, b, tol, maxiter)

            def cond(c):
                j, s = c
                return (j < steps) & sol.loop_cond(ctx, aux, s)

            def bdy(c):
                j, s = c
                return j + 1, sol.loop_body(ctx, aux, s)

            _, state = jax.lax.while_loop(
                cond, bdy, (jnp.asarray(0, jnp.int32), state))
            active = sol.loop_active(ctx, aux, state)
            done = ~jnp.any(active)
            # the chunk-level true-residual probe: the guard's only
            # detector for corruption the recurrences never see (a NaN
            # planted in x, transport payload flips, Chebyshev anything)
            rt = b - ctx.spmv(state["x"])
            true_rel = (jnp.sqrt(pdot(axes, rt, rt))
                        / jnp.maximum(aux["bnorm"], 1e-30))
            return pack_state(state) + (done, true_rel, active)

        chunk = bind(shard_chunk, (spec, P(), P(), P()) + state_specs,
                     state_specs + (P(), P(), P()))

        def shard_finish(*args):
            ctx, mask, rest = mk_ctx(args)
            b, tol, maxiter = rest[:3]
            b = b[0, 0] * mask
            state = strip_state(rest[3:])
            aux = sol.loop_aux(ctx, b, tol, maxiter)
            x, iters, rel = sol.loop_finish(ctx, aux, state)
            return x[None, None], iters, rel

        finish = bind(shard_finish, (spec, P(), P()) + state_specs,
                      (spec, P(), P()))

        return _Programs(restart=restart, chunk=chunk, finish=finish,
                         transport=body.transport,
                         wire_dtype=body.wire_dtype)

    return _Resilient(plan, mesh, layout, sol, pre, kinds, skeys, opts,
                      build, transport)


# --------------------------------------------------------------------- #
# the host-side guard
# --------------------------------------------------------------------- #
def _guard_verdict(sol, state: dict, true_rel: np.ndarray, *,
                   best_rel: float, tol: float, since_improve: int,
                   stall_chunks: int, divergence_factor: float,
                   mismatch_factor: float,
                   done: bool = False) -> tuple[bool, str]:
    """(ok, reason) for one completed chunk.  Pure host numpy — reads the
    replicated state scalars the iteration already reduced plus the
    chunk's true-residual probe; never touches device code."""
    scalars = {k: np.asarray(v) for k, v in sol.guard_scalars(state).items()}
    for k, v in scalars.items():
        if not np.all(np.isfinite(v)):
            return False, f"nonfinite:{k}"
    worst = float(np.max(true_rel))
    if not np.isfinite(worst):
        return False, "nonfinite:true_residual"
    for k in sol.positive_scalars:
        if k in scalars and np.any(scalars[k] <= 0):
            return False, f"breakdown:{k}"
    if worst > divergence_factor * max(best_rel, tol):
        return False, "diverged"
    if "rr" in scalars:
        # the recurrence residual and the true residual must tell the same
        # story; a silently-corrupted x leaves the recurrence pristine
        rec = float(np.max(np.sqrt(np.maximum(scalars["rr"], 0.0))))
        if worst > mismatch_factor * (rec + tol) and worst > 10 * tol:
            return False, "mismatch"
    # stagnation only means "stuck" for residual-driven solvers that are
    # still asking for iterations; an a-priori-budget method idling at its
    # attainable floor (solver.stagnation_guard == False) and a chunk that
    # already reported completion are both healthy
    if (sol.stagnation_guard and not done
            and since_improve >= stall_chunks and worst > 10 * tol):
        return False, "stagnation"
    return True, "ok"


# --------------------------------------------------------------------- #
# the driver
# --------------------------------------------------------------------- #
def resilient_solve(A_or_plan, b, *, solver="cg", precond="jacobi",
                    mesh: jax.sharding.Mesh | None = None,
                    layout: dict | None = None, A=None,
                    n_node: int = 1, n_core: int = 1, mode: str = "balanced",
                    node_partition=None, format: str = "ell",
                    axis_names: tuple[str, str] = ("node", "core"),
                    backend: str = "jnp", transport=None,
                    neighbor_offsets=None, wire_dtype: str | None = None,
                    tol: float = 1e-5, maxiter: int = 10_000,
                    maxiter_static: int = 10_000,
                    check_every: int = 50, max_retries: int = 3,
                    checkpoint_dir: str | None = None,
                    resume_from: str | None = None,
                    injector: FaultInjector | None = None,
                    watchdog: Watchdog | None = None,
                    options: dict | None = None,
                    precond_options: dict | None = None,
                    divergence_factor: float = 1e3,
                    mismatch_factor: float = 1e3,
                    stall_chunks: int = 8,
                    programs: _Resilient | None = None) -> ResilientResult:
    """Run a registered solver under the resilience protocol.

    ``A_or_plan``: either a host matrix (anything with ``matvec`` /
    ``n_rows`` / ``diagonal``, e.g. the generators in ``repro.sparse``) —
    the plan is built here with ``n_node``/``n_core``/``mode``/
    ``format``/``node_partition`` — or an existing ``SpMVPlan`` (then
    ``layout`` is required and ``A`` optional but recommended: with the
    host matrix the guard recomputes the true residual in f64 on the
    host; without it the device-side probe is used).

    ``b`` is a global RHS, ``(n,)`` or ``(nrhs, n)`` numpy.

    ``check_every`` bounds each chunk; the guard runs between chunks and a
    healthy chunk is snapshotted (device references — cheap) and, when
    ``checkpoint_dir`` is set, persisted layout-independently via
    ``checkpoint.store``.  ``resume_from`` restores the latest checkpoint
    in that directory onto *this* plan — any mesh shape, partition,
    format, or transport — and resumes from the checkpointed iteration.

    ``injector`` arms one deterministic fault (see
    ``repro.runtime.fault.FaultInjector``); production solves leave it
    ``None``.

    ``programs`` reuses a prebuilt :func:`make_resilient` result (must be
    for this plan) so repeated solves hit the jit cache instead of
    re-tracing — what the bench harness does for its warm/timed pair.

    ``wire_dtype`` selects the halo wire codec ('f32' | 'bf16' | 'int8';
    ``None`` follows ``plan.wire_dtype``).  A lossy codec perturbs each
    SpMV by up to its relative bound, so the recurrence and the true
    residual legitimately disagree at that scale: the guard's
    mismatch/stagnation verdicts use ``max(tol, codec.rel_bound)`` so
    compressed wire does not trigger false rollbacks.  The solver's
    convergence ``tol`` itself is untouched.
    """
    from repro.checkpoint import latest_step
    from repro.checkpoint import load as ckpt_load
    from repro.checkpoint import save as ckpt_save
    from repro.core.spmv import build_spmv_plan
    from repro.util import make_mesh_compat

    if hasattr(A_or_plan, "matvec"):
        A = A_or_plan
        plan, layout = build_spmv_plan(
            A, n_node, n_core, mode=mode, node_partition=node_partition,
            format=format,
            transport=transport if isinstance(transport, str) else "a2a",
            wire_dtype=wire_dtype if wire_dtype is not None else "f32")
        if neighbor_offsets is None:
            neighbor_offsets = layout["neighbor_offsets"]
    else:
        plan = A_or_plan
        if layout is None:
            raise ValueError("resilient_solve(plan, ...) needs layout= "
                             "(the dict build_spmv_plan returned with it)")
        n_node, n_core = plan.n_node, plan.n_core
    if mesh is None:
        mesh = make_mesh_compat((n_node, n_core), axis_names)

    b = np.asarray(b, np.float64)
    unbatched = b.ndim == 1
    B = np.atleast_2d(b)
    nrhs, n = B.shape
    if n != plan.n:
        raise ValueError(f"b has {n} rows, plan has {plan.n}")

    if programs is not None:
        if programs.plan is not plan:
            raise ValueError("programs= was built for a different plan")
        rs = programs
    else:
        rs = make_resilient(plan, mesh, solver=solver, precond=precond,
                            axis_names=axis_names, backend=backend,
                            transport=transport,
                            neighbor_offsets=neighbor_offsets,
                            wire_dtype=wire_dtype,
                            maxiter_static=maxiter_static, A=A,
                            layout=layout, options=options,
                            precond_options=precond_options)
    sol = rs.sol
    # lossy wire legitimately separates recurrence from true residual by up
    # to the codec bound — widen the guard's thresholds to it (f32: no-op)
    from repro.core.transport import get_codec
    guard_tol = float(max(tol, get_codec(rs.wire_dtype).rel_bound))
    skeys = rs.skeys
    x_idx, k_idx = skeys.index("x"), skeys.index("k")
    if injector is not None and injector.kind == "nan":
        key = injector.state_key
        if rs.kinds.get(key) != "vector":
            raise ValueError(
                f"injector state_key {key!r} is not a vector state of "
                f"solver {sol.name!r}; vectors: "
                f"{[k for k, v in rs.kinds.items() if v == 'vector']}")

    bd = to_dist_batch(B, layout, plan)
    told = jnp.asarray(tol, jnp.float32)
    mxd = jnp.asarray(maxiter, jnp.int32)
    steps_d = jnp.asarray(int(check_every), jnp.int32)
    bnorms = np.maximum(np.linalg.norm(B, axis=1), 1e-30)

    def host_true_rel(x_dev) -> np.ndarray:
        if A is None:
            return None
        X = from_dist_batch(x_dev, layout, plan)
        R = B - np.stack([A.matvec(X[j].astype(np.float64))
                          for j in range(nrhs)])
        return np.linalg.norm(R, axis=1) / bnorms

    # ---- entry: cold start, or elastic resume from a checkpoint -------- #
    resumed_from = None
    trajectory: list = []
    if resume_from is not None:
        step = latest_step(resume_from)
        if step is None:
            raise ValueError(f"resume_from={resume_from!r}: no checkpoint "
                             "found")
        like = {"x": jax.ShapeDtypeStruct((nrhs, plan.n), np.float32)}
        gstate, extra = ckpt_load(resume_from, step, like)
        if extra.get("n") not in (None, plan.n) or \
                extra.get("nrhs") not in (None, nrhs):
            raise ValueError(
                f"checkpoint is for n={extra.get('n')}, "
                f"nrhs={extra.get('nrhs')}; this solve has n={plan.n}, "
                f"nrhs={nrhs}")
        gstate = {k: np.asarray(v) for k, v in gstate.items()}
        x_entry = sol.state_from_global(gstate, layout, plan,
                                        dtype=bd.dtype)
        k_entry = jnp.asarray(np.asarray(extra.get("iteration",
                                                   [step] * nrhs),
                                         np.int32))
        trajectory = [tuple(t) for t in extra.get("trajectory", [])]
        resumed_from = step
        _log.info("resuming from %s step %d (solver then: %s)",
                  resume_from, step, extra.get("solver"))
    else:
        x_entry = jnp.zeros_like(bd)
        k_entry = jnp.zeros((nrhs,), jnp.int32)

    state = rs.restart(bd, told, mxd, x_entry, k_entry)
    last_good = (state[x_idx], np.asarray(state[k_idx], np.int32))

    def persist(x_dev, k_host, step_tag=None):
        if checkpoint_dir is None:
            return
        g = sol.state_to_global({"x": np.asarray(x_dev)}, layout, plan)
        g = {k: np.asarray(v, np.float32) for k, v in g.items()}
        step = int(np.max(k_host)) if step_tag is None else step_tag
        ckpt_save(checkpoint_dir, step, g,
                  extra={"iteration": np.asarray(k_host).tolist(),
                         "solver": sol.name, "precond": rs.pre.name,
                         "tol": float(tol), "n": int(plan.n),
                         "nrhs": int(nrhs),
                         "trajectory": [list(t) for t in trajectory]})

    persist(*last_good)             # survive a preemption before chunk 1

    wd = watchdog or Watchdog()
    best_rel = min([t[1] for t in trajectory], default=1.0)
    since_improve = 0
    chunks = rollbacks = retries = 0
    true_rel_vec = np.ones(nrhs)
    done = False

    while not done:
        k_cur = int(np.max(np.asarray(state[k_idx])))
        program = rs.chunk
        if injector is not None and injector.crossed(k_cur,
                                                     k_cur + check_every):
            if injector.kind == "preempt":
                injector.preempt()         # SIGKILL — never returns
            elif injector.kind == "nan":
                nd, cd = injector.shard
                nd, cd = nd % n_node, cd % n_core
                # only a slot the mask marks real can propagate: the
                # matvec and the masked reductions never read padding, so
                # a NaN in a pad slot would be an inert injection
                valid = np.flatnonzero(np.asarray(plan.mask)[nd, cd] > 0)
                slot = (int(valid[injector.poison_slot(len(valid))])
                        if len(valid) else 0)
                i = skeys.index(injector.state_key)
                arr = jnp.asarray(state[i]).at[
                    nd, cd, :, slot].set(jnp.nan)
                state = state[:i] + (arr,) + state[i + 1:]
                _log.warning("injected NaN into %s shard (%d,%d) slot %d "
                             "at iteration %d", injector.state_key,
                             nd, cd, slot, k_cur)
            elif injector.kind == "bitflip":
                program = rs.faulty_chunk()
                _log.warning("running chunk at iteration %d through the "
                             "faulty transport", k_cur)

        guard = StepGuard(wd, on_emergency=lambda: persist(*last_good))
        with guard:
            out = jax.block_until_ready(
                program(bd, told, mxd, steps_d, *state))
        chunks += 1
        new_state = out[:len(skeys)]
        done = bool(out[len(skeys)])
        dev_true_rel = np.asarray(out[len(skeys) + 1])
        k_host = np.asarray(new_state[k_idx], np.int32)
        k_cur = int(np.max(k_host))
        tr = host_true_rel(new_state[x_idx])
        true_rel_vec = tr if tr is not None else dev_true_rel

        ok, reason = _guard_verdict(
            sol, dict(zip(skeys, new_state)), true_rel_vec,
            best_rel=best_rel, tol=guard_tol, since_improve=since_improve,
            stall_chunks=stall_chunks, divergence_factor=divergence_factor,
            mismatch_factor=mismatch_factor, done=done)
        if not ok:
            retries += 1
            rollbacks += 1
            k_good = int(np.max(last_good[1]))
            _log.warning("guard verdict %s at iteration %d "
                         "(retry %d/%d) — rolling back to iteration %d",
                         reason, k_cur, retries, max_retries, k_good)
            if retries > max_retries:
                raise SolveFailure(
                    f"solve failed at iteration {k_cur}: {reason} "
                    f"persisted through {retries - 1} rollbacks",
                    reason=reason, iteration=k_cur, retries=retries - 1,
                    trajectory=trajectory)
            state = rs.restart(bd, told, mxd, last_good[0],
                               jnp.asarray(last_good[1]))
            done = False
            continue

        retries = 0
        state = new_state
        worst = float(np.max(true_rel_vec))
        trajectory.append((k_cur, worst))
        if worst < best_rel * 0.999:
            best_rel = worst
            since_improve = 0
        else:
            since_improve += 1
        last_good = (state[x_idx], k_host)
        persist(*last_good)

    xd, iters, rel = jax.block_until_ready(rs.finish(bd, told, mxd, *state))
    X = from_dist_batch(xd, layout, plan)
    tr = host_true_rel(xd)
    true_rel_vec = tr if tr is not None else true_rel_vec
    iters = np.asarray(iters)
    rel = np.asarray(rel)
    result = ResilientResult(
        x=X[0] if unbatched else X,
        iters=iters[0] if unbatched else iters,
        rel=rel[0] if unbatched else rel,
        true_rel=float(np.max(true_rel_vec)),
        converged=bool(np.all(rel <= tol * 1.001) or
                       np.all(true_rel_vec <= tol * 10)),
        chunks=chunks, rollbacks=rollbacks, trajectory=trajectory,
        resumed_from=resumed_from, checkpoint_dir=checkpoint_dir)
    return result
