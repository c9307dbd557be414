"""Krylov solver registry + the fused sharded-solve factory.

The Krylov layer gets the same treatment the shard-storage layer got in
``repro.sparse.formats``: every solver is a named plugin supplying the
per-shard iteration loop, and everything around it — the two-phase SpMV
shard body, the preconditioner application, the shard_map plumbing, the
batched-RHS vmapping — is shared machinery owned by this module.

A solver sees the world through a :class:`SolverCtx`:

  * ``ctx.spmv``    — the fused two-phase SpMV over this core's shard,
                      already vmapped over the RHS axis: ``(nrhs, rc_pad)
                      -> (nrhs, rc_pad)``.  Each call costs 1 ``all_to_all``
                      + 2 core ``all_gather``s and **zero all-reduces**
                      (the ghost assembly is gather+add, see
                      ``repro.core.spmv.make_shard_body``), so any
                      all-reduce in the compiled loop body belongs to the
                      solver's own reductions — the collective census is
                      exact.
  * ``ctx.precond`` — shard-local preconditioner application ``z = M^-1 r``
                      (``repro.solvers.precond``), communication-free.
  * ``pdot`` / ``pdot_stack`` — the VecDot split: per-RHS local partial
                      sums + one tiny ``psum`` over the whole mesh.
                      ``pdot_stack`` fuses k dots into a single ``(k, nrhs)``
                      all-reduce — the batched analogue of PR 1's stacked
                      scalar psum.

Vectors inside a solver loop are always ``(nrhs, rc_pad)``; the unbatched
user-facing path is the same code with ``nrhs == 1`` and squeezed outputs.
Per-RHS convergence is handled by *freezing*: a converged RHS keeps its
state bit-for-bit while the rest of the batch iterates, so a batched solve
is exactly equal to running its columns one at a time.

``make_solver`` is the user entry point (mirroring ``make_spmv`` /
``make_cg``)::

    solve = make_solver(plan, mesh, solver="pipelined_cg",
                        precond="block_jacobi", A=A, layout=layout)
    x, iters, rel = solve(bd, tol=1e-6, maxiter=10_000)
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.solvers.precond import Preconditioner, get_precond
from repro.util import BoundJit, place_operands, shard_map_compat

# NOTE: repro.core is imported lazily inside the functions below —
# repro.core.cg itself imports this module (for local_dot/jacobi_inverse
# re-exports), so a top-level import would be circular.

__all__ = ["local_dot", "pdot", "pdot_stack", "SolverCtx", "Solver",
           "register_solver", "get_solver", "available_solvers",
           "make_solver", "make_precond_apply",
           "to_dist_batch", "from_dist_batch"]


# --------------------------------------------------------------------- #
# the VecDot pattern, deduped (was cg.py::_dot, sharded_cg.py::pdot/pdot2)
# --------------------------------------------------------------------- #
def local_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Local f32 dot over the trailing axis (no communication).

    1-D inputs give a scalar; ``(nrhs, m)`` inputs give per-RHS ``(nrhs,)``
    partials.  This is PETSc's ``VecDot`` local phase; auto-sharded callers
    (the unfused ``cg_solve``) let XLA insert the allreduce, sharded callers
    use :func:`pdot` / :func:`pdot_stack`.
    """
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32), axis=-1)


@jax.named_scope("dot")
def pdot(axes, a: jax.Array, b: jax.Array) -> jax.Array:
    """VecDot: local partial + one tiny allreduce over ``axes``."""
    return jax.lax.psum(local_dot(a, b), axes)


@jax.named_scope("dot")
def pdot_stack(axes, *pairs) -> jax.Array:
    """k VecDots fused into a single stacked ``(k, nrhs)`` allreduce."""
    return jax.lax.psum(jnp.stack([local_dot(a, b) for a, b in pairs]), axes)


# --------------------------------------------------------------------- #
# solver protocol
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SolverCtx:
    """Everything a solver's shard loop may touch, pre-bound by make_solver.

    ``spmv``/``precond`` operate on ``(nrhs, rc_pad)`` blocks of vectors;
    ``mask`` is this core's ``(rc_pad,)`` valid-row mask; ``axes`` the psum
    axis names; ``options`` the solver-specific static options resolved by
    ``Solver.prepare`` (e.g. Chebyshev eigenvalue bounds).
    """

    spmv: Callable[[jax.Array], jax.Array]
    precond: Callable[[jax.Array], jax.Array]
    mask: jax.Array
    axes: tuple[str, ...]
    maxiter_static: int
    options: dict


class Solver:
    """Interface of a registered Krylov solver.

    Subclasses set ``name`` and implement the **loop hooks** below;
    ``prepare`` runs once on the host at build time and may derive static
    options from the matrix (Chebyshev uses it to estimate eigenvalue
    bounds).

    The iteration is split into hooks instead of one opaque while loop so
    the same solver runs under two execution regimes:

      * **monolithic** — :meth:`shard_loop` (the ``make_solver`` path)
        composes ``loop_setup`` + ``lax.while_loop(loop_cond, loop_body)``
        + ``loop_finish`` into the historical single fused loop;
      * **chunked** — the resilient driver
        (``repro.solvers.resilient``) runs the *same* ``loop_cond`` /
        ``loop_body`` in bounded chunks of ``check_every`` iterations,
        with the loop state a named dict that crosses the shard_map
        boundary between chunks.  Because the per-iteration ops are
        identical, the chunked iterates match the monolithic ones and the
        while-body collective census is unchanged.

    State contract: the loop state is a ``dict[str, jax.Array]``;
    :meth:`state_kinds` declares each entry as ``"vector"`` (``(nrhs,
    rc_pad)`` per shard — sharded over the mesh outside the loop) or
    ``"scalar"`` (per-RHS ``(nrhs,)`` or plain ``()`` — replicated).
    Every state dict must carry ``"x"`` (the iterate) and ``"k"`` (per-RHS
    iteration count, int32).

    Restartability: :meth:`loop_restart` rebuilds a valid state from an
    arbitrary iterate ``x`` with a **true-residual recompute** (r = b −
    Ax) and a reset recurrence chain — the β-chain reset idiom pipelined
    CG already uses for drift control.  It is the single recovery
    primitive behind cold start (``x = 0``), rollback after corruption,
    and elastic restore onto a different mesh/partition/format/transport.

    Layout independence: :meth:`state_to_global` /
    :meth:`state_from_global` convert the checkpointable part of the
    state between the plan's distributed layout and global row ordering,
    riding the existing ``to_dist``/``from_dist`` machinery.  The default
    persists the iterate alone — exactly what ``loop_restart`` needs —
    so a checkpoint written under one (mesh, partition, format,
    transport) restores under any other.
    """

    name: str = ""
    #: declared all-reduce count of one ``loop_body`` iteration — the
    #: solver's side of the collective-census contract.  The static
    #: verifier (``repro.analysis.jaxpr_pass``) traces ``shard_loop``
    #: device-free and proves the while-body psum count equals this
    #: declaration for every registered format x transport combination
    #: (the SpMV contributes zero all-reduces by construction, so the
    #: whole body count is attributable to the solver).  ``None`` means
    #: "no contract declared" and is itself flagged: a registered solver
    #: must state its synchronisation cost.
    reductions_per_iter: int | None = None
    #: :meth:`guard_scalars` keys that must stay strictly positive while
    #: the solve is healthy (SPD breakdown detection: CG's rz and p·Ap).
    positive_scalars: tuple[str, ...] = ()
    #: whether a flat true-residual trajectory is a corruption signal the
    #: resilient guard should roll back on.  Residual-driven solvers stop
    #: when converged, so chunks that stop improving mean the solve is
    #: stuck; a-priori-budget methods (Chebyshev) legitimately idle at
    #: their attainable floor for the rest of the budget — they set this
    #: False and rely on the nonfinite/diverged probe checks alone.
    stagnation_guard: bool = True

    def prepare(self, plan, precond: Preconditioner,
                pdata: dict, A=None, layout=None,
                options: dict | None = None) -> dict:
        """Resolve static solve options on the host.  Default: passthrough."""
        return dict(options or {})

    def lossy_wire_options(self) -> dict:
        """Option defaults applied when the halo wire codec is lossy
        (``repro.core.transport`` bf16/int8).  A quantised SpMV is a
        *different* perturbed operator on every call; solvers whose
        recurrences amplify such inconsistency override this (pipelined
        CG tightens its residual-replacement period).  Merged UNDER user
        options by the refinement combinator (``repro.solvers.refine``)."""
        return {}

    # -- the chunked-execution loop hooks ------------------------------- #
    def state_kinds(self) -> dict[str, str]:
        """``{state key: "vector" | "scalar"}`` — the loop-state layout."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the chunked-loop "
            "protocol (state_kinds)")

    def loop_aux(self, ctx: SolverCtx, b: jax.Array, tol: jax.Array,
                 maxiter: jax.Array) -> dict:
        """Derived per-solve values (tolerances, caps, bounds) recomputed
        at every chunk entry.  Must be cheap and deterministic — it runs
        once per chunk, outside the while body."""
        raise NotImplementedError

    def loop_setup(self, ctx: SolverCtx, b, tol, maxiter):
        """Monolithic entry: ``(aux, initial state)`` — may fuse the aux
        and init reductions (the historical pre-loop code path)."""
        raise NotImplementedError

    def loop_restart(self, ctx: SolverCtx, aux: dict, b, x, k) -> dict:
        """State continuing from iterate ``x`` at iteration count ``k``:
        true-residual recompute + recurrence-chain reset (0 extra
        collectives beyond the SpMV and the re-derived dots)."""
        raise NotImplementedError

    def loop_active(self, ctx: SolverCtx, aux: dict, state: dict):
        """Per-RHS ``(nrhs,)`` bool: which columns are still iterating.

        This is the *slot* signal of the serving layer
        (``repro.serve.engine``): a column that goes inactive has either
        converged (residual-driven solvers freeze it bit-exactly) or
        exhausted its budget, and its batch slot can be retired and
        refilled with the next queued RHS.  ``loop_cond`` is its
        ``any``-reduction, so the two can never disagree.
        """
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the chunked-loop "
            "protocol (loop_active)")

    def loop_cond(self, ctx: SolverCtx, aux: dict, state: dict):
        """Replicated scalar: any RHS still iterating?  Default: the
        ``any``-reduction of :meth:`loop_active` — override only if the
        whole-batch predicate is cheaper than the per-column one."""
        return jnp.any(self.loop_active(ctx, aux, state))

    def loop_body(self, ctx: SolverCtx, aux: dict, state: dict) -> dict:
        """One iteration on the state dict (the while-loop body)."""
        raise NotImplementedError

    def loop_finish(self, ctx: SolverCtx, aux: dict, state: dict):
        """``(x, iters, rel)`` from a final state."""
        raise NotImplementedError

    def guard_scalars(self, state: dict) -> dict:
        """The state scalars a host-side guard can check between chunks
        (finite? positive where SPD demands it?).  Keys are
        solver-specific; ``{}`` for residual-free recurrences (Chebyshev)
        whose corruption only the driver's true-residual recompute can
        see."""
        return {}

    # -- layout-independent checkpoint state ---------------------------- #
    def state_to_global(self, state_host: dict, layout: dict, plan) -> dict:
        """Host state -> layout-independent checkpoint payload (global row
        ordering).  Default: the iterate ``x`` alone, via ``from_dist``."""
        return {"x": from_dist_batch(state_host["x"], layout, plan)}

    def state_from_global(self, gstate: dict, layout: dict, plan,
                          dtype=None) -> jax.Array:
        """Checkpoint payload -> the iterate in the (possibly different)
        plan's distributed layout, ready for :meth:`loop_restart`."""
        import numpy as np
        return to_dist_batch(np.atleast_2d(np.asarray(gstate["x"])),
                             layout, plan, dtype=dtype)

    # -- the monolithic composition (the make_solver path) -------------- #
    def shard_loop(self, ctx: SolverCtx, b: jax.Array, tol: jax.Array,
                   maxiter: jax.Array):
        """Run the iteration on ``(nrhs, rc_pad)`` shards.

        Returns ``(x, iters, rel)`` with ``x`` shaped like ``b`` and
        ``iters``/``rel`` per-RHS ``(nrhs,)`` (replicated across shards).
        Default: compose the loop hooks into one fused ``while_loop``.
        """
        aux, state = self.loop_setup(ctx, b, tol, maxiter)
        with jax.named_scope("krylov"):
            state = jax.lax.while_loop(
                lambda s: self.loop_cond(ctx, aux, s),
                lambda s: self.loop_body(ctx, aux, s), state)
        return self.loop_finish(ctx, aux, state)


_SOLVERS: dict[str, Solver] = {}


def register_solver(solver: Solver, overwrite: bool = False) -> Solver:
    """Register ``solver`` under ``solver.name`` for lookup by name."""
    if not solver.name:
        raise ValueError("a Solver needs a non-empty name")
    if solver.name in _SOLVERS and not overwrite:
        raise ValueError(f"solver {solver.name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    _SOLVERS[solver.name] = solver
    return solver


def get_solver(solver: str | Solver) -> Solver:
    """Resolve a solver name (or pass through an instance)."""
    if isinstance(solver, Solver):
        return solver
    try:
        return _SOLVERS[solver]
    except KeyError:
        raise ValueError(f"unknown solver {solver!r}; available: "
                         f"{available_solvers()}") from None


def available_solvers() -> tuple[str, ...]:
    return tuple(sorted(_SOLVERS))


# --------------------------------------------------------------------- #
# batched vector layout helpers
# --------------------------------------------------------------------- #
def to_dist_batch(B, layout: dict, plan, dtype=None) -> jax.Array:
    """Stack ``(nrhs, n)`` global RHS columns into batched CG layout
    ``(n_node, n_core, nrhs, rc_pad)`` — sharded on the leading mesh axes,
    the RHS axis purely local."""
    from repro.core.spmv import to_dist
    return jnp.stack([to_dist(b, layout, plan, dtype=dtype) for b in B],
                     axis=2)


def from_dist_batch(xd: jax.Array, layout: dict, plan):
    """Inverse of :func:`to_dist_batch` -> ``(nrhs, n)`` numpy array."""
    import numpy as np

    from repro.core.spmv import from_dist
    xd = np.asarray(xd)
    return np.stack([from_dist(xd[:, :, j], layout, plan)
                     for j in range(xd.shape[2])])


# --------------------------------------------------------------------- #
# the factory
# --------------------------------------------------------------------- #
def make_solver(plan, mesh: jax.sharding.Mesh, *,
                solver: str | Solver = "cg",
                precond: str | Preconditioner = "jacobi",
                axis_names: tuple[str, str] = ("node", "core"),
                backend: str = "jnp", transport: str | None = None,
                neighbor_offsets: list[int] | None = None,
                wire_dtype: str | None = None,
                maxiter_static: int = 10_000,
                nrhs: int | None = None,
                A=None, layout: dict | None = None,
                options: dict | None = None,
                precond_options: dict | None = None):
    """Bundle plan + mesh + a registered solver/preconditioner pair into
    ``solve(b, tol=..., maxiter=...)`` running as one sharded program.

    ``nrhs=None`` (default): ``b`` is a single RHS in CG layout
    ``(n_node, n_core, rc_pad)`` and ``iters``/``rel`` are scalars — the
    ``make_fused_cg`` contract.  ``nrhs=k``: ``b`` is batched CG layout
    ``(n_node, n_core, k, rc_pad)`` (see :func:`to_dist_batch`) and
    ``iters``/``rel`` are per-RHS ``(k,)``; the whole batch is solved by
    one fused loop whose reductions are ``(·, k)``-stacked — one plan, one
    compiled program, k tenants.

    ``A``/``layout`` (the host matrix and the layout dict from
    ``build_spmv_plan``) are only needed by build-time host work:
    ``precond="block_jacobi"`` extracts and inverts each core's diagonal
    block, ``solver="chebyshev"`` estimates eigenvalue bounds when
    ``options`` does not pin ``lmin``/``lmax``.

    ``transport`` selects the halo exchange by name
    (``repro.core.transport``; ``None`` follows the plan's stamp,
    ``"auto"`` autotunes the SpMV on this mesh first and uses the stamped
    winner — exposed as ``solve.transport``).  ``wire_dtype`` selects the
    halo wire codec ('f32' | 'bf16' | 'int8'; ``None`` follows
    ``plan.wire_dtype`` — exposed as ``solve.wire_dtype``).

    The operator (plan fields, transport extras, preconditioner data and
    the mask) is placed on ``mesh`` once, here, and passed to the program
    as arguments.  ``solve.jitted`` (a :class:`repro.util.BoundJit`)
    exposes the program, called and lowered as ``(b, tol, maxiter)``, for
    HLO inspection — ``repro.util.while_body_collective_counts`` on it
    yields the per-iteration collective census.
    """
    from repro.core.spmv import (make_shard_body, plan_fields,
                                 plan_shard_arrays)

    # resolve every name FIRST: an unknown solver/precond must raise the
    # registry's ValueError (listing what is registered) before any
    # expensive work — in particular before transport="auto" spends
    # seconds compiling and timing candidate SpMVs it will throw away
    sol = get_solver(solver)
    pre = get_precond(precond)
    # validate precond options just as early — an unknown coarse-space
    # option (e.g. two_level's agg_size/smoother) must raise the
    # ValueError listing valid names before autotune or any compile
    pre.validate_options(precond_options)
    transport = transport if transport is not None else plan.transport
    if transport == "auto":     # explicit, or a deferred plan stamp
        from repro.core.transport import autotune_transport
        transport = autotune_transport(
            plan, mesh, axis_names=axis_names, backend=backend,
            neighbor_offsets=neighbor_offsets,
            wire_dtype=wire_dtype).winner
    node_ax, core_ax = axis_names
    axes = tuple(axis_names)
    body = make_shard_body(plan, axis_names=axis_names, backend=backend,
                           transport=transport,
                           neighbor_offsets=neighbor_offsets,
                           wire_dtype=wire_dtype)
    fields = plan_fields(plan) + tuple(body.extra)
    pdata, papply = pre.bind(plan, layout=layout, A=A,
                             axis_names=axis_names, backend=backend,
                             options=precond_options)
    pnames = tuple(pdata)
    opts = sol.prepare(plan, pre, pdata, A=A, layout=layout, options=options)
    batched = nrhs is not None

    def shard_solve(*args):
        consts = args[:len(fields)]
        pvals = args[len(fields):len(fields) + len(pnames)]
        mask, b, tol, maxiter = args[len(fields) + len(pnames):]
        F = {k: v[0, 0] for k, v in zip(fields, consts)}
        Pd = {k: v[0, 0] for k, v in zip(pnames, pvals)}
        mask, b = mask[0, 0], b[0, 0]
        if not batched:
            b = b[None]                     # (1, rc_pad)

        @jax.named_scope("precond")
        def precond(r):
            return papply(Pd, r)

        ctx = SolverCtx(
            spmv=jax.vmap(lambda v: body(F, v)),
            precond=precond,
            mask=mask, axes=axes, maxiter_static=maxiter_static,
            options=opts)
        x, iters, rel = sol.shard_loop(ctx, b * mask, tol, maxiter)
        if not batched:
            x, iters, rel = x[0], iters[0], rel[0]
        # iters/rel are replicated on all shards
        return x[None, None], iters, rel

    spec = P(node_ax, core_ax)
    n_consts = len(fields) + len(pnames) + 1        # + mask
    out_specs = (spec, P(), P())
    fn = shard_map_compat(
        shard_solve, mesh=mesh,
        in_specs=(spec,) * n_consts + (spec, P(), P()),
        out_specs=out_specs)

    jitted = BoundJit(fn, place_operands(
        plan_shard_arrays(plan) + tuple(body.extra.values())
        + tuple(pdata[k] for k in pnames) + (plan.mask,), mesh, axis_names),
        mesh, out_specs)

    def solve(b: jax.Array, tol: float = 1e-8, maxiter: int = 10_000):
        return jitted(b, jnp.asarray(tol, jnp.float32),
                      jnp.asarray(maxiter, jnp.int32))

    solve.jitted = jitted
    solve.solver = sol.name
    solve.precond = pre.name
    solve.transport = body.transport
    solve.wire_dtype = body.wire_dtype
    solve.options = opts
    return solve


def make_precond_apply(plan, mesh: jax.sharding.Mesh, *,
                       precond: str | Preconditioner = "jacobi",
                       axis_names: tuple[str, str] = ("node", "core"),
                       backend: str = "jnp",
                       A=None, layout: dict | None = None,
                       precond_options: dict | None = None):
    """Jitted standalone preconditioner application on the live mesh:
    ``apply(rd) -> zd`` over CG-layout ``(n_node, n_core, rc_pad)``.

    The same ``bind`` + sharded-region composition ``make_solver`` uses,
    without a Krylov loop around it — what the ``precond_check``
    conformance harness sweeps against each preconditioner's numpy
    ``host_apply`` oracle.  Carries ``apply.precond`` (resolved name).
    """
    pre = get_precond(precond)
    pre.validate_options(precond_options)
    pdata, papply = pre.bind(plan, layout=layout, A=A,
                             axis_names=axis_names, backend=backend,
                             options=precond_options)
    pnames = tuple(pdata)
    node_ax, core_ax = axis_names

    def shard_apply(*args):
        pvals = args[:len(pnames)]
        rd = args[len(pnames)]
        Pd = {k: v[0, 0] for k, v in zip(pnames, pvals)}
        z = papply(Pd, rd[0, 0][None])      # (1, rc_pad) residual block
        return z[0][None, None]

    spec = P(node_ax, core_ax)
    fn = shard_map_compat(shard_apply, mesh=mesh,
                          in_specs=(spec,) * (len(pnames) + 1),
                          out_specs=spec)

    apply = BoundJit(fn, place_operands(
        tuple(pdata[k] for k in pnames), mesh, axis_names), mesh, spec)
    apply.precond = pre.name
    return apply
