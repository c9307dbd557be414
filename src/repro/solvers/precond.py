"""Preconditioner registry: ``none``, ``jacobi``, ``block_jacobi``,
``two_level``.

A preconditioner has two lives:

  * **build time** (host, once per plan): ``bind(plan, layout, A,
    options=...)`` turns whatever host-side information it needs into
    ``(pdata, apply_fn)`` — a dict of device arrays with leading
    ``(n_node, n_core)`` shard dims, which ``make_solver`` threads into
    the sharded region alongside the plan fields, plus the apply closure
    (for simple preconditioners ``bind`` just pairs the legacy
    ``build``/``apply`` methods);
  * **solve time** (device, per iteration): ``apply_fn(P, r)`` maps the
    residual block ``(nrhs, rc_pad)`` to ``z = M^-1 r``.  Preconditioners
    declaring ``local_only=True`` must not communicate — the PETSc
    block-Jacobi design point (PCBJACOBI applies one local solve per
    process and lets the Krylov loop do all the talking), proven by the
    static verifier.  Non-local preconditioners (``two_level``) declare
    ``local_only=False`` plus ``reductions_per_apply`` — the number of
    *reduction* collectives (all-reduce / reduce-scatter) one apply emits,
    which the verifier checks against the traced jaxpr so the solver
    collective census (DESIGN §9/§12) extends instead of breaking.

``jacobi``       1/diag(A), the paper's Sec. 3 preconditioner (ported from
                 ``repro.core.cg.jacobi_inverse``, which now re-exports
                 from here).
``block_jacobi`` each core's diagonal block — the rows this core's bin owns
                 restricted to its own columns — is extracted on the host,
                 densified, inverted, and applied as one small matmul per
                 shard.  Strictly stronger than ``jacobi`` (fewer
                 iterations) at zero extra communication; the analogue of
                 PETSc's default PCBJACOBI+ILU at subdomain size = core bin.
``two_level``    additive-Schwarz two-level: M⁻¹ = B_smoother +
                 P·A_c⁻¹·R with an unsmoothed-aggregation 0/1 restriction
                 R (contiguous aggregates of ``agg_size`` rows — vertical
                 mesh columns under the extrusion-major ordering),
                 prolongation P = Rᵀ, and the Galerkin coarse operator
                 A_c = R·A·P assembled + densely inverted on the host and
                 solved redundantly per shard.  R and P execute as
                 **rectangular SpMV plans through the same shard body**
                 as A itself, their shared spaces pinned to A's exact
                 slot layout; the coarse residual is replicated by two
                 ``all_gather``\\ s (core then node), so one apply emits
                 gathers/permutes only — zero reductions — keeping every
                 solver's reductions-per-iteration census unchanged.
                 With ``agg_size`` fixed the coarse space grows with N
                 and the preconditioned condition number stays bounded,
                 so CG iteration counts stay flat under mesh refinement
                 where one-level block-Jacobi grows (DESIGN §15).
``none``         identity, for unpreconditioned baselines.

``host_apply`` returns a plain numpy ``(n,) -> (n,)`` application of the
same operator in *global* row ordering — used by Chebyshev's host-side
eigenvalue estimation (which needs to run M^-1 A without a device mesh)
and as the oracle the ``repro.testing.precond_check`` conformance
harness sweeps every registered preconditioner against.

``validate_options`` runs **before** any autotune/compile in
``make_solver`` — an unknown or ill-typed option fails fast, listing the
valid names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import CSRMatrix
from repro.util import host_array

__all__ = ["jacobi_inverse", "jacobi_inverse_np", "Preconditioner",
           "NonePrecond", "JacobiPrecond", "BlockJacobiPrecond",
           "TwoLevelPrecond", "FaultyPrecond",
           "register_precond", "unregister_precond", "get_precond",
           "available_preconds"]


def jacobi_inverse(diag_a: jax.Array, mask: jax.Array) -> jax.Array:
    """Safe 1/diag(A) on valid rows, 0 on padding.

    A zero diagonal entry under the mask would make ``jnp.where(mask > 0,
    1/diag, 0)`` evaluate ``1/0 = inf`` on the taken branch (``where`` does
    not short-circuit), silently NaN-ing the whole solve.
    ``build_spmv_plan`` rejects such matrices up front; this guard keeps the
    preconditioner finite even for hand-built plans.
    """
    valid = (mask > 0) & (diag_a != 0)
    return jnp.where(valid, 1.0 / jnp.where(valid, diag_a, 1.0), 0.0)


def jacobi_inverse_np(diag_a: np.ndarray) -> np.ndarray:
    """Numpy twin of :func:`jacobi_inverse` (host oracles, host_apply)."""
    d = np.asarray(diag_a, dtype=np.float64)
    valid = d != 0
    return np.where(valid, 1.0 / np.where(valid, d, 1.0), 0.0)


class Preconditioner:
    """Interface of a registered preconditioner (see module docstring)."""

    name: str = ""
    #: the PCBJACOBI design point as a checkable contract: ``apply`` must
    #: not communicate.  The static verifier (``repro.analysis``) traces
    #: ``apply`` under the mesh axis environment and errors on any
    #: collective primitive while this is True; a future communicating
    #: preconditioner (e.g. an additive-Schwarz coarse solve) declares
    #: itself by setting it False, which also tells the Krylov census to
    #: attribute its collectives separately.
    local_only: bool = True
    #: reduction collectives (all-reduce / reduce-scatter) one apply
    #: emits.  Only meaningful for ``local_only=False`` preconditioners;
    #: the static verifier traces ``apply`` and errors on a mismatch
    #: (``J_PRECOND_REDUCTIONS``), so the per-solver reductions/iter
    #: census stays exact with any registered preconditioner composed in.
    reductions_per_apply: int = 0
    #: option names ``validate_options`` accepts (default: none).
    valid_options: tuple[str, ...] = ()

    def validate_options(self, options: dict | None = None) -> dict:
        """Validate build options *before* any autotune/compile.

        Raises ``ValueError`` naming the valid options on an unknown key;
        returns the normalised option dict.  Subclasses with real options
        override this to type-check values too.
        """
        options = dict(options or {})
        unknown = sorted(set(options) - set(self.valid_options))
        if unknown:
            valid = list(self.valid_options) or "(none)"
            raise ValueError(
                f"{self.name or type(self).__name__}: unknown option(s) "
                f"{unknown}; valid options: {valid}")
        return options

    def build(self, plan, layout: dict | None = None, A=None
              ) -> dict[str, jax.Array]:
        """Host-side setup -> dict of ``(n_node, n_core, ...)`` arrays."""
        return {}

    def bind(self, plan, layout: dict | None = None, A=None, *,
             axis_names: tuple[str, str] = ("node", "core"),
             backend: str = "jnp", options: dict | None = None):
        """Host-side setup -> ``(pdata, apply_fn)``.

        The general entry point ``make_solver`` (and the analyzer) use:
        validates ``options``, then returns the device arrays plus the
        apply closure.  The default pairs the legacy ``build``/``apply``
        methods; preconditioners whose apply needs plan-derived structure
        beyond ``pdata`` (``two_level``'s rectangular R/P shard bodies)
        override it.
        """
        self.validate_options(options)
        return self.build(plan, layout=layout, A=A), self.apply

    def apply(self, P: dict[str, jax.Array], r: jax.Array) -> jax.Array:
        """Shard-local ``z = M^-1 r`` on ``(nrhs, rc_pad)`` blocks.

        ``P`` holds this shard's slices of the ``build`` arrays (leading
        shard dims already stripped).  Must not communicate.
        """
        raise NotImplementedError

    def host_apply(self, plan, layout: dict | None, A):
        """Numpy ``(n,) -> (n,)`` global-ordering application of M^-1."""
        raise NotImplementedError


class NonePrecond(Preconditioner):
    """Identity — unpreconditioned Krylov baselines."""

    name = "none"

    def apply(self, P, r):
        return r

    def host_apply(self, plan, layout, A):
        return lambda r: r


class JacobiPrecond(Preconditioner):
    """Point Jacobi: z = r / diag(A) (paper Sec. 3)."""

    name = "jacobi"

    def build(self, plan, layout=None, A=None):
        return {"m_inv": jacobi_inverse(plan.diag_a, plan.mask)}

    def apply(self, P, r):
        return P["m_inv"] * r        # (rc_pad,) broadcasts over (nrhs, rc_pad)

    def host_apply(self, plan, layout, A):
        inv = jacobi_inverse_np(A.diagonal())
        return lambda r: inv * r


def _core_block_inverses(layout: dict, A):
    """Dense f64 inverse of every core bin's diagonal block of ``A``.

    Yields ``(i, c, rows, inv)`` per non-empty bin: ``rows`` the bin's
    global row range (two-level partitions keep bins contiguous) and
    ``inv`` the inverse in ascending-global-row order.  Each block is a
    principal submatrix of A, so SPD inputs stay invertible.
    """
    if layout is None or A is None:
        raise ValueError("block_jacobi needs the host matrix and layout: "
                         "make_solver(..., A=A, layout=layout)")
    node_bounds = np.asarray(layout["node_bounds"], dtype=np.int64)
    for i, cb in enumerate(layout["core_bounds"]):
        lo = int(node_bounds[i])
        for c in range(len(cb) - 1):
            blo, bhi = lo + int(cb[c]), lo + int(cb[c + 1])
            nb = bhi - blo
            if nb == 0:
                continue
            block = np.zeros((nb, nb))
            for bl in range(nb):
                s, e = A.indptr[blo + bl], A.indptr[blo + bl + 1]
                cols = A.indices[s:e]
                m = (cols >= blo) & (cols < bhi)
                block[bl, cols[m] - blo] += A.data[s:e][m]
            yield i, c, (blo, bhi), np.linalg.inv(block)


class BlockJacobiPrecond(Preconditioner):
    """Shard-local dense inverse of each core's diagonal block (PCBJACOBI).

    ``build`` stores ``binv`` as ``(n_node, n_core, rc_pad, rc_pad)`` in the
    plan's slot ordering (format row permutations folded in via
    ``layout["global_row_of"]``); padding rows/columns are zero so the
    application keeps padding slots at exactly 0.
    """

    name = "block_jacobi"

    def build(self, plan, layout=None, A=None):
        g_of = np.asarray(layout["global_row_of"]) if layout else None
        binv = np.zeros((plan.n_node, plan.n_core, plan.rc_pad, plan.rc_pad))
        for i, c, (blo, bhi), inv in _core_block_inverses(layout, A):
            slots = np.flatnonzero(g_of[i, c] >= 0)
            bl = g_of[i, c, slots] - blo      # bin-local row of each slot
            binv[i, c, slots[:, None], slots[None, :]] = inv[np.ix_(bl, bl)]
        return {"binv": host_array(binv, dtype=plan.mask.dtype)}

    def apply(self, P, r):
        binv = P["binv"]                      # (rc_pad, rc_pad)
        return jnp.einsum("ij,nj->ni", binv,
                          r.astype(binv.dtype)).astype(r.dtype)

    def host_apply(self, plan, layout, A):
        blocks = [(rows, inv)
                  for _, _, rows, inv in _core_block_inverses(layout, A)]

        def apply(r):
            z = np.zeros_like(r, dtype=np.float64)
            for (blo, bhi), inv in blocks:
                z[blo:bhi] = inv @ r[blo:bhi]
            return z

        return apply


class TwoLevelPrecond(Preconditioner):
    """Two-level additive Schwarz: M⁻¹ = B_smoother + P·A_c⁻¹·R.

    R is unsmoothed aggregation — a 0/1 restriction summing contiguous
    runs of ``agg_size`` fine rows (vertical mesh columns under the
    extrusion-major ordering, so aggregates are spatially local); P = Rᵀ.
    Both execute as **rectangular SpMV plans through the same shard body**
    as the fine operator: R's column space and P's row space are pinned to
    A's exact row layout (``layout["row_space"]``, σ-permutations and
    all), and P's column space is pinned to R's row space so the coarse
    layouts coincide.  A_c = R·A·P is assembled on the host (Galerkin,
    SPD for SPD A since R has full row rank), densely inverted, and the
    inverse replicated to every shard — the coarse solve is redundant,
    the classic small-coarse-grid trade.

    One apply = smoother apply (shard-local) + R matvec + two
    ``all_gather``\\ s replicating the coarse residual + dense coarse
    solve + P matvec.  Gathers and permutes only — **zero reduction
    collectives** (``reductions_per_apply = 0``), so every solver's
    reductions-per-iteration census is unchanged with ``two_level``
    composed in.

    Options: ``agg_size`` (int >= 2, default 16) — fine rows per
    aggregate; ``smoother`` — name of any registered *local*
    preconditioner (default ``block_jacobi``).
    """

    name = "two_level"
    local_only = False
    reductions_per_apply = 0
    valid_options = ("agg_size", "smoother")

    DEFAULT_AGG_SIZE = 16
    DEFAULT_SMOOTHER = "block_jacobi"

    def validate_options(self, options=None):
        opts = super().validate_options(options)
        agg = opts.setdefault("agg_size", self.DEFAULT_AGG_SIZE)
        if not isinstance(agg, (int, np.integer)) or isinstance(agg, bool) \
                or agg < 2:
            raise ValueError(f"two_level: agg_size must be an int >= 2, "
                             f"got {agg!r}")
        sm = opts.setdefault("smoother", self.DEFAULT_SMOOTHER)
        local = [p for p in available_preconds()
                 if _PRECONDS[p].local_only and p != self.name]
        if sm not in local:
            raise ValueError(f"two_level: smoother must be a registered "
                             f"local preconditioner, one of {local}; "
                             f"got {sm!r}")
        opts["agg_size"] = int(agg)
        return opts

    # ------------------------------------------------------------------ #
    @staticmethod
    def _aggregates(n: int, agg_size: int) -> tuple[np.ndarray, int]:
        agg_of = np.arange(n, dtype=np.int64) // agg_size
        return agg_of, int(agg_of[-1]) + 1

    @staticmethod
    def _galerkin_inverse(A, agg_of: np.ndarray, nc: int) -> np.ndarray:
        """Dense f64 (R A Rᵀ)⁻¹ — A_c[a, b] = Σ A[i, j] over aggregate
        pairs; SPD for SPD A, so the dense inverse is safe."""
        rows_of = np.repeat(np.arange(A.n_rows, dtype=np.int64), A.row_nnz)
        Ac = np.zeros((nc, nc))
        np.add.at(Ac, (agg_of[rows_of], agg_of[A.indices]),
                  A.data.astype(np.float64))
        return np.linalg.inv(Ac)

    def bind(self, plan, layout=None, A=None, *,
             axis_names=("node", "core"), backend="jnp", options=None):
        opts = self.validate_options(options)
        if layout is None or A is None:
            raise ValueError("two_level needs the host matrix and layout: "
                             "make_solver(..., A=A, layout=layout)")
        if plan.n_cols != plan.n:
            raise ValueError("two_level preconditions square operators; "
                             f"got plan shape ({plan.n}, {plan.n_cols})")
        # late import: solvers sits above core in the layering
        from repro.core.spmv import (build_spmv_plan, make_shard_body,
                                     plan_fields, plan_shard_arrays)

        smoother = _PRECONDS[opts["smoother"]]
        pdata = dict(smoother.build(plan, layout=layout, A=A))

        n, n_node, n_core = plan.n, plan.n_node, plan.n_core
        agg_of, nc = self._aggregates(n, opts["agg_size"])
        ones = np.ones(n, dtype=np.float64)
        R = CSRMatrix.from_coo(agg_of, np.arange(n, dtype=np.int64), ones,
                               (nc, n))
        # R: coarse rows freely partitioned, columns pinned to A's rows.
        # P = Rᵀ: rows pinned to A's rows (the apply's output layout),
        # columns pinned to R's rows (the shared coarse layout).
        plan_R, layout_R = build_spmv_plan(
            R, n_node, n_core, mode="balanced", node_partition="nnz",
            format="ell", transport="a2a", col_space=layout["row_space"])
        plan_P, layout_P = build_spmv_plan(
            R.transpose(), n_node, n_core, mode="balanced",
            node_partition="nnz", format="ell", transport="a2a",
            row_space=layout["row_space"], col_space=layout_R["row_space"])

        dtype = plan.mask.dtype
        ainv = self._galerkin_inverse(A, agg_of, nc)
        pdata["ainv_c"] = host_array(
            np.broadcast_to(ainv, (n_node, n_core, nc, nc)), dtype=dtype)

        # global coarse id -> flat slot of the core+node-gathered R output
        gR = np.asarray(layout_R["global_row_of"])
        ii, cc, ss = np.nonzero(gR >= 0)
        coarse_gather = np.zeros(nc, dtype=np.int32)
        coarse_gather[gR[ii, cc, ss]] = \
            ((ii * n_core + cc) * plan_R.rc_pad + ss).astype(np.int32)
        pdata["coarse_gather"] = np.ascontiguousarray(
            np.broadcast_to(coarse_gather, (n_node, n_core, nc)))

        # per-shard map from the replicated coarse vector into P's input
        # (column-space) layout; padding slots read an appended zero
        gPc = np.asarray(layout_P["global_col_of"])
        pdata["p_col_map"] = np.where(gPc >= 0, gPc, nc).astype(np.int32)

        body_R = make_shard_body(plan_R, axis_names=axis_names,
                                 backend=backend)
        body_P = make_shard_body(plan_P, axis_names=axis_names,
                                 backend=backend)
        R_names = tuple(plan_fields(plan_R)) + tuple(body_R.extra)
        P_names = tuple(plan_fields(plan_P)) + tuple(body_P.extra)
        for nm, arr in zip(plan_fields(plan_R), plan_shard_arrays(plan_R)):
            pdata["R__" + nm] = arr
        for nm, arr in body_R.extra.items():
            pdata["R__" + nm] = arr
        for nm, arr in zip(plan_fields(plan_P), plan_shard_arrays(plan_P)):
            pdata["P__" + nm] = arr
        for nm, arr in body_P.extra.items():
            pdata["P__" + nm] = arr

        node_ax, core_ax = axis_names
        s_apply = smoother.apply

        def apply_fn(P, r):
            z = s_apply(P, r)
            F_R = {f: P["R__" + f] for f in R_names}
            F_P = {f: P["P__" + f] for f in P_names}

            def coarse_correction(v):
                rc = body_R(F_R, v)                       # (rc_pad_R,)
                full = jax.lax.all_gather(rc, core_ax, axis=0)
                full = jax.lax.all_gather(full, node_ax, axis=0)
                r_c = full.reshape(-1)[P["coarse_gather"]]  # (nc,)
                y_c = P["ainv_c"] @ r_c                     # redundant solve
                y_ext = jnp.concatenate(
                    [y_c, jnp.zeros((1,), y_c.dtype)])
                return body_P(F_P, y_ext[P["p_col_map"]])   # (rc_pad,)

            zc = jax.vmap(coarse_correction)(r.astype(dtype))
            return z + zc.astype(r.dtype)

        return pdata, apply_fn

    def host_apply(self, plan, layout, A, options: dict | None = None):
        opts = self.validate_options(options)
        smoother = _PRECONDS[opts["smoother"]].host_apply(plan, layout, A)
        agg_of, nc = self._aggregates(A.n_rows, opts["agg_size"])
        ainv = self._galerkin_inverse(A, agg_of, nc)

        def apply(r):
            z = np.asarray(smoother(r), dtype=np.float64)
            rc = np.bincount(agg_of, weights=np.asarray(r, np.float64),
                             minlength=nc)
            return z + (ainv @ rc)[agg_of]

        return apply


class FaultyPrecond(JacobiPrecond):
    """Deliberately broken preconditioner — **not** registered by default.

    Claims to be plain Jacobi (``local_only=True``, symmetric
    ``host_apply``) but its device ``apply`` negates the result, making
    M⁻¹ indefinite and device/host inconsistent.  Registering it must
    make the ``repro.testing.precond_check`` conformance suite fail —
    the proof the harness catches a broken registrant rather than
    trusting declarations (``--include-faulty`` must exit nonzero).
    """

    name = "faulty"

    def apply(self, P, r):
        return -(P["m_inv"] * r)


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #
_PRECONDS: dict[str, Preconditioner] = {}


def register_precond(pre: Preconditioner,
                     overwrite: bool = False) -> Preconditioner:
    """Register ``pre`` under ``pre.name`` for lookup by name."""
    if not pre.name:
        raise ValueError("a Preconditioner needs a non-empty name")
    if pre.name in _PRECONDS and not overwrite:
        raise ValueError(f"preconditioner {pre.name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    _PRECONDS[pre.name] = pre
    return pre


def unregister_precond(name: str) -> None:
    """Remove a registered preconditioner (testing hook — the conformance
    harness registers/unregisters the faulty exemplar around its sweep)."""
    _PRECONDS.pop(name, None)


def get_precond(pre: str | Preconditioner) -> Preconditioner:
    """Resolve a preconditioner name (or pass through an instance)."""
    if isinstance(pre, Preconditioner):
        return pre
    try:
        return _PRECONDS[pre]
    except KeyError:
        raise ValueError(f"unknown preconditioner {pre!r}; available: "
                         f"{available_preconds()}") from None


def available_preconds() -> tuple[str, ...]:
    return tuple(sorted(_PRECONDS))


register_precond(NonePrecond())
register_precond(JacobiPrecond())
register_precond(BlockJacobiPrecond())
register_precond(TwoLevelPrecond())
