"""Preconditioner conformance harness, run as a subprocess from tests.

Usage:  python -m repro.testing.precond_check --n-node 4 --n-core 2 \
            --case graded

Every *registered* preconditioner (``repro.solvers.precond``) is swept on
the same plan — a preconditioner nobody listed still gets checked, so
registering one that breaks conformance is a test failure, not a runtime
surprise.  Five checks per (case, format, preconditioner):

  host    the device ``make_precond_apply`` program (the exact ``bind`` +
          sharded-region composition ``make_solver`` runs) reproduces the
          preconditioner's numpy ``host_apply`` oracle in global row
          ordering (f32 device vs f64 host, relative tolerance);
  sym     M⁻¹ is symmetric on an SPD operator — v·M⁻¹w == w·M⁻¹v on the
          f64 host oracle (tight) and through the device program (fp
          tolerance).  CG's convergence theory assumes an SPD M⁻¹, so an
          asymmetric apply is a silent correctness bug;
  spd     r·M⁻¹r > 0 for random r (definiteness, same CG contract);
  static  the collective contract is *proven*, not trusted:
          ``check_precond_static`` traces apply under the mesh axis
          environment — ``local_only`` preconds must be collective-free,
          non-local ones must emit exactly their declared
          ``reductions_per_apply`` reduction collectives;
  cross   (``two_level`` only) the device apply decomposes as
          smoother + coarse correction: z_2l == z_smoother +
          P·A_c⁻¹·R r with the coarse term recomputed independently on
          the host from the aggregation — catching a wrong R/P wiring
          that still happens to look symmetric.

``--include-faulty`` registers the deliberately broken ``FaultyPrecond``
(device apply negates Jacobi — indefinite and host-inconsistent, while
still truthfully local); the harness is then EXPECTED to fail it (rc 1),
which is the proof the suite catches a broken registrant.

``--scaling`` runs the iteration-scaling regression instead of the
conformance sweep: CG on a sequence of growing graded extruded meshes,
asserting one-level ``block_jacobi`` iteration counts grow from the
smallest to the largest mesh while ``two_level`` stays flat (max/min <=
--flat-bound, default 1.3) and below it on every mesh — the
bounded-condition-number claim of DESIGN §15.  Emits
one ``SCALING {json}`` line with per-mesh iters and solve times.

Plan cases reuse the transport harness's builders: ``graded``
(non-uniform two-level node bounds + halo), ``single`` (banded extrusion
ordering), ``halofree`` (one node owns everything — no exchange; proves
local preconds need no halo machinery at all).

Sets XLA_FLAGS *before* importing jax so the host platform exposes
n_node * n_core fake devices — only inside this process.
"""
import argparse
import json
import os
import sys
import time

CASES = ("graded", "single", "halofree")

#: device-vs-host relative tolerance: the device program runs f32 with
#: fp32 gathers/matmuls against an f64 host oracle (measured ~2e-7 on
#: the conformance cases; 5e-4 leaves room for unlucky cancellation)
DEV_TOL = 5e-4
SYM_TOL_HOST = 1e-10
SYM_TOL_DEV = 2e-3


def _rel(a, b):
    import numpy as np
    den = max(float(np.linalg.norm(b)), 1e-300)
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))) / den


def conformance(args) -> bool:
    import numpy as np

    from repro.analysis import check_precond_static
    from repro.core import from_dist, to_dist
    from repro.solvers import available_preconds, get_precond
    from repro.solvers.base import make_precond_apply
    from repro.solvers.precond import TwoLevelPrecond
    from repro.testing.transport_check import build_case
    from repro.util import make_mesh_compat

    preconds = (tuple(args.preconds.split(","))
                if args.preconds else available_preconds())
    ok = True

    for fmt in args.formats.split(","):
        A, plan, layout = build_case(args.case, args.n_node, args.n_core,
                                     fmt)
        mesh = make_mesh_compat((plan.n_node, plan.n_core),
                                ("node", "core"))
        rng = np.random.default_rng(11)
        r = rng.normal(size=A.n_rows)
        v = rng.normal(size=A.n_rows)
        print(f"CASE {args.case} FORMAT {fmt} n={A.n_rows} "
              f"n_node={plan.n_node} n_core={plan.n_core} hs={plan.hs}")

        for pname in preconds:
            pre = get_precond(pname)
            line = [f"PRECOND {pname}"]

            apply_d = make_precond_apply(plan, mesh, precond=pname,
                                         A=A, layout=layout)
            host = pre.host_apply(plan, layout, A)

            def dev(u):
                return np.asarray(from_dist(
                    apply_d(to_dist(u, layout, plan)), layout, plan),
                    dtype=np.float64)

            # host: device program == numpy oracle (global ordering)
            zr_d, zr_h = dev(r), np.asarray(host(r), np.float64)
            e = _rel(zr_d, zr_h)
            h_ok = e <= DEV_TOL
            line.append(f"host={e:.2e}<={DEV_TOL:.0e}="
                        f"{'ok' if h_ok else 'BAD'}")

            # sym: v.(M^-1 r) == r.(M^-1 v), host tight + device fp
            zv_h = np.asarray(host(v), np.float64)
            sh = abs(float(v @ zr_h) - float(r @ zv_h)) / max(
                abs(float(v @ zr_h)), 1e-300)
            zv_d = dev(v)
            sd = abs(float(v @ zr_d) - float(r @ zv_d)) / max(
                abs(float(v @ zr_d)), 1e-300)
            s_ok = sh <= SYM_TOL_HOST and sd <= SYM_TOL_DEV
            line.append(f"sym={sh:.1e}/{sd:.1e}="
                        f"{'ok' if s_ok else 'BAD'}")

            # spd: r.(M^-1 r) > 0 ("none" included: identity is SPD)
            quad = float(r @ zr_d)
            p_ok = quad > 0.0
            line.append(f"spd={quad:.3g}={'ok' if p_ok else 'BAD'}")

            # static: the declared collective contract, proven by trace
            rep = check_precond_static(plan, pname, A=A, layout=layout)
            c_ok = rep.ok()
            line.append(f"static[{'local' if pre.local_only else 'comm'}]"
                        f"={'ok' if c_ok else 'BAD'}")
            ok &= h_ok and s_ok and p_ok and c_ok

            # cross: two_level decomposes into smoother + host coarse term
            if pname == "two_level":
                opts = pre.validate_options(None)
                sm_d = make_precond_apply(plan, mesh,
                                          precond=opts["smoother"],
                                          A=A, layout=layout)
                zs = np.asarray(from_dist(
                    sm_d(to_dist(r, layout, plan)), layout, plan),
                    np.float64)
                agg_of, nc = TwoLevelPrecond._aggregates(
                    A.n_rows, opts["agg_size"])
                ainv = TwoLevelPrecond._galerkin_inverse(A, agg_of, nc)
                rc = np.bincount(agg_of, weights=r, minlength=nc)
                e2 = _rel(zr_d, zs + (ainv @ rc)[agg_of])
                x_ok = e2 <= DEV_TOL
                line.append(f"cross={e2:.2e}={'ok' if x_ok else 'BAD'}")
                ok &= x_ok
            print(" ".join(line))
    return ok


#: the regression meshes: graded extruded (48, L) at growing layer
#: counts — same surface, 2x rows per step, the strong-scaling family
SCALING_MESHES = ((48, 6), (48, 12), (48, 24))

#: aggregate size for the regression: 8 fine rows per aggregate keeps
#: the coarse space proportional to n, which is what bounds the
#: preconditioned condition number (measured flat at 24/26/25 iters
#: where block_jacobi grows 43/37/52; the generic default of 16 also
#: stays bounded but drifts closer to the 1.3x gate on this family)
SCALING_AGG = 8


def scaling(args) -> bool:
    import numpy as np

    from repro.core import build_spmv_plan, to_dist
    from repro.solvers import make_solver
    from repro.sparse import graded_extruded_mesh_matrix
    from repro.util import make_mesh_compat

    mesh = make_mesh_compat((args.n_node, args.n_core), ("node", "core"))
    out = {"meshes": [], "block_jacobi": {"iters": [], "time_s": []},
           "two_level": {"iters": [], "time_s": []}}
    for n_surface, layers in SCALING_MESHES:
        A = graded_extruded_mesh_matrix(n_surface, layers, seed=0)
        plan, layout = build_spmv_plan(A, args.n_node, args.n_core,
                                       mode="balanced",
                                       node_partition="rows", format="ell")
        rng = np.random.default_rng(7)
        bd = to_dist(rng.normal(size=A.n_rows), layout, plan)
        out["meshes"].append([n_surface, layers, A.n_rows])
        row = [f"n={A.n_rows}"]
        for pname in ("block_jacobi", "two_level"):
            po = {"agg_size": SCALING_AGG} if pname == "two_level" else None
            solve = make_solver(plan, mesh, solver="cg", precond=pname,
                                A=A, layout=layout, precond_options=po)
            _, it, _ = solve(bd, tol=1e-6, maxiter=400)   # compile+warm
            t0 = time.perf_counter()
            _, it, rel = solve(bd, tol=1e-6, maxiter=400)
            dt = time.perf_counter() - t0
            out[pname]["iters"].append(int(it))
            out[pname]["time_s"].append(round(dt, 4))
            row.append(f"{pname}: iters={int(it)} rel={float(rel):.1e} "
                       f"t={dt * 1e3:.0f}ms")
        print("  ".join(row))

    bj = out["block_jacobi"]["iters"]
    tl = out["two_level"]["iters"]
    # block_jacobi's f32 recursive residual stalls for ~10 iterations
    # just above 1e-6 on this family, so its count at tol 1e-6 moves by
    # ~10 with any change of rounding order (n=288: 33 at tol 1.2e-6,
    # 43 at 8e-7).  Monotone growth is reported, not gated: the verdict
    # is growth end to end, two_level flat, and two_level cheaper at
    # every size.
    mono = all(b >= a for a, b in zip(bj, bj[1:]))
    flat = max(tl) / min(tl)
    grow = bj[-1] > bj[0]
    below = all(t < b for t, b in zip(tl, bj))
    ok = grow and below and flat <= args.flat_bound
    out.update(bj_monotone=mono, bj_grows=grow, tl_below_bj=below,
               tl_flat_ratio=round(flat, 3), flat_bound=args.flat_bound,
               ok=ok)
    print(f"SCALING {json.dumps(out)}")
    print(f"block_jacobi iters {bj} growing={'ok' if grow else 'BAD'}; "
          f"two_level iters {tl} below={'ok' if below else 'BAD'} "
          f"max/min={flat:.2f}<={args.flat_bound}="
          f"{'ok' if flat <= args.flat_bound else 'BAD'}")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-node", type=int, default=4)
    ap.add_argument("--n-core", type=int, default=2)
    ap.add_argument("--case", default="graded", choices=CASES)
    ap.add_argument("--formats", default="ell,sell")
    ap.add_argument("--preconds", default=None,
                    help="comma list (default: every registered precond)")
    ap.add_argument("--include-faulty", action="store_true",
                    help="register the broken 'faulty' preconditioner "
                         "before the sweep; the harness is EXPECTED to "
                         "fail it (rc 1) — the proof it catches a broken "
                         "registrant")
    ap.add_argument("--scaling", action="store_true",
                    help="run the iteration-scaling regression instead "
                         "of the conformance sweep")
    ap.add_argument("--flat-bound", type=float, default=1.3,
                    help="two_level max/min iteration ratio bound across "
                         "the scaling meshes")
    args = ap.parse_args()

    ndev = args.n_node * args.n_core
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}")

    import jax
    assert len(jax.devices()) == ndev, (len(jax.devices()), ndev)

    if args.scaling:
        ok = scaling(args)
        print("OK" if ok else "FAIL")
        return 0 if ok else 1

    faulty = False
    if args.include_faulty:
        from repro.solvers.precond import FaultyPrecond, register_precond
        register_precond(FaultyPrecond())
        faulty = True
    try:
        ok = conformance(args)
    finally:
        if faulty:
            from repro.solvers.precond import unregister_precond
            unregister_precond("faulty")
    print("OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
