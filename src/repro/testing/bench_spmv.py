"""Measured SpMV / CG timing in a fresh process with N host devices.

Prints one JSON dict.  Used by benchmarks/ratio_sweep.py (paper Fig. 2) and
benchmarks/strong_scaling.py (Figs. 3-4).
"""
import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-node", type=int, required=True)
    ap.add_argument("--n-core", type=int, required=True)
    ap.add_argument("--mode", default="balanced")
    ap.add_argument("--node-partition", default=None,
                    choices=["rows", "nnz"],
                    help="node-axis row split (default: nnz for balanced "
                         "mode, rows otherwise)")
    ap.add_argument("--transport", default="a2a",
                    help="halo transport (repro.core.transport), 'auto' to "
                         "autotune, or a comma list to sweep (SpMV path "
                         "only): per-transport timings + census land in "
                         "the JSON under 'transports'")
    ap.add_argument("--format", default="ell",
                    help="shard storage format (repro.sparse.formats): "
                         "'ell' row-padded, 'sell' sliced ELL (SELL-C-σ)")
    ap.add_argument("--matrix", default="mesh", choices=["mesh", "graded"],
                    help="'graded' = skewed adapted-mesh analogue with "
                         "exponentially varying row nnz")
    ap.add_argument("--n-surface", type=int, default=2000)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=3,
                    help="SpMV sweep: executions of each *compiled* program "
                         "before timing starts (beyond the compile call), "
                         "so no transport pays first-run costs in its "
                         "timed passes")
    ap.add_argument("--reps", type=int, default=5,
                    help="SpMV sweep: timed repetitions of the --iters "
                         "loop per transport; us_per_spmv is their median, "
                         "us_per_spmv_min their (low-noise) min")
    ap.add_argument("--wire-dtype", default="f32",
                    help="halo wire codec (repro.core.transport: f32 | "
                         "bf16 | int8), or a comma list to sweep (SpMV "
                         "path only): per-dtype timings + predicted and "
                         "traced wire bytes land in the JSON under 'wire'")
    ap.add_argument("--cg", action="store_true")
    ap.add_argument("--fused", action="store_true",
                    help="with --cg: time the fully-sharded fused CG solver")
    ap.add_argument("--solver", default=None,
                    help="time a registered solver (repro.solvers) instead "
                         "of the historical --cg path; implies the fused "
                         "sharded loop")
    ap.add_argument("--precond", default="jacobi",
                    help="preconditioner for --solver (none | jacobi | "
                         "block_jacobi)")
    ap.add_argument("--nrhs", type=int, default=0,
                    help="with --solver: batched multi-RHS solve width")
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--check-every", type=int, default=0,
                    help="with --solver: also time the solve under the "
                         "resilient chunked driver (repro.solvers.resilient) "
                         "and report its per-iteration overhead vs the "
                         "monolithic loop under 'resilient'")
    ap.add_argument("--no-collectives", action="store_true",
                    help="skip the compiled-HLO collective-op census")
    args = ap.parse_args()

    from repro.util import enable_compile_cache, use_host_devices
    use_host_devices(args.n_node * args.n_core)
    enable_compile_cache()

    import jax
    import numpy as np

    from repro.core import build_spmv_plan, make_cg, make_spmv, to_dist
    from repro.sparse import extruded_mesh_matrix, graded_extruded_mesh_matrix

    t0 = time.time()
    gen = (graded_extruded_mesh_matrix if args.matrix == "graded"
           else extruded_mesh_matrix)
    A = gen(args.n_surface, args.layers, seed=0)
    t_gen = time.time() - t0
    from repro.util import make_mesh_compat
    mesh = make_mesh_compat((args.n_node, args.n_core), ("node", "core"))
    t0 = time.time()
    plan, layout = build_spmv_plan(A, args.n_node, args.n_core,
                                   mode=args.mode,
                                   node_partition=args.node_partition,
                                   format=args.format)
    t_plan = time.time() - t0

    rng = np.random.default_rng(0)
    x = to_dist(rng.normal(size=A.n_rows), layout, plan)

    stats = layout["stats"]
    out = {"n_node": args.n_node, "n_core": args.n_core, "mode": args.mode,
           "node_partition": layout["node_partition"],
           "format": layout["format"],
           "transport": args.transport, "matrix": args.matrix,
           "n_rows": A.n_rows, "nnz": A.nnz,
           "t_gen_s": round(t_gen, 2), "t_plan_s": round(t_plan, 3),
           "halo_bytes_per_node": plan_halo_bytes(layout),
           "node_imbalance": round(stats["node_imbalance"], 4),
           "core_imbalance": round(stats["core_imbalance"], 4),
           "padding_waste": round(stats["padding_waste"], 4),
           }

    if (args.solver or args.cg) and "," in args.transport:
        ap.error("--transport sweeps are SpMV-only; pick one transport "
                 "for --solver/--cg runs")
    if (args.solver or args.cg) and "," in args.wire_dtype:
        ap.error("--wire-dtype sweeps are SpMV-only; pick one wire dtype "
                 "for --solver/--cg runs")

    if args.solver:
        import jax.numpy as jnp

        from repro.solvers import make_solver
        from repro.solvers.base import to_dist_batch
        from repro.util import (census_split, collective_counts_from_text,
                                compiled_hlo_text,
                                while_body_collective_counts_from_text)

        nrhs = args.nrhs if args.nrhs > 1 else None
        solve = make_solver(plan, mesh, solver=args.solver,
                            precond=args.precond, transport=args.transport,
                            neighbor_offsets=layout["neighbor_offsets"],
                            wire_dtype=args.wire_dtype,
                            nrhs=nrhs, A=A, layout=layout)
        out["wire_dtype"] = solve.wire_dtype
        if nrhs:
            b_host = rng.normal(size=(nrhs, A.n_rows))
            b = to_dist_batch(b_host, layout, plan)
        else:
            b_host = rng.normal(size=A.n_rows)
            b = to_dist(b_host, layout, plan)
        xd, it, rel = solve(b, tol=args.tol, maxiter=200)  # warmup+compile
        jax.block_until_ready(xd)
        t0 = time.time()
        xd, it, rel = solve(b, tol=args.tol, maxiter=args.iters)
        jax.block_until_ready(xd)
        dt = time.time() - t0
        iters = int(np.max(np.asarray(it)))
        out.update(solver=args.solver, precond=args.precond,
                   transport=solve.transport,
                   nrhs=nrhs or 1, cg_iters=iters,
                   cg_rel=float(np.max(np.asarray(rel))),
                   us_per_iter=dt / max(iters, 1) * 1e6)
        if not args.no_collectives:
            # compile once, census twice (module-wide + while-body)
            txt = compiled_hlo_text(
                solve.jitted, b, jnp.asarray(args.tol, jnp.float32),
                jnp.asarray(args.iters, jnp.int32))
            out["collectives"] = collective_counts_from_text(txt)
            # exact per-iteration census: ops inside the while body only,
            # split into solver reductions vs transport traffic
            out["collectives_per_iter"] = \
                while_body_collective_counts_from_text(txt)
            out["census_split"] = census_split(out["collectives_per_iter"])
        if args.check_every > 0:
            from repro.solvers import make_resilient, resilient_solve

            # compile the three chunked programs once, then warm + time
            # through the same prebuilt object so the timed pass hits the
            # jit cache exactly like the monolithic pair above; the guard
            # thresholds are effectively disabled (we are timing the
            # chunking machinery, not exercising rollbacks — tol=1e-8 is
            # below the f32 floor, so every chunk looks "stagnant")
            rs = make_resilient(plan, mesh, solver=args.solver,
                                precond=args.precond,
                                transport=args.transport,
                                neighbor_offsets=layout["neighbor_offsets"],
                                wire_dtype=args.wire_dtype,
                                A=A, layout=layout)
            kw = dict(solver=args.solver, precond=args.precond, mesh=mesh,
                      layout=layout, A=None, tol=args.tol,
                      maxiter=args.iters, check_every=args.check_every,
                      stall_chunks=10**9, programs=rs)
            resilient_solve(plan, b_host, **kw)          # warmup+compile
            t0 = time.time()
            res = resilient_solve(plan, b_host, **kw)
            dt = time.time() - t0
            r_iters = int(np.max(np.asarray(res.iters)))
            r_us = dt / max(r_iters, 1) * 1e6
            out["resilient"] = {
                "check_every": args.check_every,
                "chunks": res.chunks,
                "iters": r_iters,
                "us_per_iter": r_us,
                "overhead_vs_monolithic":
                    round(r_us / out["us_per_iter"] - 1.0, 4),
            }
    elif args.cg:
        import jax.numpy as jnp

        from repro.util import collective_counts

        if args.wire_dtype != "f32":
            ap.error("--cg is the legacy f32-wire path; use --solver for "
                     "compressed wire")
        solve = make_cg(plan, mesh, fused=args.fused,
                        transport=args.transport,
                        neighbor_offsets=layout["neighbor_offsets"])
        b = to_dist(rng.normal(size=A.n_rows), layout, plan)
        xd, it, rel = solve(b, tol=args.tol, maxiter=200)  # warmup+compile
        jax.block_until_ready(xd)
        t0 = time.time()
        xd, it, rel = solve(b, tol=args.tol, maxiter=args.iters)
        jax.block_until_ready(xd)
        dt = time.time() - t0
        out.update(cg_iters=int(it), cg_rel=float(rel), fused=args.fused,
                   transport=getattr(solve, "transport", args.transport),
                   us_per_iter=dt / max(int(it), 1) * 1e6)
        if not args.no_collectives:
            # one `while` body per module text -> counts ~ per-iteration
            out["collectives"] = collective_counts(
                solve.jitted, b, jnp.asarray(args.tol, jnp.float32),
                jnp.asarray(args.iters, jnp.int32))
    else:
        from repro.core import transport_census
        from repro.util import collective_counts

        names = args.transport.split(",")
        wire_dtypes = args.wire_dtype.split(",")
        wire_sweep = {}
        for wd in wire_dtypes:
            census = transport_census(plan, wire_dtype=wd)
            sweep = {}
            for name in names:
                res = {}
                if name == "auto":
                    from repro.core.transport import autotune_transport
                    at = autotune_transport(plan, mesh, wire_dtype=wd)
                    spmv = at.spmv
                    res["resolved"] = at.winner
                    res["autotune"] = {
                        "winner": at.winner,
                        "timings_us": {k: round(v, 1)
                                       for k, v in at.timings_us.items()},
                        "timings_min_us": {
                            k: round(v, 1)
                            for k, v in at.timings_min_us.items()}}
                else:
                    spmv = make_spmv(plan, mesh, transport=name,
                                     wire_dtype=wd)
                    res["resolved"] = spmv.transport
                # fairness: the first call pays compilation and first-run
                # setup — warm the *compiled* program before any timing so
                # no transport's timed pass carries one-off costs
                for _ in range(max(args.warmup, 1)):
                    y = spmv(x)
                jax.block_until_ready(y)
                rep_us = []
                for _ in range(max(args.reps, 1)):
                    t0 = time.time()
                    for _ in range(args.iters):
                        y = spmv(x)
                    jax.block_until_ready(y)
                    rep_us.append((time.time() - t0) / args.iters * 1e6)
                res["us_per_spmv"] = float(np.median(rep_us))
                # min-of-reps: the low-noise estimator (reps_us swing up
                # to ~10x on a shared CPU; the min of identical repeated
                # work converges to the uncontended cost) — downstream
                # winner picks should compare us_per_spmv_min
                res["us_per_spmv_min"] = float(np.min(rep_us))
                res["reps_us"] = [round(v, 1) for v in rep_us]
                res["gflops"] = (2.0 * A.nnz
                                 / (res["us_per_spmv_min"] * 1e-6) / 1e9)
                # the transport's own static prediction at this wire
                # dtype (wire bytes + per-kind collective counts), to be
                # held against the compiled-HLO census below
                res["predicted"] = census[res["resolved"]]
                if plan.hs > 0:
                    from repro.analysis.jaxpr_pass import (
                        derived_wire_bytes, trace_exchange)
                    res["traced_wire_bytes"] = derived_wire_bytes(
                        trace_exchange(plan, res["resolved"],
                                       wire_dtype=wd),
                        plan.n_node, plan.n_core)
                if not args.no_collectives:
                    res["collectives"] = collective_counts(spmv, x)
                sweep[name] = res
            wire_sweep[wd] = sweep
        out["transports"] = wire_sweep[wire_dtypes[0]]
        if len(wire_dtypes) > 1:
            out["wire"] = wire_sweep
        out["wire_dtype"] = wire_dtypes[0]
        first = wire_sweep[wire_dtypes[0]][names[0]]
        out["transport"] = (first["resolved"] if len(names) == 1
                            else "sweep")
        out["us_per_spmv"] = first["us_per_spmv"]
        out["us_per_spmv_min"] = first["us_per_spmv_min"]
        out["gflops"] = first["gflops"]
        if "collectives" in first:
            out["collectives"] = first["collectives"]

    print(json.dumps(out))
    return 0


def plan_halo_bytes(layout) -> float:
    halo = layout["halo"]
    return halo.comm_bytes_per_node(itemsize=4)


if __name__ == "__main__":
    sys.exit(main())
