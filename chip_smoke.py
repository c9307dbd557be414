#!/usr/bin/env python3
"""Bring-up smoke run: the paper's Fig. 3 pressure solve on a TPU.

Drives the main path once through the entry points a user calls, on the
``PAPER_SMALL`` operator (``repro.configs.fluidity_cg``: the extruded
coastline mesh with 210k surface points and 64 layers, ~13.4M rows,
generated on the host from a seed), cut by default to ``SMOKE_LAYERS``
layers (3.36M rows) so that both phases end inside 20 minutes:

1. ``build_spmv_plan`` -> ``make_solver`` (fused sharded CG + Jacobi,
   ``backend="jnp"``) on a 1x1 mesh, with the paper's protocol (maxiter
   10,000).  The steady solve runs under ``jax.transfer_guard("disallow")``,
   so any per-call host<->device copy of the operator fails the run.
2. ``SolveService`` on the same operator with 4 batch slots, answering 4
   requests with spread tolerances; two of them are submitted while
   another request is still in flight, so they are spliced mid-solve.

Every answer is checked against the host f64 true residual
``||b - A x|| / ||b||``, which must be within 10x the tolerance.  Any
failed check raises, so the script exits non-zero and prints no result.

  python chip_smoke.py               # one chip (phases 1 and 2)
  python chip_smoke.py --chips 4     # only phase 1, on 4x1 and 2x2 meshes
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny operator, CPU

Without a TPU the script exits non-zero unless ``--rehearse`` is given;
a rehearsal names the platform it really ran on.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: tiny operator for ``--rehearse`` (n_surface, layers)
REHEARSAL = (300, 4)
#: the default cut of PAPER_SMALL's 64 layers.  XLA's gather takes ~0.5 s
#: per SpMV at 16 layers on one v5e, and ~470 CG iterations reach 1e-5:
#: with 4x the rows per SpMV and no fewer iterations, the uncut operator's
#: two phases would not end inside 20 minutes.
SMOKE_LAYERS = 16
#: above the f32 floor of CG's recursive residual at these sizes
TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def true_rel(A, x, b) -> float:
    """Host f64 true relative residual ``||b - A x|| / ||b||``."""
    import numpy as np
    r = b - A.matvec(np.asarray(x, np.float64))
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def device_memory(devices) -> list:
    """Per-device (bytes in use, peak bytes in use), where reported."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"device": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def operand_bytes_per_device(operands) -> dict:
    """Bytes of the placed operator resident on each device."""
    per: dict = {}
    for a in operands:
        for sh in a.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def unserved(A, shape, *, tol: float, maxiter: int, seed: int,
             mode: str = "balanced") -> None:
    """One solve through ``build_spmv_plan`` -> ``make_solver``."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import build_spmv_plan, from_dist, to_dist
    from repro.serve.plans import batch_sharding
    from repro.solvers import make_solver
    from repro.util import make_mesh_compat

    n_node, n_core = shape
    t0 = time.perf_counter()
    plan, layout = build_spmv_plan(A, n_node, n_core, mode=mode,
                                   transport="a2a")
    plan_s = time.perf_counter() - t0
    mesh = make_mesh_compat(shape, ("node", "core"))
    t0 = time.perf_counter()
    solve = make_solver(plan, mesh, solver="cg", precond="jacobi",
                        maxiter_static=maxiter)
    place_s = time.perf_counter() - t0
    per_dev = operand_bytes_per_device(solve.jitted.operands)
    total = sum(per_dev.values())
    log(f"[{n_node}x{n_core}] plan {plan_s:.3f} s (rc_pad {plan.rc_pad}, "
        f"halo width {plan.hs}); operator on device {total} B, placed in "
        f"{place_s:.3f} s; per device {per_dev}")

    b = np.random.default_rng(seed).normal(size=A.n_rows)
    bd = jax.device_put(to_dist(b, layout, plan), batch_sharding(mesh))
    tol_d, one_d, max_d = jax.device_put(
        (np.float32(tol), np.int32(1), np.int32(maxiter)),
        NamedSharding(mesh, PartitionSpec()))
    t0 = time.perf_counter()
    jax.block_until_ready(solve(bd, tol=tol_d, maxiter=one_d))
    first_s = time.perf_counter() - t0
    # the steady solve: the operator must already live on the mesh
    with jax.transfer_guard("disallow"):
        t0 = time.perf_counter()
        xd, it, rel = solve(bd, tol=tol_d, maxiter=max_d)
        jax.block_until_ready(xd)
        solve_s = time.perf_counter() - t0
    iters, rel = int(it), float(rel)
    x = from_dist(np.asarray(xd), layout, plan)
    tr = true_rel(A, x, b)
    log(f"[{n_node}x{n_core}] first call (compile + 1 iteration) "
        f"{first_s:.3f} s; solve {solve_s:.3f} s, {iters} iterations, "
        f"solver rel {rel:.3e}, host f64 true rel {tr:.3e} (tol {tol:g})")
    check(iters < maxiter and rel <= tol,
          f"{n_node}x{n_core} solve did not converge ({iters} iterations, "
          f"rel {rel:.3e})")
    check(tr <= 10 * tol, f"{n_node}x{n_core} true residual {tr:.3e} > "
          f"10 x tol {tol:g}")
    if n_node * n_core > 1:
        # padded shards are equal: each device holds 1/n of the operator
        share = max(per_dev.values()) / total
        check(len(per_dev) == n_node * n_core
              and share <= 1.05 / (n_node * n_core),
              f"operator not split evenly across the mesh: {per_dev}")


def served(A, *, tol: float, maxiter: int, seed: int) -> None:
    """Four requests through ``SolveService`` with 4 batch slots."""
    import numpy as np

    from repro.serve import EngineConfig, PlanCache, SolveService

    cfg = EngineConfig(nrhs=4, n_node=1, n_core=1, maxiter=maxiter,
                       maxiter_static=maxiter, default_tol=tol)
    t0 = time.perf_counter()
    svc = SolveService(A, cfg, cache=PlanCache())
    build_s = time.perf_counter() - t0
    B = np.random.default_rng(seed + 1).normal(size=(4, A.n_rows))
    tols = [30 * tol, tol, 3 * tol, 10 * tol]
    t0 = time.perf_counter()
    futs = [svc.submit(B[0], tol=tols[0]), svc.submit(B[1], tol=tols[1])]
    futs[0].result()            # pumps chunks until the loosest retires
    mid_solve = not futs[1].done()
    futs += [svc.submit(B[j], tol=tols[j]) for j in (2, 3)]
    svc.drain()
    serve_s = time.perf_counter() - t0
    stats = svc.stats()
    answers = []
    for j, f in enumerate(futs):
        r = f.result()
        tr = true_rel(A, r.x, B[j])
        answers.append({"tol": tols[j], "iterations": r.iterations,
                        "true_rel": tr})
        check(tr <= 10 * tols[j], f"served request {j}: true residual "
              f"{tr:.3e} > 10 x tol {tols[j]:g}")
    log(f"[served] build (plan + compile + warm) {build_s:.3f} s; "
        f"4 requests in {serve_s:.3f} s; answers {answers}")
    log(f"[served] executables {stats['executables']}, recompiles "
        f"{stats['recompiles']}, splices {stats['splices']}, chunks "
        f"{stats['chunks']}, spliced while another was in flight: "
        f"{mid_solve}")
    check(mid_solve, "requests 2 and 3 were not spliced mid-solve")
    check(all(v == 1 for v in stats["executables"].values()),
          f"served programs recompiled: {stats['executables']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the unserved solve on 4x1 and 2x2 "
                         "meshes of four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run a tiny operator on whatever platform JAX "
                         "finds (a CPU rehearsal; never a chip result)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.configs.fluidity_cg import PAPER_SMALL
    from repro.sparse import extruded_mesh_matrix
    from repro.util import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {platform!r}); pass "
              "--rehearse for a CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"devices: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache}")

    cfg = PAPER_SMALL
    n_surface, layers = (REHEARSAL if args.rehearse
                         else (cfg.n_surface, SMOKE_LAYERS))
    if (n_surface, layers) != (cfg.n_surface, cfg.layers):
        log(f"operator cut: {n_surface} surface points x {layers} layers "
            f"(PAPER_SMALL is {cfg.n_surface} x {cfg.layers})")
    t0 = time.perf_counter()
    A = extruded_mesh_matrix(n_surface, layers, seed=cfg.seed)
    gen_s = time.perf_counter() - t0
    log(f"operator {cfg.name}: {A.n_rows} rows, {A.nnz} nnz "
        f"({A.nnz / A.n_rows:.2f} per row), generated in {gen_s:.3f} s")

    kw = dict(tol=TOL, maxiter=cfg.maxiter, seed=args.seed)
    if args.chips == 4:
        for shape in ((4, 1), (2, 2)):
            unserved(A, shape, **kw)
            gc.collect()
    else:
        unserved(A, (1, 1), **kw)
        gc.collect()        # the served engine places its own operator
        served(A, **kw)
    for m in device_memory(devices):
        log(f"device {m['device']}: peak {m['peak_bytes_in_use']} B")

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
