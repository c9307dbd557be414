"""Golden square-case hashes: the rectangular refactor must be a no-op.

Usage:  python -m repro.testing.square_golden --write tests/golden_square_hashes.json
        python -m repro.testing.square_golden --check tests/golden_square_hashes.json

The PR-10 rectangular generalisation of ``build_spmv_plan`` promises that
square inputs with no explicit column-space override reduce *bit-identically*
to the pre-refactor plans.  This module pins that promise: it sha256-hashes

  plan    every data array of the packed ``SpMVPlan`` (fmt_data, halo
          tables, x_gather, diag_a, mask) — pure numpy construction,
          deterministic across platforms;
  spmv    ``make_spmv`` output on a fixed seeded input vector, per
          (format x transport), on the n_node x n_core mesh;
  cg      the fused CG solve (solution bytes + iteration count) with
          jacobi preconditioning,

for the graded matrix at ell+sell x every registered transport.  The
fixture committed at ``tests/golden_square_hashes.json`` was generated at
the pre-refactor HEAD; ``--check`` re-derives the hashes from the current
tree and fails on any drift.

Plan hashes are asserted unconditionally.  The spmv/cg output hashes are
XLA-program dependent, so ``--check`` compares them only when the
recorded jax version matches the running one (stamped in the fixture) —
on a version mismatch they are reported as SKIP, never silently passed.

Sets XLA_FLAGS *before* importing jax (transport_check idiom).
"""
import argparse
import hashlib
import json
import os
import sys

FORMATS = ("ell", "sell")
PLAN_META = ("n", "n_node", "n_core", "rc_pad", "nl_pad", "g_pad", "hs")
ELL_BLOCKS = ("diag_cols", "diag_vals", "offd_cols", "offd_vals")


def _hash(arr) -> str:
    import numpy as np

    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()


def build_entries(n_node: int, n_core: int) -> dict:
    import jax
    import numpy as np

    from repro.core import (available_transports, build_spmv_plan, from_dist,
                            make_spmv, to_dist)
    from repro.core.spmv import plan_fields, plan_shard_arrays
    from repro.sparse import graded_extruded_mesh_matrix
    from repro.solvers import make_solver
    from repro.util import make_mesh_compat

    A = graded_extruded_mesh_matrix(48, 6, seed=0)
    rng = np.random.default_rng(7)
    x = rng.standard_normal(A.n_rows).astype(np.float32)
    b = rng.standard_normal(A.n_rows).astype(np.float32)
    mesh = make_mesh_compat((n_node, n_core), ("node", "core"))

    out: dict = {}
    for fmt in FORMATS:
        for tr in sorted(available_transports()):
            plan, layout = build_spmv_plan(
                A, n_node, n_core, mode="balanced", node_partition="nnz",
                format=fmt, transport=tr)
            entry: dict = {"meta": {k: int(getattr(plan, k))
                                    for k in PLAN_META}}
            # ELL blocks are stored (width, rc_pad); hash them in the
            # fixture's row-major (rc_pad, width) orientation, so the pin
            # is on which entry sits in which slot, not on storage order
            entry["plan"] = {
                name: _hash(np.swapaxes(arr, -1, -2)
                            if name in ELL_BLOCKS else arr)
                for name, arr in zip(plan_fields(plan),
                                     plan_shard_arrays(plan))}
            entry["plan"]["mask"] = _hash(plan.mask)
            entry["plan"]["diag_a"] = _hash(plan.diag_a)

            spmv = make_spmv(plan, mesh)
            xd = to_dist(x, layout, plan)
            y = from_dist(np.asarray(jax.device_get(spmv(xd))), layout, plan)
            entry["spmv"] = _hash(np.asarray(y, np.float32))

            solve = make_solver(plan, mesh, solver="cg", precond="jacobi",
                                A=A, layout=layout)
            bd = to_dist(b, layout, plan)
            xs, iters, rel = solve(bd, tol=1e-6, maxiter=400)
            xg = from_dist(np.asarray(jax.device_get(xs)), layout, plan)
            entry["cg"] = {"x": _hash(np.asarray(xg, np.float32)),
                           "iters": int(iters)}
            out[f"{fmt}/{tr}"] = entry
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-node", type=int, default=4)
    ap.add_argument("--n-core", type=int, default=2)
    ap.add_argument("--write", default=None, metavar="PATH")
    ap.add_argument("--check", default=None, metavar="PATH")
    args = ap.parse_args()
    if (args.write is None) == (args.check is None):
        ap.error("exactly one of --write / --check is required")

    ndev = args.n_node * args.n_core
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}")

    import jax

    assert len(jax.devices()) == ndev, (len(jax.devices()), ndev)
    got = build_entries(args.n_node, args.n_core)

    if args.write:
        doc = {"jax_version": jax.__version__,
               "n_node": args.n_node, "n_core": args.n_core,
               "entries": got}
        with open(args.write, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"square_golden: wrote {len(got)} entries -> {args.write}")
        return 0

    with open(args.check) as f:
        doc = json.load(f)
    same_jax = doc.get("jax_version") == jax.__version__
    fails, skips = [], []
    for key, want in doc["entries"].items():
        if key not in got:
            fails.append(f"{key}: missing from current tree")
            continue
        cur = got[key]
        if cur["meta"] != want["meta"]:
            fails.append(f"{key}: plan meta drift {want['meta']} -> "
                         f"{cur['meta']}")
        for name, h in want["plan"].items():
            if cur["plan"].get(name) != h:
                fails.append(f"{key}: plan array {name!r} hash drift")
        for name in ("spmv", "cg"):
            if cur[name] != want[name]:
                if same_jax:
                    fails.append(f"{key}: {name} output hash drift")
                else:
                    skips.append(f"{key}: {name} (jax "
                                 f"{doc.get('jax_version')} != "
                                 f"{jax.__version__})")
    for s in skips:
        print(f"SKIP {s}")
    for msg in fails:
        print(f"FAIL {msg}")
    print(f"square_golden: {len(doc['entries'])} entries, "
          f"{len(fails)} failures, {len(skips)} skipped")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
