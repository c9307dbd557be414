"""Benchmark driver — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/*.py for the
paper-figure mapping):
  fig2_ratio/*        Fig. 2  — process:thread ratio sweep, 3 algorithms
  fig3_measured/*     Fig. 3  — measured strong scaling (host devices)
  fig3_model/fig4_*   Figs. 3-4 — pod-scale modelled curves, paper matrices
  cg_convergence/*    Sec. 3  — CG+Jacobi protocol
  kernel/*            kernel-level padding-waste / balance comparison
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import emit  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: ratio,scaling,cg,kernel")
    ap.add_argument("--quick", action="store_true",
                    help="smaller matrices / fewer iters")
    args = ap.parse_args()
    want = set((args.only or "ratio,scaling,cg,kernel").split(","))

    n = 0
    if "kernel" in want:
        import spmv_kernel
        r = spmv_kernel.run()
        emit(r)
        n += len(r)
    if "ratio" in want:
        import ratio_sweep
        r = ratio_sweep.run(n_surface=1000 if args.quick else 2000,
                            layers=8 if args.quick else 16,
                            iters=10 if args.quick else 30)
        emit(r)
        n += len(r)
    if "scaling" in want:
        import strong_scaling
        r = strong_scaling.run(iters=10 if args.quick else 30)
        emit(r)
        n += len(r)
    if "cg" in want:
        import cg_convergence
        r = cg_convergence.run()
        emit(r)
        n += len(r)
    print(f"# {n} benchmark rows", file=sys.stderr)


if __name__ == "__main__":
    main()
