#!/usr/bin/env python3
"""The control of the correctness check: the plain reference, computed one
precision below the configuration's, put in the program's place.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs a cell's whole flow (operator, traffic, window, check) on each seed
with the control answering the calls, and prints each seed's compared
numbers beside their limits, one JSON line per seed.  A configuration of
``"precision": "float32"`` gets bfloat16 storage with float32 arithmetic,
one of ``"float64"`` float32 arithmetic.  A sound check reads
``"correct": false`` on every seed: the comparison can tell the
configuration's precision from the next lower one.  The limits in ``cells/`` are set
between the program's readings and these (PERF.md).  ``--rehearse`` runs
the tiny operator.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def control_system(csr, cell, devices):
    import numpy as np

    import harness
    import reference
    store = {"float32": reference.bf16_store,
             "float64": None}[cell.config["precision"]]
    return harness.ReferenceSystem(csr, cell.config, np.float32, store)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = run.parse(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--trace", "0"]
                         + (["--rehearse"] if a.rehearse else []))
        out = run.run(args, make_system=control_system)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
