#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (operator generation, plan build, placement, compile or cache load,
warm-up) is timed from process start.  The window then calls the solve
back to back, whole passes over the traffic's pool of right-hand sides,
until ``--seconds`` have passed.  Afterwards the answers
are compared with the plain f64 reference (``reference.py``).  The last
line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and, last, ``checks``: each number compared with its limit.
The same comparisons are the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--rehearse`` runs a tiny operator on whatever JAX
finds (a CPU rehearsal) and prints no metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny operator on whatever platform JAX finds; "
                         "prints no metric")
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 2


def run(args, make_system=None) -> dict:
    """One run; returns the result object (``make_system(csr, cell,
    devices)`` puts another system in the program's place)."""
    import jax

    import harness
    from roofline import peaks
    from spec import resolve_cell
    from traffic import make_traffic

    from repro.util import enable_compile_cache

    cell = resolve_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            raise SystemExit(fail(f"no TPU (JAX found {dev.platform!r})"))
        if len(devices) < cell.chips:
            raise SystemExit(fail(f"cell {cell.name} needs {cell.chips} "
                                  f"chips, JAX found {len(devices)}"))
    pk = None if args.rehearse else peaks(dev.device_kind)
    counter = harness.CompileCounter()

    csr = harness.operator_csr(cell, args.rehearse)
    n = len(csr[0]) - 1
    if make_system is None:
        system = harness.program_system(csr, cell.config, cell.mesh,
                                        devices[:cell.chips])
    else:
        system = make_system(csr, cell, devices[:cell.chips])
    traffic = make_traffic(cell.mix, cell.config, n, args.seed)
    rec = harness.measure(cell, system, csr, traffic, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          counter=counter, device_trace=not args.rehearse)
    if rec["compiles_in_window"]:
        raise RuntimeError(f"{rec['compiles_in_window']} traces or compiles "
                           "inside the measured window")
    checks = rec["checks"]
    correct = (bool(rec["calls"]) and rec["failed"] == 0
               and all(v <= lim for _, v, lim in checks))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": len(rec["calls"]),
           "failed": rec["failed"]}
    if args.rehearse:
        out["rehearsal"] = True
        out["metrics"] = {}
    else:
        out["metrics"] = harness.metrics(
            cell, rec, trace=bool(args.trace), peaks=pk,
            operator={"n_rows": n, "n_cols": n, "nnz": len(csr[1])})
    if args.trace and not args.rehearse:
        tr = rec["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["device"] = device
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    out = run(args)
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
