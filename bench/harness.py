"""One run of one cell: set-up, the measured window, the check, the result.

The system in the window is the program (:class:`ProgramSystem`, or
:class:`RefinedSystem` where the configuration asks for a float64 true
residual) or the plain reference in its place (:class:`ReferenceSystem`,
the control of ``control.py``).  Each answers ``call(b, tol, maxiter) ->
(x, iterations, rel)``; the window and the check are the same code for
all.
"""
from __future__ import annotations

import contextlib
import gc
import os
import tempfile
import time

import numpy as np

import reference
from operators import build_operator
from spec import Cell, metric_reader
from traffic import Traffic


class ProgramSystem:
    """The system under test: ``build_spmv_plan`` -> ``make_solver`` on the
    cell's ``(node, core)`` mesh, called as a user calls ``solve``."""

    #: the window allows no implicit transfer between host and device
    transfer_guard = "disallow"

    def __init__(self, csr: tuple, config: dict, mesh: tuple, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core import build_spmv_plan
        from repro.sparse import CSRMatrix

        n = len(csr[0]) - 1
        self.A = CSRMatrix(*csr, shape=(n, n))
        t = time.perf_counter()
        self.plan, self.layout = build_spmv_plan(self.A, *mesh)
        self.plan_build_s = time.perf_counter() - t
        self.mesh = jax.make_mesh(
            mesh, ("node", "core"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devices)
        self.vec = NamedSharding(self.mesh, PartitionSpec("node", "core"))
        self.rep = NamedSharding(self.mesh, PartitionSpec())
        self.devices = list(devices)
        self.build(config)

    def build(self, config: dict) -> None:
        from repro.solvers import make_solver
        self.solve = make_solver(self.plan, self.mesh,
                                 solver=config["solver"],
                                 precond=config["precond"],
                                 maxiter_static=config["maxiter"])

    def place(self, B: np.ndarray) -> list:
        import jax

        from repro.core import to_dist
        return [jax.device_put(to_dist(b, self.layout, self.plan), self.vec)
                for b in B]

    def scalars(self, tol: float, maxiter: int) -> tuple:
        import jax
        return jax.device_put((np.float32(tol), np.int32(maxiter)), self.rep)

    def call(self, b, tol, maxiter):
        import jax
        return jax.block_until_ready(self.solve(b, tol=tol, maxiter=maxiter))

    def warm(self, b, traffic: Traffic) -> None:
        """Compile (or load) the timed program and run it twice."""
        scal = self.scalars(traffic.tol, min(traffic.maxiter, 1))
        for _ in range(2):
            self.call(b, *scal)

    def host(self, x) -> np.ndarray:
        from repro.core import from_dist
        return from_dist(np.asarray(x), self.layout, self.plan).astype(
            np.float64)

    def memory_peak_bytes(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def program_text(self, b, traffic: Traffic) -> str:
        """The compiled text of the program the window drives."""
        return self.solve.jitted.lower(
            b, *self.scalars(traffic.tol, traffic.maxiter)).compile().as_text()

    def close(self) -> None:
        del self.solve, self.plan
        gc.collect()


class RefinedSystem(ProgramSystem):
    """The program's float64 iterative refinement (``make_refine``) over its
    float32 solve: how the program meets a tolerance on the float64 true
    residual.  Each cycle moves the residual and the correction between
    host and device, so the window allows those transfers."""

    transfer_guard = "allow"

    def build(self, config: dict) -> None:
        from repro.solvers.refine import make_refine
        self.refine = make_refine(self.plan, self.mesh,
                                  solver=config["solver"],
                                  precond=config["precond"],
                                  A=self.A, layout=self.layout,
                                  maxiter_inner=config["maxiter"],
                                  maxiter_static=config["maxiter"])
        self.solve = self.refine.solve

    def place(self, B):
        return [np.asarray(b, np.float64) for b in B]

    def scalars(self, tol, maxiter):
        return tol, maxiter

    def call(self, b, tol, maxiter):
        r = self.refine(b, tol=tol)
        return r.x, r.inner_iters, r.rel

    def _inner_args(self, b):
        from repro.core import to_dist
        return to_dist(np.asarray(b, np.float32), self.layout, self.plan)

    def warm(self, b, traffic):
        import jax
        for _ in range(2):
            jax.block_until_ready(self.solve(self._inner_args(b), tol=1.0,
                                             maxiter=1))
        self.A.matvec(b)

    def host(self, x):
        return np.asarray(x, np.float64)

    def program_text(self, b, traffic):
        import jax.numpy as jnp
        return self.solve.jitted.lower(
            self._inner_args(b), jnp.asarray(1.0, jnp.float32),
            jnp.asarray(1, jnp.int32)).compile().as_text()

    def close(self):
        del self.refine
        super().close()


def program_system(csr: tuple, config: dict, mesh: tuple, devices):
    """The program's entry for what the configuration asks of an answer:
    its float32 solve, or float64 refinement over it where the tolerance
    holds for the float64 true residual (``"residual": "true"``)."""
    cls = RefinedSystem if config["residual"] == "true" else ProgramSystem
    return cls(csr, config, mesh, devices)


class ReferenceSystem:
    """The plain reference in the program's place (the control): its
    arithmetic in ``dtype``, its stored values rounded by ``store``."""

    transfer_guard = "allow"

    def __init__(self, csr: tuple, config: dict, dtype, store):
        self.A = reference.operator(*csr, dtype=dtype)
        self.precond = config["precond"]
        self.store = store
        self.plan_build_s = 0.0

    def place(self, B):
        return list(B)

    def scalars(self, tol, maxiter):
        return tol, maxiter

    def call(self, b, tol, maxiter):
        return reference.cg(self.A, b, tol, maxiter, precond=self.precond,
                            store=self.store)

    def warm(self, b, traffic):
        pass

    def host(self, x):
        return np.asarray(x, np.float64)

    def memory_peak_bytes(self):
        return None

    def program_text(self, b, traffic):
        return ""

    def close(self):
        pass


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


def operator_csr(cell: Cell, rehearse: bool) -> tuple:
    """The configuration's operator, or at its ``rehearsal`` parameters."""
    block = dict(cell.config["operator"])
    if rehearse:
        block.update(cell.config["rehearsal"])
    return build_operator(block)


def window(system, placed: list, traffic: Traffic, seconds: float,
           counter: CompileCounter | None, annotate) -> dict:
    """Whole passes over the pool, in the seed's order, back to back, until
    ``seconds`` have passed: every run does the same work.  Keeps every
    call's slot, answer, iterations and residual."""
    import jax
    scal = system.scalars(traffic.tol, traffic.maxiter)
    calls = []
    if counter is not None:
        counter.active = True
    with jax.transfer_guard(system.transfer_guard), annotate("window"):
        t0 = time.perf_counter()
        while True:
            for slot in traffic.order:
                with annotate("solve_call"):
                    x, it, rel = system.call(placed[slot], *scal)
                with annotate("host_check"):
                    calls.append((int(slot), x, it, rel))
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    if counter is not None:
        counter.active = False
    return {"window_s": t1 - t0, "calls": calls}


def check(A64, traffic: Traffic, calls: list, limits: dict,
          precond: str) -> tuple[list, int]:
    """Compare every answer of the window with the plain reference.

    Fixed iterations (tol 0): each answer against the f64 reference's
    iterate after the same iterations on its right-hand side (``x_err``),
    and each call's count.  Solves: each answer by its f64 true residual
    (``true_rel``), and each call's own residual against the tolerance.
    ``calls`` holds ``(slot, x, iterations, rel)`` with ``x`` on the host.
    Returns ``(checks, failed)``, a check being ``(name, value, limit)``."""
    if traffic.fixed_iterations:
        ref = {}
        for slot in {s for s, _, _, _ in calls}:
            ref[slot] = reference.cg(A64, traffic.B[slot], 0.0,
                                     traffic.maxiter, precond=precond)[0]
        err = [reference.iterate_err(x, ref[s]) for s, x, _, _ in calls]
        name, limit = "x_err", limits["x_err"]
        own = [it != traffic.maxiter for _, _, it, _ in calls]
        own_check = ("short_calls", float(sum(own)), 0.0)
    else:
        err = [reference.true_rel(A64, x, traffic.B[s])
               for s, x, _, _ in calls]
        name, limit = "true_rel", limits["true_rel"]
        own = [not rel <= traffic.tol for _, _, _, rel in calls]
        own_check = ("solver_rel", max((r for _, _, _, r in calls),
                                       default=float("nan")), traffic.tol)
    bad = [o or not e <= limit for e, o in zip(err, own)]
    checks = [(name, max(err, default=float("nan")), limit), own_check]
    return checks, int(sum(bad))


def measure(cell: Cell, system, csr: tuple, traffic: Traffic, *,
            seconds: float, trace: bool, t_start: float,
            counter: CompileCounter | None = None,
            device_trace: bool = True) -> dict:
    """Set-up's remainder, the window, then everything read after it.
    ``device_trace=False`` (a CPU rehearsal) records the trace but reads
    only its host spans, since no TPU planes are in it."""
    import jax
    placed = system.place(traffic.B)
    system.warm(placed[0], traffic)
    gc.collect()

    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    tmp = tempfile.TemporaryDirectory() if trace else None
    setup_s = time.perf_counter() - t_start
    if trace:
        jax.profiler.start_trace(tmp.name)
    try:
        w = window(system, placed, traffic, seconds, counter, annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    mem = system.memory_peak_bytes()
    calls = [(s, system.host(x), int(it), float(rel))
             for s, x, it, rel in w["calls"]]
    text = system.program_text(placed[0], traffic) if trace else ""
    plan_build_s = system.plan_build_s
    del placed, w["calls"]
    system.close()
    summary = None
    if trace:
        import hlo
        import xplane
        path = _trace_file(tmp.name)
        if device_trace:
            summary = xplane.reduce_trace(path)
            summary["loop_gathers"] = sorted(hlo.loop_gathers(text))
        elif not any(n == xplane.WINDOW for n, _, _ in
                     xplane.read_events(path)[0]):
            raise RuntimeError("the trace holds no window span")
        tmp.cleanup()
    A64 = reference.operator(*csr)
    checks, failed = check(A64, traffic, calls, cell.limits,
                           cell.config["precond"])
    return {"setup_s": setup_s, "window_s": w["window_s"],
            "calls": [(s, it, rel) for s, _, it, rel in calls],
            "checks": checks, "failed": failed, "memory_peak_bytes": mem,
            "plan_build_s": plan_build_s, "trace": summary,
            "compiles_in_window": None if counter is None else counter.count}


def _trace_file(path: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
             if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {path}, found "
                           f"{found}")
    return found[0]


E2E = {
    "setup_s": lambda r: r["setup_s"],
    "iter_ms": lambda r: 1e3 * r["window_s"] / sum(it for _, it, _ in
                                                    r["calls"]),
    "solve_s": lambda r: r["window_s"] / len(r["calls"]),
}


def metrics(cell: Cell, rec: dict, *, trace: bool, peaks: dict | None,
            operator: dict) -> dict:
    """The cell's end-to-end metrics (trace off) or its per-layer metrics
    (trace on), each as ``{"value", "unit"}``; a reader that finds nothing
    leaves its metric out."""
    out = {}
    if not trace:
        for m in cell.end_to_end:
            out[m["name"]] = {"value": E2E[m["name"]](rec), "unit": m["unit"]}
        return out
    ctx = {"spans": {"plan_build_s": rec["plan_build_s"]},
           "counters": {"iters": [it for _, it, _ in rec["calls"]]},
           "trace": rec["trace"], "operator": operator, "chips": cell.chips,
           "peaks": peaks}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
