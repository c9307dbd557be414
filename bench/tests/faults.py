"""Run one cell with its timed path broken underneath.

  python bench/tests/faults.py <fault> <bench/run.py arguments>

Each fault breaks the program where a later change could break it, then
the whole run goes on as usual; its check must read ``correct: false``.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def state_unchanged():
    """Each CG step counts an iteration and returns its state unchanged."""
    from repro.solvers.krylov import CGSolver

    def body(self, ctx, aux, state):
        return {**state, "k": state["k"] + 1}
    CGSolver.loop_body = body


def answer_altered():
    """The answer is scaled by 1 + 1e-2 where it is produced: at the end of
    the solve, and at the end of the float64 refinement around it (which
    would correct an inner solve's error, as it should)."""
    from repro.solvers import refine as refine_module
    from repro.solvers.krylov import CGSolver
    finish = CGSolver.loop_finish

    def altered(self, ctx, aux, state):
        x, k, rel = finish(self, ctx, aux, state)
        return x * (1 + 1e-2), k, rel
    CGSolver.loop_finish = altered
    make = refine_module.make_refine

    def make_altered(*args, **kwargs):
        inner = make(*args, **kwargs)

        def refine(b, **kw):
            r = inner(b, **kw)
            r.x = r.x * (1 + 1e-2)
            return r
        refine.__dict__.update(inner.__dict__)
        return refine
    refine_module.make_refine = make_altered


FAULTS = {f.__name__: f for f in (state_unchanged, answer_altered)}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
