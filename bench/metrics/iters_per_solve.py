"""Krylov iterations per solve, from the iteration counts the solver
returned for the window's solves."""


def read(ctx):
    iters = ctx["counters"]["iters"]
    return sum(iters) / len(iters) if iters else None
