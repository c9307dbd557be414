"""Refinement cycles per solve of the window: counter ``refine.cycles``
over the calls of span ``refine.solve`` (``repro.solvers.refine``)."""
from program_spans import totals


def read(ctx):
    t = totals()
    if "refine.cycles" not in t or not t.get("refine.solve", (0,))[0]:
        return None
    return t["refine.cycles"][0] / t["refine.solve"][0]
