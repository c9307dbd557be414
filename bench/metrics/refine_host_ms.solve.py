"""Host milliseconds of a refinement solve outside its inner device solves:
span ``refine.solve`` less span ``refine.inner``
(``repro.solvers.refine``), per solve of the window."""
from program_spans import totals


def read(ctx):
    t = totals()
    if "refine.inner" not in t or not t.get("refine.solve", (0,))[0]:
        return None
    (solves, solve_s), inner_s = t["refine.solve"], t["refine.inner"][1]
    return 1e3 * (solve_s - inner_s) / solves
