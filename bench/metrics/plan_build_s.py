"""Host seconds of ``build_spmv_plan`` for the cell's operator and mesh,
timed by the harness around the call (set-up)."""


def read(ctx):
    return ctx["spans"]["plan_build_s"]
