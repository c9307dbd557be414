"""Which instructions of a compiled program's loops gather."""
import hlo

TEXT = """HloModule jit_bound, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0: f32[8], param_1: s32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %gather.1 = f32[8]{0} gather(f32[8]{0} %param_0, s32[8,1]{1,0} %i), offset_dims={}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.2 = f32[8]{0} multiply(f32[8]{0} %param_0.1, f32[8]{0} %param_0.1)
}

%body.3 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %a, s32[8]{0} %b), kind=kCustom, calls=%fused_computation.1
  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %fusion.7), kind=kLoop, calls=%fused_computation.2
  %gather.9 = f32[8]{0} gather(f32[8]{0} %fusion.8, s32[8,1]{1,0} %i), offset_dims={}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %k, f32[8]{0} %gather.9)
}

%cond.4 (arg.1: (s32[], f32[8])) -> pred[] {
  ROOT %lt = pred[] compare(s32[] %k, s32[] %n), direction=LT
}

ENTRY %main.5 (p: f32[8]) -> f32[8] {
  %fusion.10 = f32[8]{0} fusion(f32[8]{0} %p, s32[8]{0} %q), kind=kCustom, calls=%fused_computation.1
  %while.6 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%cond.4, body=%body.3
  ROOT %gte = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %while.6), index=1
}
"""


def test_loop_gathers_by_hand():
    # the prologue's gather fusion.10 is outside the loop; fusion.8 holds
    # no gather
    assert hlo.loop_gathers(TEXT) == {"fusion.7", "gather.9"}


def test_no_loop_no_gathers():
    entry = TEXT[TEXT.index("ENTRY"):].replace(
        "%while.6 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
        "condition=%cond.4, body=%body.3",
        "%copy.6 = (s32[], f32[8]{0}) copy((s32[], f32[8]{0}) %t)")
    assert hlo.loop_gathers(TEXT[:TEXT.index("ENTRY")] + entry) == set()


def test_compiled_solve_loop_gathers(no_compile_cache):
    """The program's CG solve, compiled here, gathers inside its loop (on
    the CPU the stored columns' gathers fuse into one instruction; the
    TPU's compiler keeps one per column)."""
    import jax
    import jax.numpy as jnp
    from operators import build_operator
    from repro.core import build_spmv_plan
    from repro.solvers import make_solver
    from repro.sparse import CSRMatrix

    ip, ix, d = build_operator({"generator": "unit_cube_p1",
                                "target_dofs": 100})
    n = len(ip) - 1
    plan, _ = build_spmv_plan(CSRMatrix(ip, ix, d, shape=(n, n)), 1, 1)
    mesh = jax.make_mesh((1, 1), ("node", "core"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    solve = make_solver(plan, mesh, solver="cg", precond="none")
    text = solve.jitted.lower(jnp.zeros(plan.cg_shape, jnp.float32),
                              jnp.float32(1e-5), jnp.int32(5)
                              ).compile().as_text()
    assert hlo.loop_gathers(text)
