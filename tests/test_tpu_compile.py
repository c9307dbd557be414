"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a topology that is
described rather than attached: what it refuses here (a layout that does
not fit HBM, a kernel Mosaic cannot lower) would be refused on the chip.
Nothing runs, so these tests say nothing about results or speed.

* the ``backend="jnp"`` SpMV, the fused CG + Jacobi solve and the served
  chunk program compile on one chip and on a 2x2 mesh and fit one chip's
  16 GB of HBM;
* the five Pallas SpMV kernels are refused by Mosaic as written (ROADMAP
  A2) — pinned strictly, so a kernel rewrite that compiles flips them.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file.  The program factories place their operator with
``jax.device_put``, which has no device to put to here, so each test
swaps it for an abstract placement (the operator's shape, dtype and
sharding) while it builds the program.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import build_spmv_plan, make_spmv
from repro.solvers import make_resilient, make_solver
from repro.sparse import extruded_mesh_matrix
from repro.sparse.formats import get_format

HBM_BYTES = 16 * 1024**3        # one v5e chip
MESHES = [(1, 1), (2, 2)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def operator():
    return extruded_mesh_matrix(2000, 8, seed=0)     # 16k rows


@pytest.fixture
def abstract_put(monkeypatch):
    def put(x, sharding=None, *args, **kwargs):
        return jax.ShapeDtypeStruct(
            np.shape(x), jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=sharding)
    monkeypatch.setattr(jax, "device_put", put)


def _mesh(topo, shape):
    devs = np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape)
    return Mesh(devs, ("node", "core"), axis_types=(AxisType.Auto,) * 2)


def _sds(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _fits_one_chip(compiled, plan):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    txt = compiled.as_text()
    # the jnp path is XLA's gather: no Mosaic kernel in the program
    assert "tpu_custom_call" not in txt
    # the format's stored blocks come first among a program's operands
    _no_operator_copy(txt, len(get_format(plan.format).fields))


def _no_operator_copy(txt, n_blocks):
    """No instruction of the entry computation copies an operator block
    whole: the blocks arrive in the layout the loop reads them in, so a
    call does not relayout the operator first
    (``repro.util.place_operands``)."""
    entry = txt[txt.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")].splitlines()[1:]
    inst = re.compile(r"\s*(?:ROOT )?(%\S+) = (.*?) ([\w\-]+)\((.*)")
    arr = re.compile(r"[a-z]+\d*\[(\d+(?:,\d+)+)\]")
    params = {}                        # name -> element count
    for line in entry:
        m = inst.match(line)
        op = re.search(r'op_name="args\[(\d+)\]"', line)
        if m and m.group(3) == "parameter" and op \
                and int(op.group(1)) < n_blocks:
            dims = arr.match(m.group(2))
            size = dims and int(np.prod(
                [int(d) for d in dims.group(1).split(",")]))
            if size:
                params[m.group(1)] = size
    assert params, "no operator block in the entry computation"
    passthrough = {"parameter", "tuple", "get-tuple-element", "while",
                   "bitcast"}
    for line in entry:
        m = inst.match(line)
        if not m or m.group(3) in passthrough:
            continue
        # a copy-start between two layouts that differ only in memory
        # space prefetches a small operator, it does not relayout it
        if m.group(3) == "copy-start" and len(set(
                re.sub(r"S\(\d+\)", "", t) for t in re.findall(
                    r"\w+\[[\d,]*\]\{[^}]*\}", m.group(2))[:2])) == 1:
            continue
        out = {int(np.prod([int(d) for d in dims.split(",")]))
               for dims in arr.findall(m.group(2))}
        for name, size in params.items():
            assert not (re.search(re.escape(name) + r"[,)]", m.group(4))
                        and size in out), \
                f"operator copied per call: {line.strip()[:160]}"


@pytest.mark.parametrize("shape", MESHES)
def test_spmv_compiles_for_v5e(topo, operator, abstract_put, shape):
    mesh = _mesh(topo, shape)
    plan, _ = build_spmv_plan(operator, *shape)
    spmv = make_spmv(plan, mesh)
    x = _sds(plan.x_shape, jnp.float32, mesh, P("node", "core"))
    _fits_one_chip(spmv.lower(x).compile(), plan)


@pytest.mark.parametrize("shape", MESHES)
def test_cg_jacobi_solve_compiles_for_v5e(topo, operator, abstract_put,
                                          shape):
    mesh = _mesh(topo, shape)
    plan, _ = build_spmv_plan(operator, *shape)
    solve = make_solver(plan, mesh, solver="cg", precond="jacobi")
    b = _sds(plan.cg_shape, jnp.float32, mesh, P("node", "core"))
    _fits_one_chip(solve.jitted.lower(
        b, _sds((), jnp.float32, mesh), _sds((), jnp.int32, mesh)).compile(),
        plan)


def test_exchange_carries_spmv_scopes_for_v5e(topo, operator, abstract_put):
    """On a 2x2 mesh the loop's node exchange and x-bin gather keep the
    ``spmv/halo`` and ``spmv/assemble`` scopes in their ``op_name``, so a
    device trace can tell the exchange from the solver's reductions."""
    mesh = _mesh(topo, (2, 2))
    plan, _ = build_spmv_plan(operator, 2, 2)
    solve = make_solver(plan, mesh, solver="cg", precond="none",
                        maxiter_static=10_000)
    b = _sds(plan.cg_shape, jnp.float32, mesh, P("node", "core"))
    txt = solve.jitted.lower(
        b, _sds((), jnp.float32, mesh),
        _sds((), jnp.int32, mesh)).compile().as_text()
    names = {}
    for line in txt.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = .*? (all-to-all|all-gather)"
                     r"(?:-start)?\(.*op_name=\"([^\"]*)\"", line)
        if m:
            names.setdefault(m.group(1), []).append(m.group(2))
    # under the solver's vmap the scope reads ``vmap(spmv)/halo/...``
    assert any(re.search(r"spmv\)?/halo/", n)
               for n in names["all-to-all"]), names
    assert any(re.search(r"spmv\)?/assemble/", n)
               for n in names["all-gather"]), names


@pytest.mark.parametrize("shape", MESHES)
def test_served_chunk_compiles_for_v5e(topo, operator, abstract_put, shape):
    mesh = _mesh(topo, shape)
    plan, _ = build_spmv_plan(operator, *shape)
    rs = make_resilient(plan, mesh, solver="cg", precond="jacobi")
    nrhs = 4
    vec = _sds((*shape, nrhs, plan.rc_pad), jnp.float32, mesh,
               P("node", "core"))
    tol = _sds((nrhs,), jnp.float32, mesh)
    count = _sds((), jnp.int32, mesh)
    k = _sds((nrhs,), jnp.int32, mesh)
    state = jax.eval_shape(rs.restart.fn, *rs.restart.operands,
                           vec, tol, count, vec, k)
    state = [_sds(s.shape, s.dtype, mesh,
                  P("node", "core") if rs.kinds[key] == "vector" else P())
             for key, s in zip(rs.skeys, state)]
    _fits_one_chip(rs.chunk.lower(vec, tol, count, count, *state).compile(),
                   plan)


# --------------------------------------------------------------------- #
# the Pallas kernels as written: Mosaic refuses each (ROADMAP A2)
# --------------------------------------------------------------------- #
def _pallas_cases():
    from repro.kernels import spmv_bcsr as k
    f32, i32 = jnp.float32, jnp.int32
    rows, w, nnz = 1024, 8, 4096
    ell = [(rows, w, f32), (rows, w, i32)]
    sell = [(nnz, f32), (nnz, i32), (nnz, i32)]
    return {
        "ell": (k.ell_spmv_pallas, ell + [(rows, f32)], {}),
        "fused_ell": (k.fused_ell_spmv_pallas,
                      ell + ell + [(rows, f32), (rows, f32)], {}),
        "sell": (k.sell_spmv_pallas, sell + [(rows, f32)],
                 {"rc_pad": rows}),
        "fused_sell": (k.fused_sell_spmv_pallas,
                       sell + sell + [(rows, f32), (rows, f32)],
                       {"rc_pad": rows}),
        "balanced": (k.balanced_spmv_pallas,
                     [(4, nnz, f32), (4, nnz, i32), (4, nnz, i32),
                      (rows, f32)], {"rows_pad": rows // 4}),
    }


#: how Mosaic refuses each kernel today
REFUSALS = {
    "ell": (NotImplementedError, "1-D in-VMEM gather"),
    "fused_ell": (NotImplementedError, "1-D in-VMEM gather"),
    "sell": (NotImplementedError, "dynamic_slice has no TPU lowering"),
    "fused_sell": (NotImplementedError, "dynamic_slice has no TPU lowering"),
    "balanced": (ValueError, "(1, nnz_pad) block not divisible by 8"),
}


@pytest.mark.parametrize("kernel", [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=exc, reason=f"Mosaic: {why} (ROADMAP A2)"))
    for name, (exc, why) in REFUSALS.items()])
def test_pallas_kernel_compiles_for_v5e(topo, kernel):
    from jax.sharding import SingleDeviceSharding
    fn, shapes, static = _pallas_cases()[kernel]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
            for s in shapes]
    # interpret mode lowers to plain HLO: the call itself is well formed
    fn.lower(*args, interpret=True, **static).compile()
    fn.lower(*args, interpret=False, **static).compile()
