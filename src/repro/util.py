"""Small shared helpers used across core/, kernels/ and sparse/."""
from __future__ import annotations

import os
from typing import Any, Iterator

import jax
import numpy as np

from repro.spans import count, span

__all__ = ["align_up", "shard_map_compat", "make_mesh_compat",
           "host_array", "place_operands", "BoundJit",
           "enable_compile_cache", "use_host_devices",
           "compiled_hlo_text", "collective_counts",
           "collective_counts_from_text", "while_body_collective_counts",
           "while_body_collective_counts_from_text", "census_split",
           "COLLECTIVE_OPS", "SOLVER_REDUCTION_OPS", "TRANSPORT_OPS",
           "PRIM_COLLECTIVE", "iter_jaxpr_eqns", "subjaxprs",
           "jaxpr_collective_counts", "jaxpr_while_eqns"]

COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "all-to-all", "collective-permute",
                  "collective-broadcast")

#: the two sides of the census.  The SpMV shard body deliberately emits no
#: reduction collectives (ghost assembly is gather + local add, see
#: ``repro.core.spmv.make_shard_body``), so in a compiled Krylov loop body
#: every op in SOLVER_REDUCTION_OPS belongs to the solver's own reductions
#: and every op in TRANSPORT_OPS to the halo transport + vector-layout
#: assembly.
SOLVER_REDUCTION_OPS = ("all-reduce", "reduce-scatter")
TRANSPORT_OPS = ("all-gather", "all-to-all", "collective-permute",
                 "collective-broadcast")


def census_split(counts: dict) -> dict:
    """Split a per-kind census into solver reductions vs transport traffic
    (the per-collective-kind attribution the bench harness reports)."""
    return {"solver_reductions": sum(counts.get(k, 0)
                                     for k in SOLVER_REDUCTION_OPS),
            "transport_ops": sum(counts.get(k, 0) for k in TRANSPORT_OPS)}


def collective_counts(jitted, *args) -> dict:
    """Count collective ops in the compiled (post-SPMD) HLO of ``jitted``.

    Lowers with the given example args, compiles, and greps the HLO module
    text.  Counting the *compiled* module matters: the baseline CG's dot
    products are auto-sharded, so their all-reduces only exist after GSPMD
    partitioning.  A ``while`` body appears exactly once in the module text,
    so the counts reflect one loop iteration plus setup.
    """
    return collective_counts_from_text(compiled_hlo_text(jitted, *args))


def compiled_hlo_text(jitted, *args) -> str:
    """Post-optimization HLO module text of ``jitted`` for ``args``.

    XLA compilation dominates the cost of the census helpers — callers
    needing both the module-wide and the while-body census should compile
    once here and use the ``*_from_text`` variants.
    """
    return jitted.lower(*args).compile().as_text()


def collective_counts_from_text(txt: str) -> dict:
    import re

    # async collectives lower to start/done pairs (e.g. all-reduce-start on
    # TPU); count the start as the op and ignore the matching done
    return {name: len(re.findall(rf"{name}(-start)?\(", txt))
            for name in COLLECTIVE_OPS}


#: jaxpr primitive -> compiled-HLO collective kind (the COLLECTIVE_OPS
#: vocabulary).  This is the bridge between the two census layers: the
#: static analyzer (repro.analysis.jaxpr_pass) counts primitives in
#: device-free ``jax.make_jaxpr(..., axis_env=...)`` traces, while the CI
#: bench assertions count the same kinds in compiled HLO text — both must
#: speak predicted_cost's language.
PRIM_COLLECTIVE = {
    "psum": "all-reduce",
    "psum_scatter": "reduce-scatter",
    "reduce_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pbroadcast": "collective-broadcast",
}


def subjaxprs(eqn) -> Iterator[Any]:
    """Yield every jaxpr held in ``eqn.params`` (closed or open).

    Handles all the shapes jax uses: a single ClosedJaxpr/Jaxpr param
    (``while``'s ``body_jaxpr``/``cond_jaxpr``, ``pjit``'s ``jaxpr``) and
    tuple/list-valued params (``cond``'s ``branches``).  Missing the
    tuple case silently skips every ``lax.cond`` branch — the pipelined
    CG's drift-correction restart lives in one — so iterate containers
    before testing each element.
    """
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):        # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):       # open Jaxpr
                yield x


def iter_jaxpr_eqns(jaxpr) -> Iterator[Any]:
    """Yield every equation of ``jaxpr`` and all nested sub-jaxprs
    (while/cond/pjit/scan bodies), depth-first."""
    if hasattr(jaxpr, "jaxpr"):            # accept ClosedJaxpr too
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn):
            yield from iter_jaxpr_eqns(sub)


def jaxpr_collective_counts(jaxpr) -> dict:
    """Per-kind collective census of a (device-free) jaxpr trace.

    The static twin of :func:`collective_counts_from_text`: count the
    collective primitives of ``jaxpr`` (nested sub-jaxprs included) and
    report them under the COLLECTIVE_OPS names, so the result is directly
    comparable to a transport's ``predicted_cost`` and to the compiled-HLO
    census — without devices, a mesh, or an XLA compile.
    """
    counts = {name: 0 for name in COLLECTIVE_OPS}
    for eqn in iter_jaxpr_eqns(jaxpr):
        kind = PRIM_COLLECTIVE.get(eqn.primitive.name)
        if kind is not None:
            counts[kind] += 1
    return counts


def jaxpr_while_eqns(jaxpr) -> list:
    """Every ``while`` equation of ``jaxpr``, nested ones included — the
    static analogue of finding ``body=`` computations in compiled HLO."""
    return [eqn for eqn in iter_jaxpr_eqns(jaxpr)
            if eqn.primitive.name == "while"]


def _hlo_computations(txt: str) -> dict:
    """Split compiled-HLO module text into {computation name: body text}.

    Computation definitions start at column 0 as ``[ENTRY ]%name (params)
    -> type {`` and end at the matching column-0 ``}``.
    """
    import re

    comps: dict = {}
    name, lines = None, []
    for line in txt.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name, lines = m.group(1), []
        elif line.startswith("}") and name is not None:
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def while_body_collective_counts(jitted, *args) -> dict:
    """Collective ops inside the compiled while-loop body — the exact
    per-iteration census of a fused solver.

    ``collective_counts`` counts the whole module (loop + setup);
    this helper parses the post-optimization HLO into computations, finds
    the computations referenced as ``body=`` by ``while`` ops, and counts
    only inside them.  For a fused Krylov solve that is precisely the cost
    of one iteration: e.g. the registry ``cg`` shows 2 ``all-reduce`` per
    iteration (p·Ap and the stacked [r·z, r·r]), ``pipelined_cg`` exactly
    1, ``chebyshev`` 0 (the SpMV's ghost assembly is gather+add, never an
    all-reduce — see ``repro.core.spmv.make_shard_body``).

    Raises ValueError if the compiled module has no while loop.
    """
    return while_body_collective_counts_from_text(
        compiled_hlo_text(jitted, *args))


def while_body_collective_counts_from_text(txt: str) -> dict:
    """:func:`while_body_collective_counts` on pre-compiled HLO text."""
    import re

    comps = _hlo_computations(txt)
    body_names = set()
    for m in re.finditer(r"body=\s*%?([\w\.\-]+)", txt):
        body_names.add(m.group(1))
    bodies = [comps[n] for n in body_names if n in comps]
    if not bodies:
        raise ValueError("no while-loop body computation found in the "
                         "compiled HLO — is the solve actually a fused "
                         "while_loop?")
    counts = {name: 0 for name in COLLECTIVE_OPS}
    for body in bodies:
        for name, k in collective_counts_from_text(body).items():
            counts[name] += k
    return counts


def make_mesh_compat(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types: the mesh's axes are used by
    both ``shard_map`` and auto-sharded ops."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def align_up(v: int, a: int) -> int:
    """Round ``v`` up to the next multiple of ``a`` (at least ``a``)."""
    return int(max(a, -(-int(v) // a) * a))


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the SpMV/CG shard
    bodies mix per-shard data with collectives in ways the static checker
    cannot verify."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def host_array(a, dtype=None) -> np.ndarray:
    """``a`` as the host numpy array ``jnp.asarray(a, dtype)`` would hold
    (same canonical dtype: int64 -> int32, float64 -> float32).  Operator
    arrays stay on the host until a program factory places them."""
    a = np.asarray(a)
    return a.astype(jax.dtypes.canonicalize_dtype(
        a.dtype if dtype is None else dtype), copy=False)


def place_operands(arrays, mesh, axis_names=("node", "core")) -> tuple:
    """Put each ``(n_node, n_core, d, ...)`` operator array on ``mesh``
    once, split over its two leading dims, so no call reshards or
    relayouts it.

    The two mesh dims are stored merged into ``d``, as one
    ``(n_node * n_core * d, ...)`` array: a device's shard is then
    ``(d, ...)``, with no unit dims.  A ``(1, 1, d, rows)`` shard is laid
    out by TPU with a unit dim second-minor, which is not the tiling the
    programs read it in, so every call would copy the whole operator
    first.  :class:`BoundJit` restores the shape inside the program.

    Timed as span ``solver.place`` up to the end of the transfer; the
    bytes on the devices are added to counter ``place.bytes``."""
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec(tuple(axis_names)))
    with span("solver.place"):
        placed = jax.block_until_ready(tuple(jax.device_put(
            np.reshape(a, (int(np.prod(np.shape(a)[:3])), *np.shape(a)[3:])),
            sharding) for a in arrays))
    count("place.bytes", sum(_device_bytes(a) for a in placed))
    return placed


def _device_bytes(a) -> int:
    """Bytes ``a`` takes on its devices, summed over them (replicas
    included), from its sharding alone."""
    shard = a.sharding.shard_shape(a.shape)
    return (int(np.prod(shard)) * np.dtype(a.dtype).itemsize
            * len(a.sharding.device_set))


def _operand_shape(op) -> tuple:
    """The ``(n_node, n_core, d, ...)`` shape of an operand stored merged
    by :func:`place_operands`."""
    mesh, spec = op.sharding.mesh, op.sharding.spec
    dims = tuple(mesh.shape[name] for name in spec[0])
    return (*dims, op.shape[0] // int(np.prod(dims)), *op.shape[1:])


class BoundJit:
    """``jax.jit(fn)`` with its leading operator arguments bound.

    The operator reaches the program as arguments placed by
    :func:`place_operands`, never as closure constants: the compiled
    program does not grow with nnz.  Calls, ``lower`` and ``_cache_size``
    take the trailing arguments only.  ``fn`` (the jitted program, taking
    the stored operands first) and ``operands`` are exposed for lowering
    against other (e.g. abstract) operands; ``program`` is ``fn`` before
    binding, taking each operand as ``(n_node, n_core, ...)``.

    ``out_specs`` (with ``mesh``) commits the outputs to the shard_map's
    own output specs, so an output fed back into a program reaches the
    executable it came from.
    """

    def __init__(self, fn, operands, mesh=None, out_specs=None):
        from jax.sharding import NamedSharding, PartitionSpec
        self.program = fn
        self.operands = tuple(operands)
        shapes = tuple(_operand_shape(op) for op in self.operands)

        def bound(*args):
            ops = tuple(op.reshape(s) for op, s in zip(args, shapes))
            return fn(*ops, *args[len(shapes):])

        kw = {}
        if out_specs is not None:
            kw["out_shardings"] = jax.tree.map(
                lambda s: NamedSharding(mesh, s), out_specs,
                is_leaf=lambda s: isinstance(s, PartitionSpec))
        self.fn = jax.jit(bound, **kw)

    def __call__(self, *args):
        return self.fn(*self.operands, *args)

    def lower(self, *args):
        return self.fn.lower(*self.operands, *args)

    def _cache_size(self) -> int:
        return self.fn._cache_size()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads
    it itself); otherwise the cache lives at the fixed ``.jax_cache`` of
    this checkout, so its keys stay stable from run to run.  For entry
    points only: tests never call it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def use_host_devices(n: int) -> None:
    """Ask the CPU platform for ``n`` devices, so an ``n``-device mesh runs
    on a host without accelerators.  Call before JAX touches a backend.

    The option sizes the CPU client alone: on a TPU host the mesh is built
    from the real chips, and the CPU devices go unused."""
    jax.config.update("jax_num_cpu_devices", n)
