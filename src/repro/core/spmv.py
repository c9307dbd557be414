"""Hybrid hierarchical-parallel distributed SpMV — the paper's contribution.

PETSc's MPIAIJ SpMV runs in two phases (paper Sec. 1.1):
  1. diagonal block x local vector, while remote vector elements are gathered;
  2. off-diagonal block x gathered ghost elements, added to the partial result.

The hybrid MPI/OpenMP hierarchy maps onto a 2-D device mesh:

  ``node`` axis  — MPI-rank analogue.  Block rows of A are distributed over
                   ``node``; the input vector is likewise row-distributed and
                   ghost entries are exchanged with a static halo plan
                   (one fused ``all_to_all``; see ``repro.core.halo``).
  ``core`` axis  — OpenMP-thread analogue.  Rows *within* a node group are
                   subdivided over ``core`` with **no halo communication**;
                   the node-local input slice is assembled by an intra-group
                   ``all_gather`` (the shared-memory read analogue).

Three algorithm modes, exactly as benchmarked in the paper (Sec. 2, Fig. 2):

  ``vector``    equal-*rows* split over cores, and the ghost exchange is
                *serialised* before the diagonal multiply (an
                ``optimization_barrier`` pins the schedule) — modelling
                master-only comm with no true asynchronous progress.
  ``task``      same row split, but the exchange and the diagonal multiply
                are data-independent in the HLO, so the XLA latency-hiding
                scheduler overlaps them — the task-based comm/compute overlap.
  ``balanced``  ``task`` + the greedy+diffusion **nnz-balanced** partition on
                *both* mesh axes (paper Sec. 2.3, applied hierarchically:
                nodes get nnz-balanced global row blocks, then each node's
                rows get nnz-balanced core bins — ``partition_two_level``).
                On TPU this also minimises static-shape padding, so balance
                == less wasted compute.  ``node_partition="rows"`` restores
                the equal-rows node split (the pure-MPI row distribution).

The halo exchange is **owner-split** (see ``repro.core.halo``): every core
sends the boundary rows its own bin holds, indexed straight into its
``(rc_pad,)`` vector shard, so the exchange launches without waiting for
the intra-node ``all_gather``; on receive each core scatters only its own
slice and an intra-node gather + local add combines the partial ghost
buffers (each slot has exactly one writer, so no all-reduce is needed — and
none is emitted, keeping the Krylov layer's collective census exact).

The exchange **strategy is pluggable** (``repro.core.transport``): the
plan stamps a transport name (``a2a`` | ``ring`` | ``pairwise`` | ``hier``),
``make_shard_body`` dispatches the owner-split exchange to it, and
``autotune_transport`` / ``transport="auto"`` time the candidates on the
live mesh and stamp the winner — the exchange winner is matrix- and
machine-dependent (Schubert et al., arXiv:1106.5908).

Shard-local matrix **storage is pluggable** (``repro.sparse.formats``): the
plan carries a format name plus the format-owned device arrays
(``fmt_data``), and the per-shard two-phase multiply dispatches to the
format's jnp or Pallas matvec.  ``format="ell"`` is the historical
row-padded layout; ``format="sell"`` is sliced ELL (SELL-C-σ) whose
σ-window row sorting is folded into the plan's slot maps
(``x_gather``/``global_row_of``/halo plan), so every downstream layer is
format-agnostic.  Plans with no halo traffic (single-node or
block-diagonal matrices) have ``hs == 0`` and the shard body skips the
ghost exchange and the off-diagonal phase entirely.

The per-shard two-phase multiply is shared between the standalone SpMV
(``make_spmv``) and the fully-sharded fused CG solver
(``repro.core.sharded_cg``) via ``make_shard_body``.
See DESIGN.md for the full data flow.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.halo import HaloPlan, build_halo_plan
from repro.core.partition import (NODE_PARTITIONS, partition_stats,
                                  partition_two_level)
from repro.core.transport import (HaloTransport, get_codec,
                                  resolve_transport, transport_census,
                                  transport_stamp)
from repro.spans import count, span
from repro.sparse.csr import CSRMatrix, ShardBlocks
from repro.sparse.formats import ShardFormat, get_format
from repro.util import (BoundJit, align_up, host_array, place_operands,
                        shard_map_compat)

__all__ = ["SpMVPlan", "build_spmv_plan", "make_spmv", "make_shard_body",
           "plan_shard_arrays", "plan_fields", "COMMON_FIELDS",
           "SHARD_FIELDS", "MODES"]

MODES = ("vector", "task", "balanced")

#: format-independent plan fields consumed by the shard body, in argument
#: order (the format's own ``fields`` come first).
COMMON_FIELDS = ("send_own", "recv_own", "x_gather")

#: legacy alias: the shard-body argument order of the historical ELL-only
#: plan.  Prefer ``plan_fields(plan)``, which is format-aware.
SHARD_FIELDS = ("diag_cols", "diag_vals", "offd_cols", "offd_vals",
                "send_own", "recv_own", "x_gather")


@partial(jax.tree_util.register_dataclass,
         data_fields=["fmt_data", "send_own", "recv_own", "x_gather",
                      "diag_a", "mask", "mask_col"],
         meta_fields=["n", "n_node", "n_core", "rc_pad", "nl_pad", "g_pad",
                      "hs", "mode", "format", "transport", "wire_dtype",
                      "n_cols", "cc_pad"])
@dataclasses.dataclass
class SpMVPlan:
    """Device-ready distributed matrix + halo plan (a pytree).

    Leading axes of every data field are (n_node, n_core, ...) so that
    ``shard_map`` with ``P('node', 'core')`` assigns one slice per device.
    Vectors in "CG layout" (the **row space** — SpMV outputs, Krylov
    iterates) are (n_node, n_core, rc_pad); SpMV *inputs* live in the
    **column space**, (n_node, n_core, cc_pad) (``x_shape``).  For square
    plans with no explicit column-space override the two spaces coincide
    (``cc_pad == rc_pad``, ``mask_col is mask``) and every array is
    bit-identical to the historical square-only plans; rectangular plans
    (n_cols != n) key the halo/ghost machinery and ``x_gather`` on a
    separate column-space partition.
    """

    # format-owned local matrix blocks, one entry per format field
    # (e.g. ELL: diag/offd cols+vals (n_node, n_core, rc_pad, w);
    #  SELL: flat slice-major streams (n_node, n_core, nnz_pad))
    fmt_data: dict[str, jax.Array]
    # owner-split halo plan (indices into the core's own (rc_pad,) shard)
    send_own: jax.Array    # (n_node, n_core, n_node, hs) int32
    recv_own: jax.Array    # (n_node, n_core, n_node, hs) int32 -> ghost slot
    # vector layout maps
    x_gather: jax.Array     # (n_node, n_core, nl_pad) int32 (replicated on core)
    diag_a: jax.Array       # (n_node, n_core, rc_pad) diag(A) in CG layout (1 at pad)
    mask: jax.Array         # (n_node, n_core, rc_pad) 1.0 valid / 0.0 padding
    # static meta
    n: int
    n_node: int
    n_core: int
    rc_pad: int
    nl_pad: int
    g_pad: int
    hs: int
    mode: str
    format: str
    # the plan's selected halo transport (repro.core.transport).  Defaults
    # to the VecScatter-analogue all_to_all; ``autotune_transport`` stamps
    # the measured winner here, and ``make_spmv``/``make_solver`` with
    # ``transport=None`` follow the stamp.
    transport: str = "a2a"
    # the plan's halo wire codec (repro.core.transport.WireCodec):
    # "f32" (exact), "bf16", or "int8" — ghost payloads ride the
    # inter-node wire at this dtype; the ghost-buffer accumulate stays
    # the vector dtype.  Builders with ``wire_dtype=None`` follow the
    # stamp.
    wire_dtype: str = "f32"
    # column-space meta (rectangular operators; default to the row space,
    # preserving the historical square plan bit-for-bit)
    n_cols: int = -1       # -1 -> n (square)
    cc_pad: int = -1       # -1 -> rc_pad (square)
    # (n_node, n_core, cc_pad) 1.0 valid / 0.0 padding in the *input*
    # (column-space) layout; the same array object as ``mask`` for square
    # plans with the default column space.
    mask_col: jax.Array | None = None

    def __post_init__(self):
        if self.n_cols < 0:
            self.n_cols = self.n
        if self.cc_pad < 0:
            self.cc_pad = self.rc_pad
        if self.mask_col is None:
            self.mask_col = self.mask

    # ------------------------------------------------------------------ #
    @property
    def cg_shape(self) -> tuple[int, int, int]:
        """Row-space (output / Krylov iterate) distributed shape."""
        return (self.n_node, self.n_core, self.rc_pad)

    @property
    def x_shape(self) -> tuple[int, int, int]:
        """Column-space (SpMV input) distributed shape."""
        return (self.n_node, self.n_core, self.cc_pad)

    def nnz_stored(self) -> int:
        return get_format(self.format).nnz_stored(self.fmt_data)

    # legacy ELL accessors (KeyError for other formats)
    @property
    def diag_cols(self) -> jax.Array:
        return self.fmt_data["diag_cols"]

    @property
    def diag_vals(self) -> jax.Array:
        return self.fmt_data["diag_vals"]

    @property
    def offd_cols(self) -> jax.Array:
        return self.fmt_data["offd_cols"]

    @property
    def offd_vals(self) -> jax.Array:
        return self.fmt_data["offd_vals"]


def plan_fields(plan: SpMVPlan) -> tuple[str, ...]:
    """Shard-body argument names: the format's fields, then the common ones."""
    return get_format(plan.format).fields + COMMON_FIELDS


def plan_shard_arrays(plan: SpMVPlan) -> tuple[jax.Array, ...]:
    """The plan's shard-body inputs in ``plan_fields`` order."""
    fmt = get_format(plan.format)
    return tuple(plan.fmt_data[f] for f in fmt.fields) + (
        plan.send_own, plan.recv_own, plan.x_gather)


# ---------------------------------------------------------------------- #
# host-side plan construction (one-off, cached with the matrix)
# ---------------------------------------------------------------------- #
def build_spmv_plan(A: CSRMatrix, n_node: int, n_core: int,
                    mode: str = "balanced", dtype=jnp.float32,
                    rows_align: int = 8, width_align: int = 1,
                    node_partition: str | None = None,
                    format: str | ShardFormat = "ell",
                    transport: str | HaloTransport = "a2a",
                    wire_dtype: str = "f32",
                    row_space: dict | None = None,
                    col_space: dict | None = None,
                    verify: bool = False
                    ) -> tuple[SpMVPlan, dict]:
    """Partition ``A``, split diag/offdiag, pack shard blocks + halo plan.

    ``mode="balanced"`` balances non-zeros on **both** mesh axes
    (``partition_two_level``): nodes get nnz-balanced global row blocks and
    each node's rows get nnz-balanced core bins.  ``vector``/``task`` use
    equal rows on both axes — the paper's pure-MPI row distribution.
    ``node_partition`` ("rows" | "nnz") overrides the node-axis strategy
    independently of ``mode`` (e.g. ``"rows"`` reproduces the old
    equal-rows node split under balanced core bins).

    ``format`` selects the shard-local storage (``repro.sparse.formats``):
    ``"ell"`` (row-padded, the historical layout) or ``"sell"`` (sliced
    ELL with σ-window row sorting, whose storage tracks true nnz — the
    cheap companion of the two-level balanced partition).  The format's
    row permutation is folded into every layout map, so ``to_dist`` /
    ``from_dist`` / the halo plan are format-agnostic.

    ``transport`` stamps the plan's halo transport
    (``repro.core.transport``; validated here, so a typo fails at plan
    build, not at trace time inside ``shard_map``).  ``"auto"`` defers the
    choice to ``autotune_transport`` at the first ``make_spmv`` /
    ``make_solver`` on a live mesh.  ``wire_dtype`` stamps the halo wire
    codec ("f32" | "bf16" | "int8" — also validated here): ghost payloads
    ride the inter-node wire compressed to that dtype while the ghost
    accumulate stays f32.

    Returns (plan, layout) where ``layout`` carries the host-side index
    arrays needed by ``to_dist`` / ``from_dist``, a ``stats`` dict with
    per-axis ``imbalance()`` and the format-computed ``padding_waste``,
    and ``transport_census`` — every registered transport's predicted
    exchange cost (padded wire bytes + per-kind collective counts) for
    this plan.

    ``A`` may be **rectangular** (n_rows != n_cols): the row partition /
    slot layout / mask / diag are keyed on the row space as before, while
    column ownership — the halo plan, ``x_gather`` and the input-vector
    layout — is keyed on a separate column-space partition (same two-level
    strategy over per-column nnz).  Square inputs with no explicit
    ``col_space`` reduce *bit-identically* to the historical square-only
    plans (``tests/golden_square_hashes.json`` pins this).

    ``row_space`` / ``col_space`` pin the corresponding partition to an
    existing plan's layout instead of computing one — dicts with keys
    ``node_bounds`` (n_node+1,), ``core_bounds`` (per-node arrays),
    ``lr`` (per-node bin-local slot maps) and ``pad`` (the shard slot
    count), exactly what ``layout["row_space"]`` / ``layout["col_space"]``
    of the plan to pin against carry.  This is how restriction /
    prolongation plans lock their shared spaces to the fine operator's
    exact slot layout (including a SELL plan's σ-window permutation).

    ``verify=True`` runs the static contract verifier's host layers
    (``repro.analysis``: plan invariants + kernel index-stream bounds)
    on the finished plan and raises ``ValueError`` on any error-severity
    violation — the same checks ``repro.testing.analyze`` sweeps in CI,
    available inline for plans built outside the registry sweep.
    """
    with span("plan.build"):
        return _build_spmv_plan(A, n_node, n_core, mode, dtype, rows_align,
                                width_align, node_partition, format,
                                transport, wire_dtype, row_space, col_space,
                                verify)


def _build_spmv_plan(A, n_node, n_core, mode, dtype, rows_align, width_align,
                     node_partition, format, transport, wire_dtype,
                     row_space, col_space, verify):
    """:func:`build_spmv_plan`'s work, inside its ``plan.build`` span."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    # -- up-front shape validation: fail here, not at pack/trace time ----- #
    if A.n_rows < 1:
        raise ValueError("build_spmv_plan: empty row space "
                         f"(A.shape = {A.shape}); the plan needs at least "
                         "one row to partition")
    if A.n_cols < 1:
        raise ValueError("build_spmv_plan: empty column space "
                         f"(A.shape = {A.shape})")
    if A.indices.size:
        c_lo, c_hi = int(A.indices.min()), int(A.indices.max())
        if c_lo < 0 or c_hi >= A.n_cols:
            raise ValueError(
                "build_spmv_plan: stored column index out of range for "
                f"shape {A.shape}: indices span [{c_lo}, {c_hi}] but "
                f"n_cols = {A.n_cols}")
    if transport != "auto":
        transport = transport_stamp(transport)       # fail fast on typos
    wire_dtype = get_codec(wire_dtype).name          # fail fast on typos
    if node_partition is None:
        node_partition = "nnz" if mode == "balanced" else "rows"
    if node_partition not in NODE_PARTITIONS:
        raise ValueError(f"node_partition must be one of {NODE_PARTITIONS}, "
                         f"got {node_partition!r}")
    fmt = get_format(format)
    n = A.n_rows
    core_partition = "nnz" if mode == "balanced" else "rows"
    if row_space is not None:
        node_bounds = np.asarray(row_space["node_bounds"], dtype=np.int64)
        core_bounds_all = [np.asarray(cb, dtype=np.int64)
                           for cb in row_space["core_bounds"]]
        if len(node_bounds) != n_node + 1 or int(node_bounds[-1]) != n:
            raise ValueError(
                f"row_space pin inconsistent with A: node_bounds covers "
                f"[0, {int(node_bounds[-1])}] over {len(node_bounds) - 1} "
                f"node(s), matrix has {n} rows on {n_node} node(s)")
    else:
        node_bounds, core_bounds_all = partition_two_level(
            A.row_nnz, n_node, n_core,
            node_partition=node_partition,
            core_partition=core_partition)

    # Column-space partition: for square inputs with no override it *is*
    # the row partition (same array objects -> the historical square plan,
    # bit for bit); otherwise it is pinned (``col_space``) or computed as
    # an independent two-level split over per-column nnz.
    square_default = (A.n_cols == n) and col_space is None
    if square_default:
        col_node_bounds, col_core_bounds = node_bounds, core_bounds_all
    elif col_space is not None:
        col_node_bounds = np.asarray(col_space["node_bounds"],
                                     dtype=np.int64)
        col_core_bounds = [np.asarray(cb, dtype=np.int64)
                           for cb in col_space["core_bounds"]]
        if (len(col_node_bounds) != n_node + 1
                or int(col_node_bounds[-1]) != A.n_cols):
            raise ValueError(
                f"col_space pin inconsistent with A: node_bounds covers "
                f"[0, {int(col_node_bounds[-1])}] over "
                f"{len(col_node_bounds) - 1} node(s), matrix has "
                f"{A.n_cols} columns on {n_node} node(s)")
    else:
        col_nnz = np.bincount(A.indices.astype(np.int64),
                              minlength=A.n_cols) \
            if A.indices.size else np.zeros(A.n_cols, dtype=np.int64)
        col_node_bounds, col_core_bounds = partition_two_level(
            col_nnz, n_node, n_core,
            node_partition=node_partition,
            core_partition=core_partition)

    # uniform static shapes across every (node, core) shard
    rc_pad = align_up(max(int(np.diff(cb).max()) for cb in core_bounds_all),
                      rows_align)
    if row_space is not None and row_space.get("pad") is not None:
        if int(row_space["pad"]) < rc_pad:
            raise ValueError(f"row_space pad {row_space['pad']} smaller "
                             f"than the largest core bin ({rc_pad} slots)")
        rc_pad = int(row_space["pad"])
    if square_default:
        cc_pad = rc_pad
    else:
        cc_pad = align_up(
            max(int(np.diff(cb).max()) for cb in col_core_bounds),
            rows_align)
        if col_space is not None and col_space.get("pad") is not None:
            if int(col_space["pad"]) < cc_pad:
                raise ValueError(
                    f"col_space pad {col_space['pad']} smaller than the "
                    f"largest column core bin ({cc_pad} slots)")
            cc_pad = int(col_space["pad"])
    # x_gather width: the widest node-local *column* count (== the widest
    # node-local row count for square plans)
    nl_pad = align_up(max(int(col_node_bounds[i + 1] - col_node_bounds[i])
                          for i in range(n_node)), rows_align)

    x_gather = np.zeros((n_node, n_core, nl_pad), dtype=np.int32)
    mask = np.zeros((n_node, n_core, rc_pad), dtype=np.float64)
    diag_a = np.ones((n_node, n_core, rc_pad), dtype=np.float64)
    # host layout maps for to_dist / from_dist
    global_row_of = np.full((n_node, n_core, rc_pad), -1, dtype=np.int64)
    # bin-local *column* id -> input-vector-layout slot, per shard (for the
    # halo remap; the column space is the row space for square plans)
    slot_of = np.zeros((n_node, n_core, cc_pad), dtype=np.int32)

    lr_all: list[np.ndarray] = []
    for i in range(n_node):
        lo = int(node_bounds[i])
        nl = int(node_bounds[i + 1]) - lo
        cb = core_bounds_all[i]
        ar = np.arange(nl, dtype=np.int64)
        c_of = np.searchsorted(cb, ar, side="right") - 1   # owning core per row
        if row_space is not None and row_space.get("lr") is not None:
            lr = np.asarray(row_space["lr"][i], dtype=np.int64)  # pinned slots
        else:
            lr = fmt.slot_order(A.row_nnz[lo:lo + nl], cb)   # slot in the bin
        lr_all.append(lr)
        mask[i, c_of, lr] = 1.0
        global_row_of[i, c_of, lr] = lo + ar
        if square_default:
            # column space == row space: the input-vector maps reuse the
            # row-space structures unchanged (the historical code path)
            x_gather[i, :, :nl] = (c_of * rc_pad + lr)[None, :]
            slot_of[i, c_of, ar - cb[c_of]] = lr

    if square_default:
        col_lr_all = lr_all
        mask_col = mask
        global_col_of = global_row_of
    else:
        col_lr_all = []
        mask_col = np.zeros((n_node, n_core, cc_pad), dtype=np.float64)
        global_col_of = np.full((n_node, n_core, cc_pad), -1, dtype=np.int64)
        for i in range(n_node):
            clo = int(col_node_bounds[i])
            ncl = int(col_node_bounds[i + 1]) - clo
            ccb = col_core_bounds[i]
            ar = np.arange(ncl, dtype=np.int64)
            c_of = np.searchsorted(ccb, ar, side="right") - 1
            if col_space is not None and col_space.get("lr") is not None:
                lr = np.asarray(col_space["lr"][i], dtype=np.int64)
            else:
                lr = ar - ccb[c_of]     # identity slot order within the bin
            col_lr_all.append(lr)
            x_gather[i, :, :ncl] = (c_of * cc_pad + lr)[None, :]
            mask_col[i, c_of, lr] = 1.0
            global_col_of[i, c_of, lr] = clo + ar
            slot_of[i, c_of, ar - ccb[c_of]] = lr

    # the diag/offd split: a pass over A only where a node does not own
    # every column (its ghost map and per-row split)
    with span("plan.split"):
        blocks = ShardBlocks(A, node_bounds, col_node_bounds,
                             core_bounds_all, lr_all)
    with span("plan.pack"):
        fmt_data, fill = fmt.pack(blocks, rc_pad, width_align, dtype)
        diag_full = blocks.walk(fill)
    count("plan.pack.chunks", len(blocks.chunks))
    count("plan.pack.threads", blocks.threads)
    ghost_cols = blocks.ghost_cols

    if A.n_cols == n:       # square: diag(A) exists and Jacobi needs it
        zero_diag = np.flatnonzero(diag_full == 0)
        if zero_diag.size:
            raise ValueError(
                f"A has a zero or missing diagonal entry on {zero_diag.size} "
                f"owned row(s) (first: row {int(zero_diag[0])}); the Jacobi "
                "preconditioner 1/diag(A) would be infinite there.  Add a "
                "diagonal shift or fix the assembly.")
        owned = global_row_of >= 0
        diag_a[owned] = diag_full[global_row_of[owned]]
    # rectangular: no diagonal; diag_a stays ones

    with span("plan.halo"):
        halo: HaloPlan = build_halo_plan(ghost_cols, col_node_bounds, n_core,
                                         core_bounds=col_core_bounds)
        # halo send indices are bin-local row ids; route them through the
        # format's slot assignment (identity for ELL) so the exchange reads
        # the permuted vector shards correctly with no format special case
        send_own = slot_of[np.arange(n_node)[:, None, None, None],
                           np.arange(n_core)[None, :, None, None],
                           halo.send_own]

        # neighbour structure (diagnostics; the ring/pairwise transports
        # derive the same structure from the plan's own recv table): which
        # (dst - src) mod n offsets actually carry halo traffic.
        # Contiguous partitions of banded (extrusion-ordered) matrices
        # touch only a few neighbours.
        pair_counts = np.zeros((n_node, n_node), dtype=np.int64)
        for dst in range(n_node):
            g = np.asarray(ghost_cols[dst], dtype=np.int64)
            if g.size:
                owner = np.searchsorted(col_node_bounds, g, side="right") - 1
                pair_counts[dst] = np.bincount(owner, minlength=n_node)
        offsets = sorted({int((dst - src) % n_node)
                          for dst in range(n_node) for src in range(n_node)
                          if pair_counts[dst, src] > 0})

    # every plan array stays on the host: the program factories place
    # each one on their mesh once (``repro.util.place_operands``)
    plan = SpMVPlan(
        fmt_data=fmt_data,
        send_own=host_array(send_own),
        recv_own=host_array(halo.recv_own),
        x_gather=host_array(x_gather),
        diag_a=host_array(diag_a, dtype=dtype),
        mask=host_array(mask, dtype=dtype),
        n=n, n_node=n_node, n_core=n_core,
        rc_pad=rc_pad, nl_pad=nl_pad, g_pad=halo.g_pad, hs=halo.h_own,
        mode=mode, format=fmt.name, transport=transport,
        wire_dtype=wire_dtype,
        n_cols=A.n_cols, cc_pad=cc_pad,
        mask_col=(None if square_default
                  else host_array(mask_col, dtype=dtype)),
    )
    # what one SpMV moves between devices, summed over them: the node
    # exchange as the stamped transport predicts it (none stamped yet under
    # "auto"), and the core-axis all_gather of the x bins
    census = transport_census(plan)
    if transport != "auto":
        count("halo.wire_bytes", census[transport]["wire_bytes"])
    count("halo.ghosts", halo.total_ghosts)
    count("spmv.gather_bytes", n_node * n_core * (n_core - 1) * cc_pad
          * np.dtype(dtype).itemsize)
    stats = partition_stats(A.row_nnz, node_bounds, core_bounds_all)
    # fraction of stored slots (diag + offd, all shards) holding no real
    # entry — computed by the format, since only it knows what it pads
    stats["padding_waste"] = fmt.padding_waste(fmt_data, A.nnz)
    layout = {
        "node_bounds": node_bounds,
        "core_bounds": core_bounds_all,
        "node_partition": node_partition,
        "format": fmt.name,
        "global_row_of": global_row_of,
        "global_col_of": global_col_of,
        "halo": halo,
        "neighbor_offsets": offsets,
        "pair_counts": pair_counts,
        "transport_census": census,
        "stats": stats,
        # partition descriptors another plan can pin its spaces to
        # (restriction / prolongation locking onto this plan's layout)
        "row_space": {"node_bounds": node_bounds,
                      "core_bounds": core_bounds_all,
                      "lr": lr_all, "pad": rc_pad},
        "col_space": {"node_bounds": col_node_bounds,
                      "core_bounds": col_core_bounds,
                      "lr": col_lr_all, "pad": cc_pad},
    }
    if verify:
        # late import: repro.analysis sits above core in the layering
        from repro.analysis import check_kernel_streams, check_plan
        rep = check_plan(plan, layout)
        rep.extend(check_kernel_streams(plan).violations)
        if rep.errors:
            raise ValueError(
                "build_spmv_plan(verify=True): plan violates "
                f"{len(rep.errors)} static contract(s):\n  "
                + "\n  ".join(str(v) for v in rep.errors))
    return plan, layout


# ---------------------------------------------------------------------- #
# vector layout conversion (host)
# ---------------------------------------------------------------------- #
def to_dist(v: np.ndarray, layout: dict, plan: SpMVPlan,
            dtype=None, space: str = "col") -> jax.Array:
    """Global vector -> distributed layout.  Driven entirely by the
    layout's slot tables, so it is exact for non-uniform ``node_bounds``
    (two-level nnz partitions) and format row permutations alike.

    ``space="col"`` (default) produces the SpMV *input* layout — an
    ``(n_cols,)`` vector into ``plan.x_shape``; ``space="row"`` produces
    the output / Krylov-iterate layout — ``(n,)`` into ``plan.cg_shape``.
    For square plans with the default column space the two are identical
    (so existing square callers see no change)."""
    if space not in ("row", "col"):
        raise ValueError(f"space must be 'row' or 'col', got {space!r}")
    if space == "col":
        g = layout.get("global_col_of", layout["global_row_of"])
        shape = plan.x_shape
    else:
        g = layout["global_row_of"]
        shape = plan.cg_shape
    out = np.zeros(shape, dtype=np.asarray(v).dtype)
    valid = g >= 0
    out[valid] = np.asarray(v)[g[valid]]
    return jnp.asarray(out, dtype=dtype or plan.mask.dtype)


def from_dist(vd: jax.Array, layout: dict, plan: SpMVPlan,
              space: str = "row") -> np.ndarray:
    """Distributed layout -> global vector (inverse of ``to_dist``;
    ``space="row"`` (default) reads ``plan.cg_shape`` SpMV outputs,
    ``space="col"`` reads ``plan.x_shape`` input-layout vectors)."""
    if space not in ("row", "col"):
        raise ValueError(f"space must be 'row' or 'col', got {space!r}")
    if space == "col":
        g = layout.get("global_col_of", layout["global_row_of"])
        n = plan.n_cols
    else:
        g = layout["global_row_of"]
        n = plan.n
    vd = np.asarray(vd)
    out = np.zeros(n, dtype=vd.dtype)
    valid = g >= 0
    out[g[valid]] = vd[valid]
    return out


# ---------------------------------------------------------------------- #
# the distributed SpMV shard body (shared by make_spmv and the fused CG)
# ---------------------------------------------------------------------- #
def make_shard_body(plan: SpMVPlan,
                    axis_names: tuple[str, str] = ("node", "core"),
                    backend: str = "jnp",
                    transport: str | HaloTransport | None = None,
                    neighbor_offsets: list[int] | None = None,
                    wire_dtype: str | None = None):
    """Build the per-shard two-phase SpMV body: ``body(F, x_mine) -> y_mine``.

    ``F`` maps ``plan_fields(plan)`` names (plus the transport's
    ``body.extra`` arrays) to per-shard arrays (leading (1, 1) shard dims
    already stripped); ``x_mine`` is this core's (cc_pad,) bin of the
    distributed *input* (column-space) vector — (rc_pad,) and identical
    to the output layout for square plans — and the returned ``y_mine``
    is the (rc_pad,) row-space bin.  Meant to run *inside* a ``shard_map``
    over
    ``axis_names`` — ``make_spmv`` wraps it directly and ``repro.solvers``
    calls it from the fused Krylov ``while_loop``.

    The halo exchange dispatches to the plan's registered
    ``HaloTransport`` (``repro.core.transport``; ``transport=None``
    follows ``plan.transport``, ``wire_dtype=None`` follows
    ``plan.wire_dtype``).  Whatever the transport, the body emits
    **zero all-reduces** — ghost assembly is gather + local add (each
    ghost slot has exactly one writer), so any all-reduce in a compiled
    solver loop is attributable to the solver's own reductions
    (``repro.solvers``' collective census).  The per-transport collective
    counts are the transport's ``predicted_cost`` plus the one core-axis
    ``all_gather`` that assembles the node-local ``x`` slice.

    Plans with **no halo traffic** (``plan.hs == 0`` — single-node or
    block-diagonal matrices) skip the exchange and the ghost assembly
    entirely and run the diagonal phase alone.

    Transport name and state are validated *here*, up front — an unknown
    transport or an incomplete ``neighbor_offsets`` override raises
    ``ValueError`` (naming the registered transports) before any tracing
    starts.  The returned ``body`` carries ``body.transport`` (resolved
    name) and ``body.extra`` (the transport's extra device arrays, to be
    appended to the shard_map inputs after ``plan_fields(plan)``).

    ``backend``: 'jnp' or 'pallas' — dispatched to the plan format's local
    matvec (``repro.sparse.formats``; Pallas kernels run interpret-mode on
    CPU).
    """
    node_ax, core_ax = axis_names
    mode = plan.mode
    n_node, g_pad, rc_pad = plan.n_node, plan.g_pad, plan.rc_pad
    has_halo = plan.hs > 0
    transport = transport if transport is not None else plan.transport
    if transport == "auto":
        raise ValueError("transport='auto' is resolved by make_spmv/"
                         "make_solver (needs a live mesh to time); "
                         "make_shard_body takes a concrete transport")
    tr, tstate = resolve_transport(transport, plan,
                                   neighbor_offsets=neighbor_offsets,
                                   wire_dtype=wire_dtype)

    fmt = get_format(plan.format)
    if backend == "pallas":
        if jax.default_backend() == "tpu":
            # ROADMAP A2: Mosaic refuses every kernel as written — the ELL
            # kernels' 1-D in-VMEM gather ("Only 2D gather is supported"),
            # the SELL kernels' dynamic_slice, and the balanced kernel's
            # (1, nnz_pad) block (not divisible by 8).  The chip path is
            # backend="jnp" (XLA's gather).
            raise NotImplementedError(
                "backend='pallas' does not compile for TPU yet: Mosaic "
                "refuses the SpMV kernels (1-D gather in the ELL kernels, "
                "dynamic_slice in the SELL kernels); use backend='jnp'")
        local_matvec = fmt.matvec_pallas
    elif backend == "jnp":
        local_matvec = fmt.matvec_jnp
    else:
        raise ValueError(f"unknown backend {backend!r}")

    @jax.named_scope("spmv")
    def body(F: dict, x_mine: jax.Array) -> jax.Array:
        if has_halo:
            # -- VecScatter analogue: owner-split halo exchange straight from
            #    this core's shard (no dependence on the intra-node gather) --
            with jax.named_scope("halo"):
                x_ghost = tr.exchange(x_mine, F, state=tstate,
                                      axes=axis_names, n_node=n_node,
                                      g_pad=g_pad)
        else:
            x_ghost = None      # halo-free plan: no exchange, no ghost phase

        # -- shared-memory read analogue: assemble the node-local x slice --
        with jax.named_scope("assemble"):
            x_bins = jax.lax.all_gather(x_mine, core_ax, axis=0)  # (n_core, cc_pad)
            x_local = x_bins.reshape(-1)[F["x_gather"]]       # (nl_pad,)

        if mode == "vector":
            # master-only comm: no asynchronous progress — the diagonal
            # multiply must wait for the exchange to finish.
            if x_ghost is None:
                x_local = jax.lax.optimization_barrier(x_local)
            else:
                x_local, x_ghost = jax.lax.optimization_barrier(
                    (x_local, x_ghost))

        with jax.named_scope("local"):
            return local_matvec(F, x_local, x_ghost, rc_pad)

    body.transport = tr.name
    body.wire_dtype = tstate["wire_codec"].name
    body.extra = tr.extra_arrays(plan, tstate) if has_halo else {}
    return body


# ---------------------------------------------------------------------- #
# standalone jitted SpMV
# ---------------------------------------------------------------------- #
def make_spmv(plan: SpMVPlan, mesh: jax.sharding.Mesh,
              axis_names: tuple[str, str] = ("node", "core"),
              backend: str = "jnp",
              transport: str | HaloTransport | None = None,
              neighbor_offsets: list[int] | None = None,
              wire_dtype: str | None = None):
    """Build the jitted distributed SpMV:
    ``plan.x_shape`` (n_node, n_core, cc_pad) -> ``plan.cg_shape``
    (n_node, n_core, rc_pad) — the same shape for square plans.

    ``backend``: 'jnp' or 'pallas' — dispatched to the plan's shard format
    (``repro.sparse.formats``; Pallas kernels run interpret-mode on CPU).

    ``transport`` selects the halo-exchange strategy by name
    (``repro.core.transport``: 'a2a' | 'ring' | 'pairwise' | 'hier' — see
    the module docstring for when each wins).  ``None`` follows the plan's
    stamp (``plan.transport``); ``"auto"`` runs ``autotune_transport`` on
    this mesh, stamps the winner into the plan and returns the winner's
    compiled SpMV.  ``wire_dtype`` selects the halo wire codec
    ('f32' | 'bf16' | 'int8'; ``None`` follows ``plan.wire_dtype``).  The
    returned function carries ``spmv.transport`` / ``spmv.wire_dtype``
    (the resolved names).
    """
    transport = transport if transport is not None else plan.transport
    if transport == "auto":     # explicit, or a deferred plan stamp
        from repro.core.transport import autotune_transport
        return autotune_transport(plan, mesh, axis_names=axis_names,
                                  backend=backend,
                                  neighbor_offsets=neighbor_offsets,
                                  wire_dtype=wire_dtype).spmv
    node_ax, core_ax = axis_names
    body = make_shard_body(plan, axis_names=axis_names, backend=backend,
                           transport=transport,
                           neighbor_offsets=neighbor_offsets,
                           wire_dtype=wire_dtype)
    fields = plan_fields(plan) + tuple(body.extra)

    def shard_fn(*args):
        *consts, xd = args
        # strip the leading (1, 1, ...) shard dims
        F = {k: v[0, 0] for k, v in zip(fields, consts)}
        return body(F, xd[0, 0])[None, None]    # (1, 1, rc_pad)

    spec = P(node_ax, core_ax)
    fn = shard_map_compat(shard_fn, mesh=mesh,
                          in_specs=(spec,) * (len(fields) + 1),
                          out_specs=spec)
    spmv = BoundJit(fn, place_operands(
        plan_shard_arrays(plan) + tuple(body.extra.values()), mesh,
        axis_names), mesh, spec)
    spmv.transport = body.transport
    spmv.wire_dtype = body.wire_dtype
    return spmv
