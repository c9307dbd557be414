"""Pluggable per-shard matrix storage formats — the ``ShardFormat`` layer.

``build_spmv_plan`` used to hardcode row-padded ELL blocks into the plan,
the shard body and both solvers.  Every storage decision now lives behind a
``ShardFormat``: the format owns

  * the **vector-layout slot** of every row within its core bin
    (``slot_order`` — identity for ELL, σ-window nnz sorting for SELL; the
    permutation is folded into ``x_gather``/``global_row_of``/``mask``/the
    halo plan by ``build_spmv_plan``, so ``to_dist``/``from_dist`` and the
    exchange machinery need no per-format special cases);
  * the **host-side packing** of the per-(node, core) diag/offd blocks
    into device arrays (``pack`` — one dict entry per name in ``fields``,
    every array leading with ``(n_node, n_core)`` shard dims, filled chunk
    by chunk straight from the CSR, ``repro.sparse.csr.ShardBlocks``);
  * the **local two-phase matvec** in both backends (``matvec_jnp`` /
    ``matvec_pallas``), called from inside the ``shard_map`` body with the
    assembled ``x_local`` slice and the exchanged ``x_ghost`` buffer
    (``x_ghost is None`` when the plan has no halo traffic — block-diagonal
    or single-node matrices — and the ghost phase must be skipped).  The
    buffer arrives fully assembled whatever ``HaloTransport``
    (``repro.core.transport``) produced it: real slots ``< g_pad`` carry
    the owners' bits, the trailing dump slot is write-only garbage a
    matvec must never read (pad ``offd`` entries point at slot 0 with
    zero values instead);
  * its own storage **accounting** (``nnz_stored`` / ``padding_waste``) —
    the plan no longer guesses what counts as padding.

Two formats ship:

``ell``   row-padded ELLPACK, the historical layout: every shard stores
          ``(width, rc_pad)`` blocks sized by the heaviest bin/row.  Cheap
          gathers, but on skewed matrices the nnz-balanced two-level
          partition inflates ``rc_pad`` × ``width`` multiplicatively (see
          DESIGN.md §6).
``sell``  sliced ELL (SELL-C-σ, Schubert/Kreutzer et al.): rows are sorted
          by nnz within σ-row windows, grouped into slices of C rows, and
          each slice is padded to its *own* width, flattened slice-major
          with an explicit slot index per entry.  Storage tracks true nnz,
          so the nnz-balanced partition also balances *storage* — balanced
          mode stops paying the ELL padding bill.  The segmented reduction
          runs as scatter-add (jnp) or a one-hot MXU matmul chunk loop
          (Pallas, same technique as ``balanced_spmv_pallas``).

Formats register by name (``register_format``); ``build_spmv_plan``,
``make_shard_body`` and the CLIs resolve them through ``get_format``.
Custom instances (e.g. a different slice height) can be registered under
their own name — the packed arrays carry all pack-time parameters, so the
matvec dispatch only needs the name.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import ShardBlocks, sell_slices
from repro.util import align_up

__all__ = ["IndexStream", "ShardFormat", "ELLFormat", "SELLFormat",
           "register_format", "get_format", "available_formats"]


@dataclasses.dataclass(frozen=True)
class IndexStream:
    """Static descriptor of one gather/scatter index stream of a format.

    The Pallas/jnp matvecs read the vector buffers through these index
    arrays with **no runtime bounds checks** — on real accelerators an
    out-of-range index is an out-of-bounds read or a corrupting write,
    not an exception.  Each format therefore declares its streams so the
    static kernel checker (``repro.analysis.kernel_check``) can prove,
    per plan, that every index stays inside its buffer extent and that
    padding entries are value-masked, before anything executes.

    ``vals``/``cols`` name entries of ``fmt_data``; ``x`` says which
    buffer ``cols`` indexes (``"local"`` — the assembled ``(nl_pad,)``
    slice — or ``"ghost"`` — the ``(g_pad + 1,)`` exchanged buffer whose
    trailing dump slot only zero-valued entries may read); ``rows``, when
    set, is the accumulation-slot stream scattered into the ``(rc_pad,)``
    output (``None`` for row-aligned layouts like ELL, where entry ``i``
    accumulates into row ``i`` by construction).
    """

    vals: str
    cols: str
    x: str
    rows: str | None = None


class ShardFormat:
    """Interface of a shard-local matrix storage format.

    Subclasses set ``name`` (registry key) and ``fields`` (device-array
    names, in the order the shard body receives them) and implement
    ``pack``/``nnz_stored``/``matvec_jnp``/``matvec_pallas``.
    """

    name: str = ""
    fields: tuple[str, ...] = ()

    # -- vector layout ------------------------------------------------- #
    def slot_order(self, row_nnz_local: np.ndarray,
                   core_bounds: np.ndarray) -> np.ndarray:
        """Storage/vector slot of every node-local row within its core bin.

        Returns ``(nl,)`` with ``slot[r]`` a permutation of ``0..nb-1``
        inside each bin.  The default keeps rows in ascending order
        (slot == bin-local row id) — any override is transparently folded
        into the plan's layout maps and halo plan by ``build_spmv_plan``.
        """
        cb = np.asarray(core_bounds, dtype=np.int64)
        ar = np.arange(len(row_nnz_local), dtype=np.int64)
        c_of = np.searchsorted(cb, ar, side="right") - 1
        return ar - cb[c_of]

    # -- host-side packing --------------------------------------------- #
    def pack(self, blocks: ShardBlocks, rc_pad: int, width_align: int,
             dtype) -> tuple[dict[str, np.ndarray], Callable]:
        """Allocate the device arrays of ``blocks``' shards and return them
        with ``fill(chunk)``, which writes one ``ShardChunk``'s entries
        into them.  One ``(n_node, n_core, ...)`` array per name in
        ``fields``, values in ``dtype`` (canonical, as ``host_array``
        gives).  The plan builder runs ``fill`` over ``blocks.walk``,
        maybe on several threads at once: chunks own disjoint rows, and
        ``fill`` writes only their slots."""
        raise NotImplementedError

    # -- static contract ----------------------------------------------- #
    def index_streams(self) -> tuple[IndexStream, ...]:
        """The format's gather/scatter streams, for the static bounds
        checker (``repro.analysis.kernel_check``).  Every field that
        indexes a vector buffer or the output must be declared here — an
        undeclared index stream is itself flagged by the analyzer."""
        return ()

    # -- accounting ---------------------------------------------------- #
    def nnz_stored(self, data: dict[str, jax.Array]) -> int:
        """Total value slots held on device, padding included."""
        raise NotImplementedError

    def padding_waste(self, data: dict[str, jax.Array],
                      nnz_true: int) -> float:
        """Fraction of stored slots holding no real matrix entry."""
        return 1.0 - nnz_true / max(self.nnz_stored(data), 1)

    # -- device-side local matvec -------------------------------------- #
    def matvec_jnp(self, F: dict[str, jax.Array], x_local: jax.Array,
                   x_ghost: jax.Array | None, rc_pad: int) -> jax.Array:
        """Two-phase shard matvec, vectorised jnp.  ``x_ghost is None``
        means the plan has no halo traffic: skip the ghost phase."""
        raise NotImplementedError

    def matvec_pallas(self, F: dict[str, jax.Array], x_local: jax.Array,
                      x_ghost: jax.Array | None, rc_pad: int) -> jax.Array:
        """Two-phase shard matvec through the one-pass Pallas kernel."""
        raise NotImplementedError


def _max_width(row_nnz: list[np.ndarray], align: int) -> int:
    """Largest row nnz over the nodes, aligned — 0 when every block is
    empty (no dead ``(rc_pad, 1)`` gather for halo-free matrices)."""
    w = max((int(rn.max()) for rn in row_nnz if rn.size), default=0)
    return align_up(w, align) if w else 0


def _values_dtype(dtype) -> np.dtype:
    """The value dtype ``host_array`` gives for ``dtype``."""
    return np.dtype(jax.dtypes.canonicalize_dtype(dtype))


def _ell_rows_dot(vals, cols, x):
    """``y[r] = sum_k vals[k, r] * x[cols[k, r]]`` over a width-major ELL
    block, one gathered column at a time: the adds run in a fixed k order
    (every program computes the same bits, whatever else it fuses) and no
    ``(width, rc_pad)`` gather is materialised."""
    y = jnp.zeros(vals.shape[-1], vals.dtype)
    for k in range(vals.shape[0]):
        y = y + vals[k] * x[cols[k]].astype(vals.dtype)
    return y


# --------------------------------------------------------------------- #
# ELL — the historical row-padded layout
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ELLFormat(ShardFormat):
    """Row-padded ELLPACK blocks, stored ``(width, rc_pad)`` per shard.

    Width-major storage keeps the long row axis minor: TPU HBM tiles the
    two minor dims by (8, 128), so a ``(rc_pad, width)`` block with width
    ~19 would pad ~6.7x (it does not fit one v5e chip at the Fig. 3
    size), while ``(width, rc_pad)`` pads only width up to a multiple of
    8."""

    name = "ell"
    fields = ("diag_cols", "diag_vals", "offd_cols", "offd_vals")

    def index_streams(self):
        # row-aligned: entry (k, r) accumulates into row r, so there is
        # no explicit rows stream to range-check
        return (IndexStream(vals="diag_vals", cols="diag_cols", x="local"),
                IndexStream(vals="offd_vals", cols="offd_cols", x="ghost"))

    def pack(self, blocks, rc_pad, width_align, dtype):
        shard = (blocks.n_node, blocks.n_core)
        parts = [blocks.row_nnz(i) for i in range(blocks.n_node)]
        out, targets = {}, []
        for p, (cname, vname) in enumerate((("diag_cols", "diag_vals"),
                                            ("offd_cols", "offd_vals"))):
            w = _max_width([rn[p] for rn in parts], width_align)
            out[cname] = np.zeros(shard + (w, rc_pad), dtype=np.int32)
            out[vname] = np.zeros(shard + (w, rc_pad),
                                  dtype=_values_dtype(dtype))
            targets.append((out[cname], out[vname]))

        def fill(ch):
            for (cols_out, vals_out), (rn, cols, vals) in zip(
                    targets, (ch.diag, ch.offd)):
                if not len(cols):
                    continue
                # entry k of slot q sits at k * rc_pad + q of the shard's
                # (width, rc_pad) block
                first = np.cumsum(rn) - rn
                pos = (np.arange(len(cols), dtype=np.int64) * rc_pad
                       + np.repeat(ch.slots - first * rc_pad, rn))
                cols_out[ch.node, ch.core].reshape(-1)[pos] = cols
                vals_out[ch.node, ch.core].reshape(-1)[pos] = vals

        return out, fill

    def nnz_stored(self, data):
        return int(data["diag_cols"].size + data["offd_cols"].size)

    def matvec_jnp(self, F, x_local, x_ghost, rc_pad):
        y = _ell_rows_dot(F["diag_vals"], F["diag_cols"], x_local)
        if x_ghost is None:
            return y
        return y + _ell_rows_dot(F["offd_vals"], F["offd_cols"], x_ghost)

    def matvec_pallas(self, F, x_local, x_ghost, rc_pad):
        # the kernels take row-major (rows, width) blocks
        from repro.kernels.ops import ell_spmv, fused_ell_spmv
        if x_ghost is None:
            return ell_spmv(F["diag_vals"].T, F["diag_cols"].T, x_local)
        return fused_ell_spmv(F["diag_vals"].T, F["diag_cols"].T,
                              F["offd_vals"].T, F["offd_cols"].T,
                              x_local, x_ghost)


# --------------------------------------------------------------------- #
# SELL — sliced ELL with σ-window row sorting (SELL-C-σ)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SELLFormat(ShardFormat):
    """Sliced ELL: per-slice widths after σ-window nnz sorting.

    ``slice_height`` is the C of SELL-C-σ; ``sigma`` the sorting window in
    rows (``None`` sorts the whole core bin — maximal packing; finite σ
    bounds how far the permutation moves a row, which keeps the σ-sorted
    vector layout close to the mesh ordering).  ``nnz_align`` pads the
    cross-shard flattened storage length.
    """

    slice_height: int = 8
    sigma: int | None = None
    nnz_align: int = 8

    name = "sell"
    fields = ("sell_dvals", "sell_dcols", "sell_drows",
              "sell_ovals", "sell_ocols", "sell_orows")

    def index_streams(self):
        return (IndexStream(vals="sell_dvals", cols="sell_dcols",
                            x="local", rows="sell_drows"),
                IndexStream(vals="sell_ovals", cols="sell_ocols",
                            x="ghost", rows="sell_orows"))

    def slot_order(self, row_nnz_local, core_bounds):
        cb = np.asarray(core_bounds, dtype=np.int64)
        row_nnz_local = np.asarray(row_nnz_local, dtype=np.int64)
        lr = np.empty(len(row_nnz_local), dtype=np.int64)
        for c in range(len(cb) - 1):
            lo, hi = int(cb[c]), int(cb[c + 1])
            nb = hi - lo
            if nb == 0:
                continue
            bl = np.arange(nb, dtype=np.int64)
            win = bl // (self.sigma if self.sigma else nb)
            # per window: heaviest rows first (ties keep mesh order)
            order = np.lexsort((bl, -row_nnz_local[lo:hi], win))
            s = np.empty(nb, dtype=np.int64)
            s[order] = bl
            lr[lo:hi] = s
        return lr

    def pack(self, blocks, rc_pad, width_align, dtype):
        C = self.slice_height
        shard = (blocks.n_node, blocks.n_core)
        slices, sizes = {}, ([0], [0])
        for i in range(blocks.n_node):
            cb = blocks.core_bounds[i]
            parts = blocks.row_nnz(i)
            for c in range(blocks.n_core):
                lo, hi = int(cb[c]), int(cb[c + 1])
                for p in (0, 1):
                    w, starts = sell_slices(parts[p][lo:hi],
                                            blocks.slots[i][lo:hi], C)
                    slices[i, c, p] = w, starts
                    sizes[p].append(int(starts[-1]))
        out, targets = {}, []
        for p, names in enumerate((("sell_dvals", "sell_dcols", "sell_drows"),
                                   ("sell_ovals", "sell_ocols", "sell_orows"))):
            pad = (align_up(max(sizes[p]), self.nnz_align)
                   if max(sizes[p]) else 0)
            out[names[0]] = np.zeros(shard + (pad,),
                                     dtype=_values_dtype(dtype))
            out[names[1]] = np.zeros(shard + (pad,), dtype=np.int32)
            out[names[2]] = np.zeros(shard + (pad,), dtype=np.int32)
            targets.append(tuple(out[k] for k in names))

        def fill(ch):
            q = ch.slots
            for p, ((vals_out, cols_out, rows_out), (rn, cols, vals)) in \
                    enumerate(zip(targets, (ch.diag, ch.offd))):
                if not len(cols):
                    continue
                w, starts = slices[ch.node, ch.core, p]
                s = q // C
                first = np.cumsum(rn) - rn
                pos = np.arange(len(cols), dtype=np.int64) + np.repeat(
                    starts[s] + (q - s * C) * w[s] - first, rn)
                vals_out[ch.node, ch.core][pos] = vals
                cols_out[ch.node, ch.core][pos] = cols
                rows_out[ch.node, ch.core][pos] = np.repeat(q, rn)

        return out, fill

    def nnz_stored(self, data):
        return int(data["sell_dvals"].size + data["sell_ovals"].size)

    def matvec_jnp(self, F, x_local, x_ghost, rc_pad):
        dv = F["sell_dvals"]
        y = jnp.zeros((rc_pad,), dv.dtype).at[F["sell_drows"]].add(
            dv * x_local[F["sell_dcols"]].astype(dv.dtype))
        if x_ghost is None or F["sell_ovals"].shape[-1] == 0:
            return y
        ov = F["sell_ovals"]
        return y.at[F["sell_orows"]].add(
            ov * x_ghost[F["sell_ocols"]].astype(ov.dtype))

    def matvec_pallas(self, F, x_local, x_ghost, rc_pad):
        from repro.kernels.ops import fused_sell_spmv
        if x_ghost is None or F["sell_ovals"].shape[-1] == 0:
            x_ghost = None
        return fused_sell_spmv(F["sell_dvals"], F["sell_dcols"],
                               F["sell_drows"], F["sell_ovals"],
                               F["sell_ocols"], F["sell_orows"],
                               x_local, x_ghost, rc_pad=rc_pad)


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #
_FORMATS: dict[str, ShardFormat] = {}


def register_format(fmt: ShardFormat, overwrite: bool = False) -> ShardFormat:
    """Register ``fmt`` under ``fmt.name`` for lookup by plan builders."""
    if not fmt.name or not fmt.fields:
        raise ValueError("a ShardFormat needs a non-empty name and fields")
    if fmt.name in _FORMATS and not overwrite:
        raise ValueError(f"shard format {fmt.name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    _FORMATS[fmt.name] = fmt
    return fmt


def get_format(fmt: str | ShardFormat) -> ShardFormat:
    """Resolve a format name (or pass through an instance)."""
    if isinstance(fmt, ShardFormat):
        return fmt
    try:
        return _FORMATS[fmt]
    except KeyError:
        raise ValueError(f"unknown shard format {fmt!r}; available: "
                         f"{available_formats()}") from None


def available_formats() -> tuple[str, ...]:
    return tuple(sorted(_FORMATS))


register_format(ELLFormat())
register_format(SELLFormat())
