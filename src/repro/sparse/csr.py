"""Sparse matrix containers.

Three representations are used throughout the framework:

``CSRMatrix``
    Host-side (numpy) compressed sparse row storage.  All assembly,
    partitioning and halo-plan construction happens here, mirroring the
    paper's observation that "the matrix stencil does not change during the
    solve" so arbitrarily complex partitioning is a one-off host-side cost
    cached with the matrix.

``ELLMatrix``
    Device-side padded row-major (ELLPACK) storage: every row padded to the
    same width.  TPU/XLA-friendly (static shapes, vectorised gather) and is
    the "vector-based threading" analogue: work is split by *rows*.

``BalancedCOO``
    Device-side format for the Pallas kernel and the "thread-balanced" mode:
    rows are grouped into ``nbins`` contiguous bins holding an approximately
    equal number of *non-zeros* (greedy + diffusion partition, see
    ``repro.core.partition``).  Each bin is padded to a common nonzero count,
    so the nnz balancing directly minimises static-shape padding waste — the
    TPU-native payoff of the paper's load-balancing idea.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CSRMatrix", "ShardBlocks", "ShardChunk", "ELLMatrix",
           "BalancedCOO", "sell_slices", "sell_arrays_from_csr"]


@dataclasses.dataclass
class CSRMatrix:
    """Host-side CSR matrix (numpy arrays)."""

    indptr: np.ndarray   # (n_rows + 1,) int64
    indices: np.ndarray  # (nnz,) int32/int64 column indices
    data: np.ndarray     # (nnz,) float
    shape: tuple[int, int]

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)

    # ------------------------------------------------------------------ #
    # constructors
    # ------------------------------------------------------------------ #
    @classmethod
    def from_coo(cls, rows, cols, vals, shape) -> "CSRMatrix":
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        # sum duplicates
        if len(rows):
            key = rows.astype(np.int64) * shape[1] + cols.astype(np.int64)
            uniq, inv = np.unique(key, return_inverse=True)
            sums = np.zeros(len(uniq), dtype=vals.dtype)
            np.add.at(sums, inv, vals)
            rows = (uniq // shape[1]).astype(np.int64)
            cols = (uniq % shape[1]).astype(np.int64)
            vals = sums
        indptr = np.zeros(shape[0] + 1, dtype=np.int64)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr)
        return cls(indptr=indptr, indices=cols.astype(np.int64), data=vals,
                   shape=tuple(shape))

    @classmethod
    def from_dense(cls, dense: np.ndarray) -> "CSRMatrix":
        rows, cols = np.nonzero(dense)
        return cls.from_coo(rows, cols, dense[rows, cols], dense.shape)

    @classmethod
    def from_scipy(cls, m) -> "CSRMatrix":
        m = m.tocsr()
        return cls(indptr=np.asarray(m.indptr, dtype=np.int64),
                   indices=np.asarray(m.indices, dtype=np.int64),
                   data=np.asarray(m.data),
                   shape=tuple(m.shape))

    # ------------------------------------------------------------------ #
    # host-side ops
    # ------------------------------------------------------------------ #
    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=self.data.dtype)
        for r in range(self.n_rows):
            lo, hi = self.indptr[r], self.indptr[r + 1]
            out[r, self.indices[lo:hi]] += self.data[lo:hi]
        return out

    def _row_of_nnz(self) -> np.ndarray:
        """(nnz,) row id of every stored entry."""
        return np.repeat(np.arange(self.n_rows, dtype=np.int64), self.row_nnz)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference host SpMV (oracle for everything else)."""
        out_dtype = np.result_type(self.data, x)
        y = np.zeros(self.n_rows, dtype=out_dtype)
        if self.nnz == 0:
            return y
        prod = self.data * np.asarray(x)[self.indices]
        if np.issubdtype(out_dtype, np.floating):
            return np.bincount(self._row_of_nnz(),
                               weights=prod.astype(np.float64),
                               minlength=self.n_rows).astype(out_dtype)
        # exact (if slower) path for complex/other dtypes bincount can't hold
        np.add.at(y, self._row_of_nnz(), prod.astype(out_dtype))
        return y

    def transpose(self) -> "CSRMatrix":
        """Aᵀ as a new CSRMatrix (host; e.g. prolongation P = Rᵀ)."""
        return CSRMatrix.from_coo(self.indices, self._row_of_nnz(),
                                  self.data, (self.n_cols, self.n_rows))

    def diagonal(self) -> np.ndarray:
        d = np.zeros(self.n_rows, dtype=self.data.dtype)
        if self.nnz:
            hit = self.indices == self._row_of_nnz()
            # reversed so the FIRST stored duplicate wins, matching the
            # historical per-row scan
            d[self.indices[hit][::-1]] = self.data[hit][::-1]
        return d


# ---------------------------------------------------------------------- #
# the shard blocks of a partitioned CSR, walked in chunks
# ---------------------------------------------------------------------- #
#: stored entries one chunk of the shard walk holds, about: a chunk's
#: temporaries stay ~20 MB, and a Fig. 3-size matrix gives ~700 chunks
_CHUNK_NNZ = 1 << 19
#: below this many stored entries the walk runs inline, with no pool
_POOL_MIN_NNZ = 1 << 20


def _host_threads() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:          # no affinity call on this platform
        return os.cpu_count() or 1


@dataclasses.dataclass(frozen=True)
class ShardChunk:
    """Node-local rows ``[lo, hi)`` of node ``node``, all in core bin
    ``core``.  ``slots[r]`` is each row's slot in the bin; ``diag`` and
    ``offd`` are each block's ``(row_nnz, cols, vals)``: entries row after
    row, in stored order, ``cols`` in the block's own column numbering."""

    node: int
    core: int
    lo: int
    hi: int
    slots: np.ndarray
    diag: tuple
    offd: tuple


class ShardBlocks:
    """``A``'s rows as the diagonal and off-diagonal blocks of every
    ``(node, core)`` shard, read straight from ``A`` in row chunks.

    Node ``i`` owns rows ``[node_bounds[i], node_bounds[i+1])`` and columns
    ``[col_bounds[i], col_bounds[i+1])``; ``core_bounds[i]`` splits its
    rows into core bins and ``slots[i]`` gives each node-local row its
    slot in its bin.  An entry in an owned column is in the diagonal
    block, its column counted from the node's first; any other is in the
    off-diagonal block, its column renumbered into ``ghost_cols[i]``
    (PETSc's MPIAIJ split, with its compressed ghost map).  Entries keep
    their stored order within a row.

    Chunks hold about ``_CHUNK_NNZ`` entries, never cross a core bin, and
    are cut by nonzeros, not rows (the paper's thread balance, on the
    host).  From ``_POOL_MIN_NNZ`` entries on, they run on a pool of the
    host's threads: numpy's indexing drops the GIL, and chunks own
    disjoint rows and slots.  Nothing scales with the whole matrix but
    the outputs; a node whose columns span all of ``A``'s has an empty
    off-diagonal block, and its chunks copy nothing for it.
    """

    def __init__(self, A: CSRMatrix, node_bounds, col_bounds, core_bounds,
                 slots):
        self.A = A
        self.node_bounds = [int(b) for b in node_bounds]
        self.col_bounds = [int(b) for b in col_bounds]
        self.core_bounds = [np.asarray(cb, dtype=np.int64)
                            for cb in core_bounds]
        self.slots = slots
        self.n_node = len(self.node_bounds) - 1
        self.n_core = len(self.core_bounds[0]) - 1
        ip = A.indptr
        self.chunks = []
        for i in range(self.n_node):
            base = self.node_bounds[i]
            cb = self.core_bounds[i]
            for c in range(self.n_core):
                a, b = int(cb[c]), int(cb[c + 1])
                if a == b:
                    continue
                rows_ip = ip[base + a:base + b + 1]
                nnz = int(rows_ip[-1] - rows_ip[0])
                pieces = max(1, -(-nnz // _CHUNK_NNZ))
                cuts = a + np.searchsorted(
                    rows_ip, rows_ip[0] + nnz * np.arange(1, pieces) / pieces)
                edges = np.unique(np.concatenate(([a], cuts, [b])))
                self.chunks += [(i, c, int(lo), int(hi))
                                for lo, hi in zip(edges[:-1], edges[1:])]
        # a node owning every column needs no split pass
        self.covers = [self.col_bounds[i] == 0
                       and self.col_bounds[i + 1] == A.n_cols
                       for i in range(self.n_node)]
        self.ghost_cols = [np.zeros(0, dtype=np.int64)] * self.n_node
        self._offd_nnz = [None] * self.n_node
        split = [i for i in range(self.n_node) if not self.covers[i]]
        for i in split:
            self._offd_nnz[i] = np.zeros(
                self.node_bounds[i + 1] - self.node_bounds[i], dtype=np.int64)
        found = self._map(self._split_pass,
                          [t for t in self.chunks if t[0] in split])
        for i in split:
            outs = [g for t, g in found if t[0] == i and g.size]
            if outs:
                self.ghost_cols[i] = np.unique(np.concatenate(outs))

    def row_nnz(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """Per node-local row of node ``i``: its entries in the diagonal
        block and in the off-diagonal block."""
        lo, hi = self.node_bounds[i], self.node_bounds[i + 1]
        rn = np.diff(self.A.indptr[lo:hi + 1])
        offd = self._offd_nnz[i]
        if offd is None:
            return rn, np.zeros_like(rn)
        return rn - offd, offd

    def _rows(self, i, lo, hi):
        """Node ``i``'s local rows ``[lo, hi)``: their row nnz, and their
        entries' columns and values (views of ``A``)."""
        ip = self.A.indptr
        g = self.node_bounds[i]
        s, e = int(ip[g + lo]), int(ip[g + hi])
        return (ip[g + lo + 1:g + hi + 1] - ip[g + lo:g + hi],
                self.A.indices[s:e], self.A.data[s:e])

    def _split_pass(self, task):
        """A chunk's off-diagonal row nnz, and its columns outside the
        node (unique)."""
        i, _, lo, hi = task
        rn, cols, _ = self._rows(i, lo, hi)
        out = np.flatnonzero((cols < self.col_bounds[i])
                             | (cols >= self.col_bounds[i + 1]))
        row = np.searchsorted(np.cumsum(rn), out, side="right")
        self._offd_nnz[i][lo:hi] = np.bincount(row, minlength=hi - lo)
        return task, np.unique(cols[out])

    def _map(self, fn, tasks) -> list:
        """``fn`` over ``tasks``, on the host's threads if ``A`` is large;
        sets ``threads`` to the pool size (1 inline)."""
        n = min(_host_threads(), len(tasks))
        if self.A.nnz < _POOL_MIN_NNZ or n < 2:
            self.threads = 1
            return [fn(t) for t in tasks]
        self.threads = n
        with ThreadPoolExecutor(max_workers=n) as pool:
            return list(pool.map(fn, tasks))

    def walk(self, fill) -> np.ndarray:
        """Run ``fill(chunk)`` on every :class:`ShardChunk`, and return
        ``A``'s diagonal, read in the same pass: ``(n_rows,)``, 0 where a
        row stores none, the first stored duplicate winning (as
        :meth:`CSRMatrix.diagonal`)."""
        diag = np.zeros(self.A.n_rows, dtype=self.A.data.dtype)

        def one(task):
            i, c, lo, hi = task
            rn, cols, vals = self._rows(i, lo, hi)
            g = self.node_bounds[i]
            r = np.repeat(np.arange(g + lo, g + hi, dtype=np.int64), rn)
            hit = np.flatnonzero(cols == r)[::-1]
            diag[r[hit]] = vals[hit]
            if self.covers[i]:         # its columns are A's, from 0
                d = (rn, cols, vals)
                o = (np.zeros_like(rn), cols[:0], vals[:0])
            else:
                clo = self.col_bounds[i]
                ins = (cols >= clo) & (cols < self.col_bounds[i + 1])
                out = ~ins
                rn_o = self._offd_nnz[i][lo:hi]
                d = (rn - rn_o, cols[ins] - clo, vals[ins])
                o = (rn_o, np.searchsorted(self.ghost_cols[i], cols[out]),
                     vals[out])
            fill(ShardChunk(i, c, lo, hi, self.slots[i][lo:hi], d, o))

        self._map(one, self.chunks)
        return diag


# ---------------------------------------------------------------------- #
# device formats (registered as pytrees)
# ---------------------------------------------------------------------- #
def ell_arrays_from_csr(m: CSRMatrix, width: int | None = None,
                        n_rows_pad: int | None = None
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side ELL packing: returns (cols int32, vals float64) numpy."""
    rn = m.row_nnz
    w = int(width if width is not None else (rn.max() if m.n_rows else 1))
    w = max(w, 1)
    nr = int(n_rows_pad if n_rows_pad is not None else m.n_rows)
    cols = np.zeros((nr, w), dtype=np.int32)
    vals = np.zeros((nr, w), dtype=np.float64)
    if m.nnz:
        if int(rn.max()) > w:
            raise ValueError(f"max row nnz {int(rn.max())} > ELL width {w}")
        r = m._row_of_nnz()
        k = np.arange(m.nnz, dtype=np.int64) - np.repeat(m.indptr[:-1], rn)
        cols[r, k] = m.indices
        vals[r, k] = m.data
    return cols, vals


def sell_slices(row_nnz: np.ndarray, slots: np.ndarray, slice_height: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """SELL-C slice layout of rows with ``row_nnz`` entries at ``slots``:
    each slice's width (the widest of its ``slice_height`` slots; ``(1,)``
    zeros for no rows) and first storage position (``(n_slices + 1,)``,
    the last entry the total size).  Slot ``q``'s ``k``-th entry is stored
    at ``starts[s] + (q - s * C) * width[s] + k``, ``s = q // C``."""
    nr = len(row_nnz)
    C = int(slice_height)
    n_slices = -(-nr // C)
    w = np.zeros(max(n_slices, 1), dtype=np.int64)
    if nr:
        np.maximum.at(w, np.asarray(slots, dtype=np.int64) // C, row_nnz)
    starts = np.zeros(n_slices + 1, dtype=np.int64)
    starts[1:] = np.cumsum(C * w[:n_slices])
    return w, starts


def sell_arrays_from_csr(m: CSRMatrix, slots: np.ndarray, slice_height: int
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side sliced-ELL (SELL-C) packing with a caller-provided row
    permutation.

    ``slots[r]`` is the storage/vector slot of row ``r`` — a permutation of
    ``0..n_rows-1`` (σ-window sorting by row nnz is the caller's job, see
    ``repro.sparse.formats.SELLFormat``).  Slot ``q`` belongs to slice
    ``q // slice_height``; each slice is padded to ``slice_height`` rows at
    its *own* maximum row width, so total storage tracks the true nnz instead
    of ``n_rows x max_width`` (the ELL bound).

    Returns flat slice-major ``(vals float64, cols int32, rows int32)`` where
    ``rows`` holds the slot each entry accumulates into; padding entries have
    ``vals == 0`` (and ``cols == rows == 0``), so they contribute nothing.
    """
    C = int(slice_height)
    rn = m.row_nnz
    slots = np.asarray(slots, dtype=np.int64)
    w, starts = sell_slices(rn, slots, C)
    size = int(starts[-1])
    vals = np.zeros(size, dtype=np.float64)
    cols = np.zeros(size, dtype=np.int32)
    rows = np.zeros(size, dtype=np.int32)
    if m.nnz:
        r_of = m._row_of_nnz()
        k = np.arange(m.nnz, dtype=np.int64) - np.repeat(m.indptr[:-1], rn)
        q = slots[r_of]
        s = q // C
        pos = starts[s] + (q - s * C) * w[s] + k
        vals[pos] = m.data
        cols[pos] = m.indices
        rows[pos] = q
    return vals, cols, rows


@partial(jax.tree_util.register_dataclass,
         data_fields=["cols", "vals"],
         meta_fields=["n_rows", "n_cols"])
@dataclasses.dataclass
class ELLMatrix:
    """Padded-row (ELLPACK) storage: ``y[r] = sum_k vals[r,k] * x[cols[r,k]]``.

    Padding entries have ``vals == 0`` and ``cols == 0`` so they contribute
    nothing.  Equal-*rows* work splitting over this format is the
    "vector-based threading" analogue from the paper.
    """

    cols: jax.Array  # (n_rows_pad, width) int32
    vals: jax.Array  # (n_rows_pad, width) float
    n_rows: int
    n_cols: int

    @property
    def width(self) -> int:
        return self.cols.shape[1]

    @property
    def n_rows_pad(self) -> int:
        return self.cols.shape[0]

    @classmethod
    def from_csr(cls, m: CSRMatrix, width: int | None = None,
                 n_rows_pad: int | None = None,
                 dtype=jnp.float32) -> "ELLMatrix":
        cols, vals = ell_arrays_from_csr(m, width=width, n_rows_pad=n_rows_pad)
        return cls(cols=jnp.asarray(cols),
                   vals=jnp.asarray(vals.astype(np.dtype(dtype))),
                   n_rows=m.n_rows, n_cols=m.n_cols)

    def matvec(self, x: jax.Array) -> jax.Array:
        """Vectorised jnp SpMV (padding-safe)."""
        y = jnp.einsum("rk,rk->r", self.vals, x[self.cols].astype(self.vals.dtype))
        return y[: self.n_rows] if self.n_rows != self.n_rows_pad else y


@partial(jax.tree_util.register_dataclass,
         data_fields=["vals", "cols", "lrows", "bin_starts", "out_gather"],
         meta_fields=["n_rows", "n_cols", "rows_pad", "bin_nnz"])
@dataclasses.dataclass
class BalancedCOO:
    """nnz-balanced binned COO — input format of the Pallas SpMV kernel.

    Rows are grouped into ``nbins`` contiguous bins with ~equal nonzeros
    (the paper's greedy + diffusion thread partition).  Each bin is padded to
    ``nnz_pad`` entries and ``rows_pad`` rows so the kernel grid is static.
    ``lrows`` holds *bin-local* row ids; ``out_gather`` maps the kernel's
    (nbins, rows_pad) output back to the flat row vector.
    """

    vals: jax.Array        # (nbins, nnz_pad) float
    cols: jax.Array        # (nbins, nnz_pad) int32 — column into x
    lrows: jax.Array       # (nbins, nnz_pad) int32 — bin-local row id
    bin_starts: jax.Array  # (nbins,) int32 — first global row of each bin
    out_gather: jax.Array  # (n_rows,) int32 — flat index into (nbins*rows_pad)
    n_rows: int
    n_cols: int
    rows_pad: int
    bin_nnz: tuple       # true stored-entry count per bin (from indptr)

    @property
    def nbins(self) -> int:
        return self.vals.shape[0]

    @property
    def nnz_pad(self) -> int:
        return self.vals.shape[1]

    @classmethod
    def from_csr(cls, m: CSRMatrix, bounds: np.ndarray,
                 dtype=jnp.float32,
                 nnz_align: int = 128, rows_align: int = 8) -> "BalancedCOO":
        """``bounds``: (nbins+1,) row partition from ``repro.core.partition``."""
        bounds = np.asarray(bounds, dtype=np.int64)
        nbins = len(bounds) - 1
        rn = m.row_nnz
        bin_nnz = np.array([rn[bounds[t]:bounds[t + 1]].sum() for t in range(nbins)],
                           dtype=np.int64)
        bin_rows = np.diff(bounds)

        def _align(v, a):
            return int(max(a, -(-int(v) // a) * a))

        nnz_pad = _align(bin_nnz.max() if nbins else 1, nnz_align)
        rows_pad = _align(bin_rows.max() if nbins else 1, rows_align)

        vals = np.zeros((nbins, nnz_pad), dtype=np.float64)
        cols = np.zeros((nbins, nnz_pad), dtype=np.int32)
        lrows = np.zeros((nbins, nnz_pad), dtype=np.int32)
        out_gather = np.zeros(m.n_rows, dtype=np.int32)
        for t in range(nbins):
            lo_r, hi_r = bounds[t], bounds[t + 1]
            s, e = m.indptr[lo_r], m.indptr[hi_r]
            k = e - s
            vals[t, :k] = m.data[s:e]
            cols[t, :k] = m.indices[s:e]
            # bin-local row ids, repeated per nnz
            rep = np.repeat(np.arange(hi_r - lo_r), rn[lo_r:hi_r])
            lrows[t, :k] = rep
            out_gather[lo_r:hi_r] = t * rows_pad + np.arange(hi_r - lo_r)
        return cls(vals=jnp.asarray(vals, dtype=dtype),
                   cols=jnp.asarray(cols),
                   lrows=jnp.asarray(lrows),
                   bin_starts=jnp.asarray(bounds[:-1], dtype=jnp.int32),
                   out_gather=jnp.asarray(out_gather),
                   n_rows=m.n_rows, n_cols=m.n_cols, rows_pad=rows_pad,
                   bin_nnz=tuple(int(k) for k in bin_nnz))

    @property
    def padding_waste(self) -> float:
        """Fraction of stored entries that are padding — the balanced
        partition minimises this (the TPU meaning of load balance).

        Computed from the true per-bin stored-entry counts (``bin_nnz``,
        taken from the CSR ``indptr`` at construction), *not* from
        ``vals != 0`` — an explicitly stored zero value is a real entry the
        kernel streams, not padding."""
        if len(self.bin_nnz) != self.nbins:
            raise ValueError(f"bin_nnz has {len(self.bin_nnz)} entries for "
                             f"{self.nbins} bins")
        total = self.nbins * self.nnz_pad
        real = int(sum(self.bin_nnz))
        return 1.0 - real / max(total, 1)
