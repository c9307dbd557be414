"""The yardstick's arithmetic: device peaks and the bytes an SpMV must move."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).
    A kind that is not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def spmv_bytes(n_rows: int, n_cols: int, nnz: int) -> int:
    """Bytes one ``y = A x`` must move, whatever format implements it: the
    f32 values of the logical nonzeros, ``x`` read once and ``y`` written
    once.  Column indices are not counted, since a structured format may
    derive them, so no format can push the share past 100%."""
    return 4 * nnz + 4 * n_cols + 4 * n_rows


def spmv_roofline_pct(ctx: dict) -> float | None:
    """Share of the HBM roofline achieved by the SpMV of the timed solve:
    the least time the mesh's chips could take to move :func:`spmv_bytes`
    at their peak bandwidth, over the device time per SpMV.  That time is
    the traced window's seconds in the solve loop's gathering instructions
    (``hlo.loop_gathers``), over the iterations of the window's calls, one
    SpMV each.  Where the loop holds no gather, there is nothing to read.
    The operation count (2 flops per nonzero) bounds it far less than the
    bytes do."""
    tr = ctx["trace"]
    iters = sum(ctx["counters"]["iters"])
    if not tr or not iters:
        return None
    t = sum(tr["instruction_s"].get(n, 0.0) for n in tr["loop_gathers"])
    if not t:
        return None
    op = ctx["operator"]
    least = spmv_bytes(op["n_rows"], op["n_cols"], op["nnz"]) / (
        ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (t / iters)
