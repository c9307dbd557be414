"""Bytes the program placed on the devices for the operator (counter
``place.bytes``: stored blocks, mask, diagonal, preconditioner data and
layout maps, summed over devices) per logical nonzero of the operator."""
from program_spans import totals


def read(ctx):
    placed = totals().get("place.bytes")
    nnz = ctx["operator"]["nnz"]
    return placed[0] / nnz if placed and nnz else None
