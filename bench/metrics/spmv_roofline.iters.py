"""Share of the HBM roofline achieved by the shard SpMV inside the timed
solve, from the device trace of the window (``roofline.spmv_roofline_pct``;
``roofline.spmv_bytes`` counts the bytes)."""
from roofline import spmv_roofline_pct


def read(ctx):
    return spmv_roofline_pct(ctx)
