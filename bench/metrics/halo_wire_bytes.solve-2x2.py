"""Bytes one SpMV moves between chips, as the program counts them at its
plan build (``repro.core.build_spmv_plan``): counter ``halo.wire_bytes``
(the node exchange, by the transport's predicted cost for its wire codec)
plus counter ``spmv.gather_bytes`` (the core-axis all_gather of x bins),
both summed over devices, over the calls of span ``plan.build``."""
from program_spans import totals


def read(ctx):
    t = totals()
    builds = t.get("plan.build", (0,))[0]
    if "halo.wire_bytes" not in t or "spmv.gather_bytes" not in t \
            or not builds:
        return None
    return (t["halo.wire_bytes"][0] + t["spmv.gather_bytes"][0]) / builds
