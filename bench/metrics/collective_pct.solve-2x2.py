"""Share of the traced window in which a collective ran on the device
(all-to-all, all-gather, all-reduce, collective-permute, reduce-scatter,
their async halves included), averaged over the cell's chips
(``xplane.reduce_trace``'s ``collective_s``)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
