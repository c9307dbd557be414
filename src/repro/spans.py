"""Host spans and counters inside the program, on the profiler's clock.

``span(name, **meta)`` marks a stretch of host work where it runs: it opens
a ``jax.profiler.TraceAnnotation``, so a profiler trace shows the span on
the host plane, nested under its caller and on the same clock as the
device's "XLA Ops", and it adds the span's wall seconds to an in-process
table.  ``count(name, n)`` adds to the same table without timing.  With no
profiler running a ``TraceAnnotation`` does next to nothing, so spans are
always on; none runs per Krylov iteration (those run on the device, where
``jax.named_scope`` names them instead).

``totals()`` reads the table, ``{name: (count, seconds)}``: a span's count
is how often it closed, a counter's is the sum of what it was given (its
seconds stay 0).  The table grows with the number of names, not calls;
``reset()`` empties it.

Names in use:

==================  ====================================================
``plan.build``      ``core.spmv.build_spmv_plan``, the whole call
``plan.split``      inside it: the pass that finds each node's ghost
                    columns, where a node does not own every column
``plan.pack``       inside it: the shard format's ``pack`` and the chunk
                    walk that fills it; counters ``plan.pack.chunks``
                    (chunks walked) and ``plan.pack.threads`` (the
                    walk's pool, 1 inline)
``solver.place``    ``util.place_operands``, up to the transfer's end;
                    counter ``place.bytes`` (summed over devices)
``refine.solve``    one ``make_refine`` solve (``call``: its number);
                    counters ``refine.cycles``, ``refine.inner_iters``
``refine.cycle``    one refinement cycle (``cycle``), holding
                    ``refine.to_device`` (normalise, ``to_dist``),
                    ``refine.inner`` (the device solve, waited for),
                    ``refine.from_device`` (``from_dist``) and
                    ``refine.residual`` (x update, f64 matvec, norm)
==================  ====================================================
"""
from __future__ import annotations

import time

import jax

__all__ = ["span", "count", "totals", "reset"]

_TABLE: dict[str, list] = {}


class span:
    """Context manager: a named host span, traced and added to the table."""

    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        row = _TABLE.setdefault(self.name, [0, 0.0])
        row[0] += 1
        row[1] += dt
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    _TABLE.setdefault(name, [0, 0.0])[0] += n


def totals() -> dict[str, tuple]:
    """``{name: (count, seconds)}`` of every span and counter so far."""
    return {k: (c, s) for k, (c, s) in _TABLE.items()}


def reset() -> None:
    """Empty the table."""
    _TABLE.clear()
