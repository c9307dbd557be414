"""The readers of the program's own spans and counters (``repro.spans``):
each gives its number from a filled table, and nothing from an empty one
(a program that records no spans, or the control)."""
import time

import pytest

from repro import spans
from spec import metric_reader

CTX = {"operator": {"n_rows": 100, "n_cols": 100, "nnz": 400}}


@pytest.fixture
def filled():
    """Set-up (a plan build with its pack, one placement of 4,000 bytes),
    then two refinement solves of 2 and 3 cycles."""
    spans.reset()
    with spans.span("plan.build"):
        with spans.span("plan.pack"):
            time.sleep(0.001)
    with spans.span("solver.place"):
        spans.count("place.bytes", 4000)
    for cycles in (2, 3):
        with spans.span("refine.solve"):
            for _ in range(cycles):
                with spans.span("refine.inner"):
                    time.sleep(0.001)
            time.sleep(0.002)
        spans.count("refine.cycles", cycles)
    yield spans.totals()
    spans.reset()


@pytest.fixture
def empty():
    spans.reset()
    yield
    spans.reset()


EXPECTED = {
    "refine_host_ms.solve": lambda t: 1e3 * (
        t["refine.solve"][1] - t["refine.inner"][1]) / 2,
    "refine_cycles.solve": lambda t: 2.5,
    "place_s": lambda t: t["solver.place"][1],
    "plan_pack_s": lambda t: t["plan.pack"][1],
    "operator_bytes_per_nnz.iters": lambda t: 10.0,
    "operator_bytes_per_nnz.solve": lambda t: 10.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_a_filled_table(filled, name):
    value = metric_reader(name)(CTX)
    assert value == pytest.approx(EXPECTED[name](filled))
    assert value > 0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_empty_table(empty, name):
    assert metric_reader(name)(CTX) is None
