"""The four-chip cell on a 2x2 mesh, rehearsed on 4 CPU devices: with the
exchange between chips left out, or the timed path broken as
``test_faults.py`` breaks it, the check reads false; the control (the
plain reference one precision lower) reads false where the program reads
true, on three seeds.  And the cell's two readers of the exchange."""
import pytest

from conftest import last_json, run_script
from repro import spans
from spec import metric_reader

CELL = "poisson-800k.solve-2x2"
SEEDS = (3, 17, 2**31 + 11)


@pytest.mark.parametrize("runner,fault", [
    ("exchange_faults.py", "exchange_dropped"),
    ("faults.py", "state_unchanged"),
    ("faults.py", "answer_altered")])
def test_mesh_fault_reads_incorrect(runner, fault):
    rc, out, err = run_script(
        [f"bench/tests/{runner}", fault, "--workload", CELL, "--seed", "41",
         "--seconds", "0.3", "--trace", "0", "--rehearse"], devices=4)
    assert rc == 0, err
    res = last_json(out)
    assert res["device"]["count"] == 4
    assert res["correct"] is False and res["failed"] >= 1
    assert res["attempted"] >= 1


def test_mesh_control_fails_program_passes():
    seeds = ",".join(map(str, SEEDS))
    rc, out, err = run_script(
        ["bench/control.py", "--workload", CELL, "--seeds", seeds,
         "--seconds", "0.3", "--rehearse"], devices=4)
    assert rc == 0, err
    controls = [last_json(line) for line in out.strip().splitlines()]
    assert [c["seed"] for c in controls] == list(SEEDS)
    for ctl in controls:
        assert ctl["correct"] is False, ctl["checks"]
        rc, out, err = run_script(
            ["bench/run.py", "--workload", CELL, "--seed", str(ctl["seed"]),
             "--seconds", "0.3", "--rehearse"], devices=4)
        assert rc == 0, err
        prog = last_json(out)
        assert prog["correct"] is True, prog["checks"]
        # the number that separates them reads at least 10x apart
        assert ctl["checks"]["true_rel"]["value"] > 10 * prog["checks"][
            "true_rel"]["value"]


@pytest.fixture
def table():
    spans.reset()
    yield
    spans.reset()


def test_halo_wire_bytes_reads_both_counters_per_plan(table):
    read = metric_reader("halo_wire_bytes.solve-2x2")
    assert read({}) is None                 # a program that counts nothing
    for _ in range(2):
        with spans.span("plan.build"):
            spans.count("halo.wire_bytes", 1000)
            spans.count("spmv.gather_bytes", 3000)
    assert read({}) == 4000.0


def test_collective_pct_reads_the_trace():
    read = metric_reader("collective_pct.solve-2x2")
    assert read({"trace": None}) is None
    assert read({"trace": {"window_s": 2.0, "collective_s": 0.05}}) == \
        pytest.approx(2.5)
