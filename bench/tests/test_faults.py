"""With the timed path broken underneath, a run's check reads false.

Each run skips the look for a chip (``--rehearse``, a tiny operator on the
CPU) and drives the rest of the run.  Faults: a step that returns its
state unchanged, and an answer altered where it is produced.  The cells
run on one chip, so there is no exchange between chips to leave out, and
solve one right-hand side per call, so there is no batch to leave half of.
"""
import pytest

from conftest import last_json, run_script


@pytest.mark.parametrize("cell", ["fig3-13.5M.iters", "poisson-200k.solve"])
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_reads_incorrect(fault, cell):
    rc, out, err = run_script(
        ["bench/tests/faults.py", fault, "--workload", cell, "--seed", "41",
         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    assert rc == 0, err
    res = last_json(out)
    assert res["correct"] is False and res["failed"] >= 1
    assert res["attempted"] >= 1
