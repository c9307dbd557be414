"""CPU rehearsals of every cell: the whole flow on a tiny operator, no
device metric printed; and no result at all without a TPU."""
import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT, last_json, run_script

CELLS = [(w["name"], w["chips"]) for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_rehearsal(cell, chips, trace):
    rc, out, err = run_script(
        ["bench/run.py", "--workload", cell, "--seed", str(2**31 + 7),
         "--seconds", "0.3", "--trace", str(trace), "--rehearse"],
        devices=chips)
    assert rc == 0, err
    res = last_json(out)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips
    # the compared numbers close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


def test_no_tpu_no_result():
    rc, out, err = run_script(["bench/run.py", "--workload",
                               "poisson-200k.solve", "--seed", "1",
                               "--seconds", "1", "--trace", "0"])
    assert rc != 0 and out.strip() == "" and "no TPU" in err


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench")
    rc, out, _ = run_script(["bench/run.py", "--workload",
                             "poisson-200k.solve", "--seed", "1",
                             "--seconds", "1", "--trace", "0"],
                            cwd=tmp_path)
    assert rc != 0 and out.strip() == ""
