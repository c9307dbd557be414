"""A new configuration (with its own operator generator), traffic mix, cell
or per-layer metric is a new file plus new ``BENCHMARK.json`` entries: the
harness finds it by name, and no file that is there is edited."""
import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT, last_json, run_script


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark, with the program linked in beside it."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-ring", "source": "https://arxiv.org/abs/1303.5275",
        "file": "bench/configs/tiny-ring.json", "reduced": [],
        "why": "a test deployment"})
    (tmp_path / "bench/generators/ring.py").write_text(
        "import numpy as np\n\n\n"
        "def build(n):\n"
        "    i = np.arange(n)\n"
        "    cols = np.stack([(i - 1) % n, i, (i + 1) % n], 1)\n"
        "    cols.sort(axis=1)\n"
        "    vals = np.where(cols == i[:, None], 2.5, -1.0)\n"
        "    return (np.arange(0, 3 * n + 1, 3), cols.ravel().astype(np.int64),"
        " vals.ravel())\n")
    (tmp_path / "bench/configs/tiny-ring.json").write_text(json.dumps({
        "operator": {"generator": "ring", "n": 600},
        "rehearsal": {"n": 300},
        "solver": "cg", "precond": "jacobi", "tol": 1e-4, "maxiter": 500,
        "residual": "recursive", "precision": "float32"}))
    (tmp_path / "bench/traffic/few_solves.json").write_text(json.dumps({
        "arrivals": "back_to_back", "pool": 3, "pool_seed": 9,
        "tol": "config", "maxiter": "config"}))
    bench["workloads"].append({
        "name": "tiny-ring.few", "config": "tiny-ring",
        "traffic": "few_solves", "chips": 1, "why": "a test cell"})
    (tmp_path / "bench/cells/tiny-ring.few.json").write_text(
        json.dumps({"limits": {"true_rel": 1e-3}}))
    bench["workloads"].append({
        "name": "tiny-ring.few-2x2", "config": "tiny-ring",
        "traffic": "few_solves", "chips": 4, "why": "a test cell on a mesh"})
    (tmp_path / "bench/cells/tiny-ring.few-2x2.json").write_text(
        json.dumps({"mesh": [2, 2], "limits": {"true_rel": 1e-3}}))
    bench["end_to_end"][1]["workloads"] += ["tiny-ring.few",
                                            "tiny-ring.few-2x2"]
    bench["per_layer"].append({
        "name": "calls_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Krylov loop",
        "moves": "solve_s"})
    (tmp_path / "bench/metrics/calls_seen.py").write_text(
        "def read(ctx):\n    return len(ctx['counters']['iters'])\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"


def test_new_pieces_found_by_name(checkout):
    from spec import metric_reader, resolve_cell
    cell = resolve_cell("tiny-ring.few", root=str(checkout))
    assert cell.mesh == (1, 1) and cell.limits == {"true_rel": 1e-3}
    assert cell.config["operator"] == {"generator": "ring", "n": 600}
    assert cell.mix["pool"] == 3
    assert {m["name"] for m in cell.end_to_end} == {"solve_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # no workloads key: reported in every cell that reports what it moves
    assert "calls_seen" in names and "plan_build_s" in names
    assert "calls_seen" in {m["name"] for m in resolve_cell(
        "poisson-200k.solve", root=str(checkout)).per_layer}
    assert "calls_seen" not in {m["name"] for m in resolve_cell(
        "fig3-13.5M.iters", root=str(checkout)).per_layer}
    read = metric_reader("calls_seen", root=str(checkout))
    assert read({"counters": {"iters": [3, 4]}}) == 2


@pytest.mark.parametrize("cell,chips", [("tiny-ring.few", 1),
                                        ("tiny-ring.few-2x2", 4)])
def test_new_cell_runs(checkout, cell, chips):
    from spec import resolve_cell
    assert resolve_cell(cell, root=str(checkout)).mesh == (
        (2, 2) if chips == 4 else (1, 1))
    rc, out, err = run_script(
        [str(checkout / "bench/run.py"), "--workload", cell, "--seed", "5",
         "--seconds", "0.5", "--rehearse"], devices=chips, cwd=checkout)
    assert rc == 0, err
    res = last_json(out)
    assert res["correct"] and res["attempted"] >= 1
    assert res["device"]["count"] == chips


def test_unknown_cell_is_an_error(checkout):
    from spec import resolve_cell
    with pytest.raises(KeyError):
        resolve_cell("no-such.cell", root=str(checkout))
