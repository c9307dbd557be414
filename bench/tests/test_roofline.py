"""The yardstick's byte count and the SpMV's roofline share, by hand."""
import pytest

from operators import build_operator
from roofline import peaks, spmv_bytes, spmv_roofline_pct


def test_spmv_bytes_by_hand():
    # 3x3 tridiagonal: 7 logical nonzeros, 3 rows, 3 columns
    assert spmv_bytes(3, 3, 7) == 4 * 7 + 4 * 3 + 4 * 3 == 52


def test_spmv_bytes_logical_count():
    indptr, indices, _ = build_operator(
        {"generator": "extruded_prism", "n_surface": 50, "layers": 2,
         "seed": 0, "shift": 1e-3})
    n, nnz = len(indptr) - 1, len(indices)
    # per row: its stored entries (no padding), then x once and y once
    assert spmv_bytes(n, n, nnz) == 4 * (nnz + 2 * n)


def _ctx(**trace):
    return {"trace": {"instruction_s": {"fusion.1": 0.6, "fusion.2": 0.4,
                                        "fusion.3": 5.0},
                      "loop_gathers": ["fusion.1", "fusion.2"], **trace},
            "counters": {"iters": [4, 4, 2]}, "chips": 4,
            "peaks": peaks("TPU v5 lite"),
            "operator": {"n_rows": 1000, "n_cols": 1000, "nnz": 9000}}


def test_roofline_share_from_the_loop_gathers():
    # 1.0 s in the loop's gathers over 10 iterations: 0.1 s per SpMV
    least = 44_000 / (4 * 819e9)
    assert spmv_roofline_pct(_ctx()) == pytest.approx(100 * least / 0.1)


@pytest.mark.parametrize("change", [{"loop_gathers": []},
                                    {"loop_gathers": ["fusion.9"]}])
def test_nothing_to_read_gives_no_number(change):
    assert spmv_roofline_pct(_ctx(**change)) is None
    ctx = _ctx()
    ctx["trace"] = None
    assert spmv_roofline_pct(ctx) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
