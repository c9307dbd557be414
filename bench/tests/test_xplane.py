"""The trace reduction: busy, collective and idle time, top operations and
the longest idle gaps named by the harness span the host was in."""
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_synthetic_events():
    ms = 1_000_000
    host = [("window", 0, 100 * ms), ("solve_call", 0, 60 * ms),
            ("host_check", 60 * ms, 70 * ms), ("solve_call", 70 * ms,
                                               100 * ms)]
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kCustom"
    dev = [("%while.4 = (s32[]) while((s32[]) %t), body=%b", 4 * ms, 56 * ms),
           (fusion, 5 * ms, 20 * ms),
           ("%all-reduce.2 = f32[2]{0} all-reduce(f32[2]{0} %fusion.1)",
            20 * ms, 30 * ms),
           (fusion, 40 * ms, 55 * ms),
           ("%all-to-all.3 = f32[8]{0} all-to-all(f32[8]{0} %x)",
            75 * ms, 95 * ms),
           ("%copy.9 = f32[8]{0} copy(f32[8]{0} %y)", -10 * ms, 2 * ms)]
    out = xplane.reduce_events(host, {0: dev, 1: dev})
    assert out["window_s"] == pytest.approx(0.1)
    # the while spans its body and is left out; the copy is clipped to
    # the window: [0,2] + [5,30] + [40,55] + [75,95] = 2 + 25 + 15 + 20 ms
    assert out["busy_s"] == pytest.approx(0.062)
    assert out["collective_s"] == pytest.approx(0.030)
    assert out["devices"] == 2
    assert out["device_ops"][0] == ["fusion.1 = fusion kCustom",
                                    pytest.approx(0.030)]
    assert not any("while" in n for n, _ in out["device_ops"])
    # each instruction's seconds by name, averaged over the devices
    assert out["instruction_s"] == {
        "fusion.1": pytest.approx(0.030), "all-reduce.2": pytest.approx(
            0.010), "all-to-all.3": pytest.approx(0.020),
        "copy.9": pytest.approx(0.002)}
    gaps = dict((round(t, 6), n) for n, t in out["idle_gaps"])
    # [55,75] is 5 ms in solve_call and 10 ms in host_check
    assert gaps == {0.003: "solve_call", 0.01: "solve_call",
                    0.02: "host_check", 0.005: "solve_call"}
    assert out["idle_gaps"][0] == ["host_check", pytest.approx(0.02)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_events([("window", 0, 10)], {})


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: 4 calls of a 10-iteration solve of
    a 1,200-row operator, each inside the harness's spans."""
    out = xplane.reduce_trace(os.path.join(DATA, "tiny_v5e.xplane.pb"))
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.010245809)
    assert out["busy_s"] == pytest.approx(0.003706244)
    assert out["collective_s"] == 0.0           # one chip: no collectives
    names = [n for n, _ in out["device_ops"]]
    assert len(names) == xplane.TOP and not any("while" in n for n in names)
    assert all(n.endswith("= fusion kCustom") for n in names)  # the gathers
    assert [n for n, _ in out["idle_gaps"]] == ["solve_call"] * xplane.TOP
    assert out["idle_gaps"][0][1] == pytest.approx(0.002014496)
