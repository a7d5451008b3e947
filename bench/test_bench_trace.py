"""The trace reduction, on a small trace recorded on a v5e chip (one jitted
step run four times between ``bench.step`` and ``bench.wait`` spans) and
on hand-made events."""

import pytest

from bench import trace as TR
from bench.tiny import ROOT

PROBE = ROOT / "bench" / "testdata" / "v5e_probe.xplane.pb"


def test_recorded_trace_planes():
    ev = TR.events(str(PROBE))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in ev["devices"]["/device:TPU:0"]}
    assert {"convolution_tanh_fusion", "fusion"} <= names
    spans = {n for n, _, _ in ev["host"]}
    assert {"bench.traced", "bench.step", "bench.wait"} <= spans


def test_recorded_trace_reduction():
    r = TR.reduce(TR.events(str(PROBE)))
    assert r["window_s"] == pytest.approx(0.013230695)
    # three of the four steps' two 90 us fusions fall inside the window
    assert r["busy_s"] == pytest.approx(0.000544577)
    assert r["idle_frac"] == pytest.approx(1 - 0.000544577 / 0.013230695)
    assert r["exposed_comm_frac"] is None
    top = [n for n, _ in r["device_ops"]]
    assert top[:2] == ["convolution_tanh_fusion", "fusion"]
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"bench.step", "bench.wait", "between_ops",
                         "bench.other"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


US = 1000          # events in microseconds


def hand_made():
    # one device, window [0, 100) us: compute 0-30 and 50-60, an
    # all-reduce 25-45 (5 of it under compute), an async permute 52-58
    # (hidden); the host steps 0-40, waits 40-100
    def us(evs):
        return [(n, s * US, e * US) for n, s, e in evs]

    return {"devices": {"/device:TPU:0": us([("fusion.1", 0, 30),
                                             ("all-reduce.2", 25, 45),
                                             ("fusion.3", 50, 60)])},
            "async": {"/device:TPU:0": us([("collective-permute-start", 52,
                                            58)])},
            "host": us([("bench.traced", 0, 100), ("bench.step", 0, 40),
                        ("bench.wait", 40, 100)])}


def test_busy_idle_and_exposed_collectives():
    r = TR.reduce(hand_made())
    assert r["busy_s"] == pytest.approx(55e-6)        # 0-45 and 50-60
    assert r["idle_frac"] == pytest.approx(0.45)
    assert r["comm_s"] == pytest.approx(26e-6)        # 20 + 6
    assert r["exposed_comm_frac"] == pytest.approx(15 / 26)
    # idle 60-100 falls in the host's wait; 45-50 is too short to label
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(40e-6)],
                              ["between_ops", pytest.approx(5e-6)]]


def test_nested_ops_count_self_time():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 40), ("fusion.3", 50, 60)]
    t = TR.leaf_times(ops)
    assert t == {"while.1": 60, "fusion.2": 30, "fusion.3": 10}


def test_interval_helpers():
    u = TR.union([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert u == [[0, 4], [5, 10]]
    assert TR.measure(u) == 9
    assert TR.intersect(u, [[3, 6]]) == [[3, 4], [5, 6]]
    assert TR.op_name("%fusion.12 = bf16[2]{0} fusion(%all-reduce.1)") \
        == "fusion.12"
    assert not TR.is_collective(TR.op_name(
        "%fusion.12 = bf16[2]{0} fusion(%all-reduce.1)"))


def test_no_window_reads_nothing():
    ev = hand_made()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench.traced"]
    assert TR.reduce(ev) == {}
