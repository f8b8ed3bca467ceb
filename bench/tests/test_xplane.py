"""The trace reduction on a hand-built trace, and on a recorded one."""
from pathlib import Path

import pytest

import xplane

MS = 1_000_000


def planes():
    host = ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * MS),
        ("bench.tick", 0, 50 * MS),
        ("bench.tick", 50 * MS, 50 * MS),
        ("bench.sync", 95 * MS, 5 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_route_pass(3)", 10 * MS, 20 * MS),
                         ("jit_student_step(7)", 60 * MS, 30 * MS),
                         ("jit_route_pass(3)", 200 * MS, 5 * MS)]),
        ("XLA Ops", [("fusion.1", 10 * MS, 15 * MS),
                     ("fusion.2", 20 * MS, 10 * MS),     # overlaps fusion.1
                     ("convolution.3", 60 * MS, 30 * MS),
                     ("fusion.1", 200 * MS, 5 * MS)])])  # outside the window
    return [host, dev]


def test_busy_is_the_union_inside_the_window():
    red = xplane.reduce_planes(planes())
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.05)      # 10..30 and 60..90 ms
    assert red["ops"]["fusion.1"] == pytest.approx(0.015)


def test_modules_by_base_name():
    red = xplane.reduce_planes(planes())
    assert red["modules"] == pytest.approx({"route_pass": 0.02,
                                            "student_step": 0.03})
    assert red["module_calls"] == {"route_pass": 1, "student_step": 1}


def test_idle_gaps_named_by_innermost_host_span():
    red = xplane.reduce_planes(planes())
    gaps = dict((round(s, 6), n) for n, s in red["idle_gaps"])
    # 0..10 ms and 30..60 ms: the first tick; 90..100 ms: the sync
    assert gaps[0.03] == "bench.tick"
    assert gaps[0.01] in ("bench.tick", "bench.sync")
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(0.05)
    bd = xplane.breakdown(red)
    assert bd["device_ops"][0][0] == "convolution.3"
    assert len(bd["idle_gaps"]) <= 10


def test_no_device_reads_as_nothing_busy():
    red = xplane.reduce_planes([planes()[0]])
    assert red["busy_s"] == 0.0 and red["devices"] == 0


RECORDED = Path(__file__).with_name("data") / "small.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    red = xplane.reduce_trace(RECORDED)
    assert red["devices"] >= 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["modules"]
