"""Reduction of a device trace: busy union, idle share, per-program
device time and idle gaps named by the host thread's events."""
import json
import pathlib

import pytest

from bench import devtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def synthetic():
    raw = json.loads((DATA / "synthetic_trace.json").read_text())
    return [devtrace.Plane(p["name"], [
        devtrace.Line(l["name"], [tuple(e) for e in l["events"]])
        for l in p["lines"]]) for p in raw["planes"]]


def test_merge_unions_nested_and_overlapping():
    assert devtrace.merge([(5, 9), (0, 3), (2, 4), (6, 7), (9, 10)]) == \
        [(0, 4), (5, 10)]


def test_reduce_synthetic_window():
    red = devtrace.reduce(synthetic())
    # window = the marker event, 1000 .. 101000 ns
    assert red.window_ns == (1000, 101000)
    assert red.window_s == pytest.approx(100e-6)
    # busy inside it: [1000,1500) + [10000,30000) + [40000,50000)
    # + [70000,101000)
    assert red.busy_s == pytest.approx((500 + 20000 + 10000 + 31000) / 1e9)
    assert red.programs["jit_decode_tokens"] == pytest.approx(20500e-9)
    assert red.programs["jit_decode_persistent"] == pytest.approx(31000e-9)
    assert red.programs["jit_prefill"] == pytest.approx(10000e-9)
    assert devtrace.decode_seconds(red.programs) == pytest.approx(51500e-9)
    assert red.idle == [(1500, 10000), (30000, 40000), (50000, 70000)]


def test_gaps_take_the_name_of_the_pump_threads_innermost_event():
    planes = synthetic()
    red = devtrace.reduce(planes)
    line = devtrace.dispatch_line(planes)
    assert sum(n.startswith("PjitFunction(") for n, _, _ in line.events) == 4
    gaps = devtrace.name_gaps(red.idle, line)
    # idle (1500, 10000) middle 5750: inside _tick > sleep; (50000, 70000)
    # middle 60000: the same; (30000, 40000) middle 35000: _schedule
    # inside step
    assert gaps[0] == ("$engine.py:910 _tick > $time sleep",
                       pytest.approx((8500 + 20000) / 1e9))
    assert gaps[1] == ("$engine.py:900 step > $engine.py:920 _schedule",
                       pytest.approx(10000 / 1e9))


def test_gap_with_no_host_event_is_named_so():
    line = devtrace.Line("python3", [("PjitFunction(f)", 0, 10)])
    assert devtrace.name_gaps([(20, 40)], line) == [
        ("(no host event)", pytest.approx(20e-9))]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce([devtrace.Plane("/host:CPU", [])])


def test_missing_ops_line_is_an_error():
    planes = synthetic()
    dev = planes[1]
    planes[1] = devtrace.Plane(dev.name, [l for l in dev.lines
                                          if l.name != devtrace.OPS])
    with pytest.raises(ValueError, match="XLA Ops"):
        devtrace.reduce(planes)


def test_missing_mark_is_an_error():
    planes = [p for p in synthetic() if p.name != devtrace.HOST]
    with pytest.raises(ValueError, match=devtrace.MARK):
        devtrace.reduce(planes)


def test_no_dispatching_thread_is_an_error():
    with pytest.raises(ValueError):
        devtrace.dispatch_line([devtrace.Plane("/host:CPU", [
            devtrace.Line("python3", [("bench.traced", 0, 10)])])])
