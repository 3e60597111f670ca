"""Reduction of the program's spans (bench/spans.py) and the five readers
built on it, on a synthetic trace with span args.

The trace, in ns: the window mark covers [100, 1100). The device runs
two prefills, [300, 400) and [700, 800), so it is idle over [100, 300),
[400, 700) and [800, 1100). The pump thread serves rid 7 (submitted by
conn 1 with no rid: `pump.apply` joins them) and rid 8; rid 9 is
submitted near the end and never admitted. Spans of rid 6 lie before
the window.
"""
import types

import pytest

from bench import devtrace, run, spans

PUMP = [
    ("sched.schedule", 10, 40, {"live": 1, "chosen": 1}),
    ("engine.pace", 20, 50, {"op": "prefill", "modeled_us": 0.05}),
    ("prefill.group", 60, 30, {"rows": 1, "bucket": 16, "real": 9,
                               "rids": 6}),
    ("pump.wait", 100, 150, {}),
    ("pump.apply", 250, 10, {"kind": "submit", "conn": 1, "rid": 7}),
    ("engine.step", 260, 300, {"live": 0, "admitted": 1, "active": 0}),
    ("sched.schedule", 265, 10, {"live": 1, "chosen": 1}),
    ("engine.admit", 275, 25, {"rid": 7, "tokens": 37}),
    ("engine.pace", 280, 20, {"op": "prefill", "modeled_us": 0.02}),
    ("prefill.group", 300, 110, {"rows": 1, "bucket": 64, "real": 37,
                                 "rids": 7}),
    ("pump.flush", 560, 20, {"conns": 1}),
    ("engine.step", 600, 250, {"live": 1, "admitted": 1, "active": 0}),
    ("sched.schedule", 600, 30, {"live": 1, "chosen": 1}),
    ("engine.admit", 630, 70, {"rid": 8, "tokens": 20}),
    ("engine.pace", 640, 60, {"op": "prefill", "modeled_us": 0.06}),
    ("prefill.group", 700, 100, {"rows": 1, "bucket": 32, "real": 20,
                                 "rids": "8"}),
    ("pump.wait", 850, 300, {}),
]
LOOP = [
    ("server.submit", 50, 5, {"conn": 0, "rid": 6}),
    ("server.submit", 200, 5, {"conn": 1, "rid": -1}),
    ("server.write", 255, 3, {"conn": 1, "rid": 7, "first_index": -1,
                              "frames": 1}),
    ("server.submit", 500, 5, {"conn": 2, "rid": 8}),
    ("server.write", 590, 10, {"conn": 1, "rid": 7, "first_index": 0,
                               "frames": 2}),
    ("server.write", 870, 20, {"conn": 2, "rid": 8, "first_index": 0,
                               "frames": 2}),
    ("server.submit", 1000, 5, {"conn": 3, "rid": 9}),
]
MAIN = [(devtrace.MARK, 100, 1000, {})]


def planes(pump=PUMP, loop=LOOP):
    host = devtrace.Plane(devtrace.HOST, [
        devtrace.Line("python", list(loop)),
        devtrace.Line("python", list(pump)),
        devtrace.Line("python", list(MAIN))])
    ops = [("fusion", 300, 100), ("fusion", 700, 100)]
    dev = devtrace.Plane("/device:TPU:0", [
        devtrace.Line(devtrace.OPS, ops),
        devtrace.Line(devtrace.MODULES,
                      [("jit__call(1)", s, d) for _, s, d in ops])])
    return [host, dev]


def device_only(ps):
    """The planes as devtrace reads them: no args."""
    return [devtrace.Plane(p.name, [
        devtrace.Line(l.name, [e[:3] for e in l.events]) for l in p.lines])
        for p in ps]


def fake_run(ps):
    red = devtrace.reduce(device_only(ps))
    return types.SimpleNamespace(traced={
        "reduced": red, "spans": spans.Spans(ps, red.idle)})


def test_window_idle_and_clipping():
    sp = fake_run(planes()).traced["spans"]
    assert (sp.lo, sp.hi) == (100, 1100)
    assert sp.idle == [(100, 300), (400, 700), (800, 1100)]
    # spans before the window are kept but not started in it
    assert len(sp.named("sched.schedule")) == 3
    assert [s.start for s in sp.started("sched.schedule")] == [265, 600]
    assert [s.start for s in sp.started("prefill.group")] == [300, 700]


def test_rid_join_across_two_thread_lines():
    sp = fake_run(planes()).traced["spans"]
    subs = {rid: s for s, rid in sp.submits()}
    # conn 1's submit carried no rid: the pump thread's apply names it
    assert subs[7].args["rid"] == -1 and subs[7].line != sp.pump_line()
    assert set(subs) == {6, 7, 8, 9}
    assert sp.admit_waits() == pytest.approx(
        {7: 75e-9, 8: 130e-9, 9: 100e-9})       # 9 censored at 1100
    assert {rid: g.start for rid, g in sp.first_token_groups().items()} \
        == {7: 300, 8: 700}                     # rid 6's ends before 100
    assert sp.wires() == pytest.approx({7: 190e-9, 8: 90e-9})


@pytest.mark.parametrize("name,want", [
    ("pace_idle_pct", 100.0 * (20 + 60) / 1000),
    ("admit_wait_p90_s", 124e-9),       # 75, 100, 130 ns: 100 + 0.8 x 30
    ("wire_ms_p90", 180e-6),            # 90, 190 ns: 90 + 0.9 x 100
    ("sched_ms_per_step", 20e-6),       # 10 and 30 ns
    ("prefill_pad_ratio", (64 + 32) / (37 + 20)),
])
def test_reader_against_hand_value(name, want):
    assert run.reader(name)(fake_run(planes())) == pytest.approx(want)


@pytest.mark.parametrize("name", ["pace_idle_pct", "admit_wait_p90_s",
                                  "wire_ms_p90", "sched_ms_per_step",
                                  "prefill_pad_ratio"])
def test_reader_is_none_without_program_spans(name):
    assert run.reader(name)(fake_run(planes(pump=[], loop=[]))) is None
    assert run.reader(name)(types.SimpleNamespace(traced=None)) is None


def test_idle_by_innermost_pump_span():
    split = fake_run(planes()).traced["spans"].idle_by_leaf()
    assert split == pytest.approx({
        "pump.wait": 400e-9, "engine.step": 205e-9, "engine.pace": 80e-9,
        "sched.schedule": 40e-9, "pump.flush": 20e-9,
        spans.NO_SPAN: 20e-9, "engine.admit": 15e-9, "pump.apply": 10e-9,
        "prefill.group": 10e-9})
    assert sum(split.values()) == pytest.approx(800e-9)


def test_trace_is_loaded_once_per_run(monkeypatch):
    ps = planes()
    loads = []
    monkeypatch.setattr(spans, "trace_file", lambda: "trace.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda path: loads.append(path) or ps)
    r = fake_run(ps)
    del r.traced["spans"]
    for name in ("pace_idle_pct", "admit_wait_p90_s", "wire_ms_p90"):
        assert run.reader(name)(r) is not None
    assert loads == ["trace.xplane.pb"]


def test_request_parts_medians():
    parts = spans.request_parts(fake_run(planes()).traced["spans"])
    assert parts == pytest.approx({
        "admit_wait": 100e-9, "own_pace": 40e-9,     # 20 and 60
        "prefill_group": 105e-9, "wire": 140e-9})
