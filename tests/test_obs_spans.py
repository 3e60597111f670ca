"""Program spans (repro.obs.spans) on the profiler's clock.

A smoke-preset wall-clock `ServingServer` serves a few requests while
`jax.profiler` traces the process; `bench/spans.py` reads the trace
back. The spans must cover the path the requests cross, nest as the
code does, and join one request's parts across the two server threads
on its rid. Tracing must not change what the engine computes, and with
no profiler running a span must cost about a microsecond.
"""
import dataclasses
import pathlib
import sys
import time
import timeit

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace, spans as bench_spans  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core import LatencyModel, QoESpec, TPU_V5E, make_scheduler  # noqa: E402
from repro.core.request import Request  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.obs.metrics import parse_prometheus  # noqa: E402
from repro.obs.spans import SPAN_NAMES, span  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.server import ServerConfig, ServingServer, collect, fetch  # noqa: E402

SPEC = QoESpec(ttft=1.0, tds=4.8)
# (rid, prompt length, output tokens): one-token answers take the
# sequential prefill, longer ones the staged flush and decode
REQUESTS = [(100, 9, 1), (101, 20, 1), (102, 12, 4), (103, 30, 3)]


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("llama3-8b")
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(smoke, clock, hw=TPU_V5E):
    cfg, model, params = smoke
    lat = LatencyModel(cfg, hw)
    return ServingEngine(model, params, make_scheduler("andes", 4 * 64, lat),
                         lat, num_slots=4, max_seq=64, clock=clock)


@pytest.fixture(scope="module")
def traced(smoke, tmp_path_factory):
    """Serve REQUESTS through a wall-clock server under the profiler, in
    a `bench.traced` window as bench/run.py marks it. A 20 ms overhead
    per modeled operation makes every tick sleep, however slow the
    host."""
    cfg = smoke[0]
    slow = dataclasses.replace(TPU_V5E, overhead=0.02)
    srv = ServingServer(ServerConfig(clock="wall", warmup=True),
                        backend=_engine(smoke, "wall", slow), model_cfg=cfg)
    srv.start()
    logdir = tmp_path_factory.mktemp("trace")
    rng = np.random.default_rng(3)
    try:
        jax.profiler.start_trace(str(logdir))
        try:
            with jax.profiler.TraceAnnotation(devtrace.MARK):
                for rid, plen, out in REQUESTS:
                    collect("127.0.0.1", srv.port, {
                        "rid": rid, "max_tokens": out, "prompt_tokens":
                        rng.integers(0, cfg.vocab_size, plen).tolist()})
                time.sleep(0.05)            # a pump.wait or two
        finally:
            jax.profiler.stop_trace()
        _, prom = fetch("127.0.0.1", srv.port, "/metrics")
    finally:
        srv.shutdown(drain=False)
    planes = bench_spans.load(bench_spans.trace_file(logdir))
    return bench_spans.Spans(planes, idle=[]), parse_prometheus(prom)


def test_every_span_the_path_crosses_appears(traced):
    sp, _ = traced
    seen = {s.name for s in sp.all}
    # no request here is preempted
    assert seen == set(SPAN_NAMES) - {"engine.preempt"}, seen
    assert set(bench_spans.NAMES) == set(SPAN_NAMES)


def test_pace_and_schedule_nest_inside_a_step_on_the_pump_thread(traced):
    sp, _ = traced
    pump = sp.pump_line()
    steps = sp.named("engine.step")
    assert {s.line for s in steps} == {pump}
    for name in ("engine.pace", "sched.schedule", "engine.admit",
                 "prefill.group", "engine.decode"):
        inner = sp.named(name)
        assert inner, name
        for s in inner:
            assert s.line == pump, name
            assert any(st.start <= s.start and s.end <= st.end
                       for st in steps), (name, s)
    # the loop thread's spans are on another line
    assert {s.line for s in sp.named("server.write")} - {pump}


def test_a_request_joins_on_its_rid_across_both_threads(traced):
    sp, _ = traced
    submits = {rid: s for s, rid in sp.submits()}
    admits = {s.args["rid"] for s in sp.named("engine.admit")}
    groups = sp.first_token_groups()
    writes = sp.first_writes()
    for rid, plen, _ in REQUESTS:
        assert rid in submits and rid in admits and rid in writes, rid
        g = groups[rid]
        assert rid in bench_spans.rid_list(g)
        assert g.args["real"] >= plen
        assert g.args["rows"] * g.args["bucket"] >= g.args["real"]
        # submit -> admit -> prefill group -> first frame written
        assert submits[rid].start <= g.start <= g.end <= writes[rid].end
        assert submits[rid].line == writes[rid].line != g.line
    assert set(sp.admit_waits()) == {r for r, _, _ in REQUESTS}
    assert all(w >= 0 for w in sp.wires().values())


def test_engine_counters_reach_metrics(traced):
    _, prom = traced
    real = prom[("prefill_tokens_real_total", ())]
    assert real >= sum(p for _, p, _ in REQUESTS)
    assert prom[("prefill_tokens_padded_total", ())] >= real
    assert prom[("pace_seconds_total", ())] > 0


def _virtual_run(smoke):
    cfg = smoke[0]
    rng = np.random.default_rng(7)
    wl = [Request(rid=i, arrival=0.01 * i, prompt_len=plen, output_len=out,
                  spec=SPEC, prompt_tokens=rng.integers(0, cfg.vocab_size,
                                                        plen))
          for i, (_, plen, out) in enumerate(REQUESTS * 2)]
    eng = _engine(smoke, "virtual")
    eng.run(wl)
    return [(list(r.output_tokens), list(r.emit_times)) for r in wl]


def test_profiler_changes_no_token_or_timestamp(smoke, tmp_path):
    plain = _virtual_run(smoke)
    jax.profiler.start_trace(str(tmp_path))
    try:
        profiled = _virtual_run(smoke)
    finally:
        jax.profiler.stop_trace()
    assert profiled == plain


def test_span_cost_off_and_spans_per_step(smoke, monkeypatch):
    """With no profiler a span is a no-op context manager: record its
    cost, and how many spans one step opens."""
    n = 20000

    def two_args():
        with span("engine.admit", rid=1, tokens=2):
            pass

    base = timeit.timeit(lambda: None, number=n)
    ns = (min(timeit.repeat(two_args, number=n, repeat=3)) - base) / n * 1e9
    print(f"span with two args, no profiler: {ns:.0f} ns")
    assert ns < 20_000

    from repro.serving import engine as engine_mod

    opened = []

    def counting(name, **args):
        opened.append(name)
        return span(name, **args)

    monkeypatch.setattr(engine_mod, "span", counting)
    cfg = smoke[0]
    eng = _engine(smoke, "virtual")
    rng = np.random.default_rng(1)
    for i, (_, plen, out) in enumerate(REQUESTS):
        eng.submit(Request(rid=i, arrival=0.0, prompt_len=plen,
                           output_len=out, spec=SPEC,
                           prompt_tokens=rng.integers(0, cfg.vocab_size,
                                                      plen)))
    steps = 0
    while eng.step():
        steps += 1
    per_step = len(opened) / steps
    print(f"spans per step: {per_step:.2f} over {steps} steps")
    assert opened.count("engine.step") == steps
    # a step opens its own span, the scheduler's and at most one span
    # per admission, prefill group and decode dispatch
    assert per_step <= 2 + 3 * len(REQUESTS)
