"""HTTP/SSE frontend (repro.server) — wire-protocol serving tests.

Layered like the server itself: SSE framing units (no socket), a
simulator-backed server for protocol behavior (healthz, metrics
round-trip, concurrency, backpressure eviction), an engine-backed
virtual-clock server for the token-identity differential, and a slowed
wall-clock server (overhead=0.05 makes tokens ~50 ms apart, wide enough
to race against) for disconnect-cancel, mid-stream drain, and the 503
barrier. The full over-the-socket wall-vs-virtual tolerance differential
runs as the CI smoke job (scripts/server_smoke.py).
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.core import LatencyModel, QoESpec, TPU_V5E, make_scheduler
from repro.core.request import Request
from repro.configs import get_smoke_config
from repro.obs.metrics import parse_prometheus, registry_samples_dict
from repro.serving import ServingSimulator, SimConfig
from repro.server import (SSEParser, ServerConfig, ServingServer, astream,
                          build_engine, collect, fetch, format_sse, stream)
from repro.server.app import _Conn

SPEC = QoESpec(ttft=1.0, tds=4.8)


# ---------------------------------------------------------------------------
# SSE wire format units
# ---------------------------------------------------------------------------

def test_sse_roundtrip_across_chunk_boundaries():
    frames = [format_sse("token", {"index": i, "token": 7 * i, "t": 0.1 * i})
              for i in range(20)]
    frames.append(format_sse("finish", {"qoe": 1.0}, event_id=3))
    blob = b"".join(frames)
    for size in (1, 3, 7, 64, len(blob)):
        p = SSEParser()
        evs = []
        for off in range(0, len(blob), size):
            evs.extend(p.feed(blob[off:off + size]))
        assert len(evs) == 21
        assert evs[0] == ("token", {"index": 0, "token": 0, "t": 0.0})
        assert evs[-1] == ("finish", {"qoe": 1.0})
        assert p.last_id == "3"


def test_sse_parser_spec_features():
    p = SSEParser()
    wire = (b": keep-alive comment\n"
            b"data: {\"a\": 1}\n\n"                  # no event: -> "message"
            b"event: multi\r\ndata: line1\r\ndata: line2\r\n\r\n"
            b"ignored-field: x\nevent: token\ndata: {\"i\":0}\n\n")
    evs = p.feed(wire)
    assert evs[0] == ("message", {"a": 1})
    assert evs[1] == ("multi", {"raw": "line1\nline2"})  # non-JSON payload
    assert evs[2] == ("token", {"i": 0})


# ---------------------------------------------------------------------------
# simulator-backed server: protocol behavior without jax in the loop
# ---------------------------------------------------------------------------

def _sim_backend(kv=4_000):
    cfg = get_smoke_config("llama3-8b")
    lat = LatencyModel(cfg, TPU_V5E)
    sched = make_scheduler("andes", kv, lat)
    return ServingSimulator(sched, lat, SimConfig(kv_capacity_tokens=kv))


@pytest.fixture(scope="module")
def sim_server():
    srv = ServingServer(ServerConfig(clock="virtual", warmup=False),
                        backend=_sim_backend())
    srv.start()
    yield srv
    srv.shutdown(drain=False)


def test_healthz(sim_server):
    status, body = fetch("127.0.0.1", sim_server.port, "/healthz")
    assert status == 200
    import json
    h = json.loads(body)
    assert h["ok"] and not h["draining"]


def test_unknown_route_404(sim_server):
    status, _ = fetch("127.0.0.1", sim_server.port, "/nope")
    assert status == 404


def test_stream_lifecycle_frames(sim_server):
    evs = collect("127.0.0.1", sim_server.port,
                  {"prompt_len": 8, "max_tokens": 6})
    kinds = [k for k, _ in evs]
    assert kinds[0] == "accepted" and kinds[-1] == "finish"
    assert kinds.count("token") == 6
    toks = [d for k, d in evs if k == "token"]
    assert [d["index"] for d in toks] == list(range(6))
    # §5 pacing: visible instants never violate the TDS floor
    vis = [d["visible"] for d in toks]
    assert all(b - a >= 1.0 / SPEC.tds - 1e-9
               for a, b in zip(vis, vis[1:]))
    fin = evs[-1][1]
    assert fin["n_tokens"] == 6 and 0.0 <= fin["qoe"] <= 1.0


def test_stream_network_scenario_paces_visible_times(sim_server):
    """`network` in the payload routes the SSE visible_time through the
    matching JitterLossLink — satellite 3's buffer models on the wire."""
    ideal = collect("127.0.0.1", sim_server.port,
                    {"prompt_len": 8, "max_tokens": 6, "network": "ideal"})
    sat = collect("127.0.0.1", sim_server.port,
                  {"prompt_len": 8, "max_tokens": 6, "network": "satellite"})
    v_ideal = [d["visible"] for k, d in ideal if k == "token"]
    v_sat = [d["visible"] for k, d in sat if k == "token"]
    # satellite adds >= 0.3 s propagation before the first visible token
    assert v_sat[0] >= v_ideal[0] + 0.25


def test_bad_payload_400(sim_server):
    import json as _json
    import socket
    from repro.server.client import _request_bytes, _split_head
    with socket.create_connection(("127.0.0.1", sim_server.port), 5) as s:
        s.sendall(_request_bytes("POST", "/v1/stream", "x", b"not json"))
        data = b""
        while True:
            c = s.recv(65536)
            if not c:
                break
            data += c
    status, _, _ = _split_head(data)
    assert status == 400


def test_metrics_prometheus_round_trip(sim_server):
    collect("127.0.0.1", sim_server.port, {"prompt_len": 6, "max_tokens": 4})
    status, text = fetch("127.0.0.1", sim_server.port, "/metrics")
    assert status == 200
    parsed = parse_prometheus(text)
    live = registry_samples_dict(sim_server.registry)
    assert parsed.keys() == live.keys()
    for k, v in live.items():
        assert parsed[k] == pytest.approx(v, rel=1e-6, abs=1e-9), k
    # the server-layer metrics exist and moved
    assert parsed[("requests_submitted_total", ())] >= 1
    assert parsed[("sse_events_flushed_total", ())] >= 6
    assert parsed[("connection_events_total", (("event", "open"),))] >= 1


def test_fresh_server_attaches_no_trace_recorder():
    """The server keeps no event trace of its own (a caller attaches a
    TraceRecorder to the backend when it wants one): of its own it
    attaches the MetricsObserver alone, and /metrics still round-trips."""
    from repro.obs import MetricsObserver, MultiObserver, TraceRecorder

    srv = ServingServer(ServerConfig(clock="virtual", warmup=False),
                        backend=_sim_backend())
    obs = srv.backend.observer
    attached = obs.children if isinstance(obs, MultiObserver) else (obs,)
    assert sum(isinstance(o, MetricsObserver) for o in attached) == 1
    assert not any(isinstance(o, TraceRecorder) for o in attached)
    assert not hasattr(srv, "trace")
    srv.start()
    try:
        collect("127.0.0.1", srv.port, {"prompt_len": 6, "max_tokens": 3})
        status, text = fetch("127.0.0.1", srv.port, "/metrics")
        live = registry_samples_dict(srv.registry)
    finally:
        srv.shutdown(drain=False)
    assert status == 200
    parsed = parse_prometheus(text)
    assert parsed.keys() == live.keys()
    for k, v in live.items():
        assert parsed[k] == pytest.approx(v, rel=1e-6, abs=1e-9), k
    assert parsed[("requests_finished_total", ())] == 1


def test_concurrent_streams(sim_server):
    import asyncio

    async def many(n):
        return await asyncio.gather(*[
            astream("127.0.0.1", sim_server.port,
                    {"prompt_len": 6, "max_tokens": 5})
            for _ in range(n)])

    results = asyncio.run(many(8))
    assert len(results) == 8
    rids = set()
    for evs in results:
        kinds = [k for k, _ in evs]
        assert kinds[0] == "accepted" and kinds[-1] == "finish"
        assert kinds.count("token") == 5
        rids.add(evs[0][1]["rid"])
    assert len(rids) == 8                      # no cross-talk between conns


def test_backpressure_evicts_slow_consumer(sim_server):
    """_offer() mechanics: a connection whose bounded queue fills is
    evicted — unread frames dropped, `evicted` + terminal sentinel queued,
    request cancelled via the pump's command queue."""
    conn = _Conn(conn_id=9999, depth=2)
    sim_server._offer(conn, [{"event": "token", "index": 0}])
    sim_server._offer(conn, [{"event": "token", "index": 1}])
    assert not conn.dead
    sim_server._offer(conn, [{"event": "token", "index": 2}])   # overflow
    assert conn.dead
    batch = conn.queue.get_nowait()
    assert batch[0]["event"] == "evicted"
    assert conn.queue.get_nowait() is None      # stream terminated
    # further offers are no-ops
    sim_server._offer(conn, [{"event": "token", "index": 3}])
    assert conn.queue.empty()


class _FailingSim(ServingSimulator):
    """A backend whose device step fails as soon as it has work."""

    def step(self, *args, **kwargs):
        if self.has_work:
            raise RuntimeError("device lost")
        return super().step(*args, **kwargs)


def test_backend_failure_ends_streams_with_error():
    """A failure in the pump's step() must not leave streams hanging:
    live ones end with an `error` frame, new ones are refused, and
    shutdown() reports the failure (so `python -m repro.server` exits
    non-zero)."""
    cfg = get_smoke_config("llama3-8b")
    lat = LatencyModel(cfg, TPU_V5E)
    backend = _FailingSim(make_scheduler("andes", 4_000, lat), lat,
                          SimConfig(kv_capacity_tokens=4_000))
    srv = ServingServer(ServerConfig(clock="virtual", warmup=False),
                        backend=backend)
    srv.start()
    try:
        evs = collect("127.0.0.1", srv.port,
                      {"prompt_len": 6, "max_tokens": 5}, timeout=30)
        assert evs[-1][0] == "error", evs
        assert "device lost" in evs[-1][1]["message"]
        assert isinstance(srv.failure, RuntimeError)
        again = collect("127.0.0.1", srv.port,
                        {"prompt_len": 6, "max_tokens": 5}, timeout=30)
        assert again[0][0] == "http_error" and again[0][1]["status"] == 503
    finally:
        phase = srv.shutdown(drain=True, timeout=5)
    assert phase == "failed"
    assert srv.registry.value("drain_events_total", phase="failed") == 1


# ---------------------------------------------------------------------------
# engine-backed virtual server: token identity over the wire
# ---------------------------------------------------------------------------

def test_engine_stream_token_identity_vs_direct_run():
    """The SSE byte stream must carry exactly the token ids a direct
    virtual-clock engine run produces — the wire adds a protocol, never
    a behavior (acceptance criterion, fast half)."""
    config = ServerConfig(clock="virtual", warmup=False)
    srv = ServingServer(config)
    try:
        srv.start()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, srv.model_cfg.vocab_size, 9).tolist()
                   for _ in range(3)]
        got = {}
        for i, toks in enumerate(prompts):
            evs = collect("127.0.0.1", srv.port,
                          {"prompt_tokens": toks, "max_tokens": 7,
                           "rid": 50 + i})
            got[50 + i] = [d["token"] for k, d in evs if k == "token"]
    finally:
        srv.shutdown(drain=False)

    _, ref_eng = build_engine(config)
    wl = [Request(rid=50 + i, arrival=0.0, prompt_len=9, output_len=7,
                  spec=SPEC, prompt_tokens=np.asarray(toks, np.int32))
          for i, toks in enumerate(prompts)]
    ref_eng.run(wl, max_iterations=2000)
    for r in wl:
        assert got[r.rid] == [int(t) for t in r.output_tokens], r.rid


# ---------------------------------------------------------------------------
# slowed wall-clock server: cancellation, drain, and the 503 barrier
# ---------------------------------------------------------------------------

def _slow_wall_server():
    """Wall engine with overhead=0.05 s/iteration: tokens ~50 ms apart,
    so client actions (disconnect, shutdown) land mid-stream reliably."""
    import jax

    from repro.models import Model
    from repro.serving import ServingEngine

    cfg = get_smoke_config("llama3-8b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    hw = dataclasses.replace(TPU_V5E, overhead=0.05)
    lat = LatencyModel(cfg, hw)
    sched = make_scheduler("andes", 4 * 64, lat)
    eng = ServingEngine(model, params, sched, lat, num_slots=4, max_seq=64,
                        clock="wall")
    return ServingServer(ServerConfig(clock="wall", warmup=True,
                                      drain_timeout=60.0),
                         backend=eng, model_cfg=cfg)


@pytest.fixture(scope="module")
def wall_server():
    srv = _slow_wall_server()
    srv.start()
    yield srv
    if not srv._stopped.is_set():
        srv.shutdown(drain=False)


def test_disconnect_cancels_request(wall_server):
    port = wall_server.port
    rid_seen = {}
    gen = stream("127.0.0.1", port,
                 {"prompt_len": 6, "max_tokens": 50, "rid": 700},
                 max_events=4)                 # accepted + 3 tokens, then hang up
    for k, d in gen:
        if k == "accepted":
            rid_seen[700] = d["rid"]
    assert rid_seen[700] == 700
    req = next(r for r in wall_server.backend.seen if r.rid == 700)
    deadline = time.monotonic() + 30
    while not req.cancelled and time.monotonic() < deadline:
        time.sleep(0.05)
    assert req.cancelled and req.generated < 50
    # KV slot returned to the pool so survivors can use it
    deadline = time.monotonic() + 10
    while wall_server.backend.kv.slots_in_use and time.monotonic() < deadline:
        time.sleep(0.05)
    assert wall_server.backend.kv.slots_in_use == 0
    assert wall_server.registry.value("requests_cancelled_total") >= 1


def test_graceful_drain_completes_live_streams_and_503s_new(wall_server):
    """shutdown(drain=True) mid-stream: live connections run to a clean
    `finish`, new streams bounce with 503, terminal phase is "done".
    (Last test in the file — it consumes the shared wall server.)"""
    port = wall_server.port
    results = {}
    started = threading.Barrier(4)

    def client(i):
        evs = []
        g = stream("127.0.0.1", port,
                   {"prompt_len": 6, "max_tokens": 25, "rid": 800 + i})
        for ev in g:
            evs.append(ev)
            if ev[0] == "accepted":
                started.wait(timeout=30)
        results[i] = evs

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for th in threads:
        th.start()
    started.wait(timeout=30)                   # all three streams admitted

    phase_box = {}
    shut = threading.Thread(
        target=lambda: phase_box.update(p=wall_server.shutdown(drain=True)))
    shut.start()
    deadline = time.monotonic() + 10
    while not wall_server._draining and time.monotonic() < deadline:
        time.sleep(0.005)
    assert wall_server._draining
    rejected = collect("127.0.0.1", port, {"prompt_len": 4, "max_tokens": 4})
    assert rejected and rejected[0][0] == "http_error"
    assert rejected[0][1]["status"] == 503

    shut.join(timeout=120)
    for th in threads:
        th.join(timeout=30)
    assert phase_box["p"] == "done"
    for i in range(3):
        kinds = [k for k, _ in results[i]]
        assert kinds[-1] == "finish", kinds     # drained, not killed
        assert kinds.count("token") == 25
    # drain lifecycle reached the observability layer
    assert wall_server.registry.value("drain_events_total",
                                      phase="begin") == 1
    assert wall_server.registry.value("drain_events_total",
                                      phase="done") == 1
