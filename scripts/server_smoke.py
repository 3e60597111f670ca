#!/usr/bin/env python
"""CI server smoke: the ISSUE 9 acceptance criteria, over a real socket.

Boots `python -m repro.server` (wall-clock engine) as a subprocess, then
asserts, end to end:

  1. N >= 8 concurrent SSE streams complete with well-formed framing
     (accepted -> token* -> finish) over localhost.
  2. Token text is IDENTICAL to a virtual-clock reference engine run fed
     the same prompts at the same arrivals (hard gate).
  3. Wall-clock TTFT/TDS/QoE distributions agree with the reference
     within the CI-generous tolerance gates (serving.tolerance).
  4. GET /metrics parses as Prometheus text and reflects the traffic.
  5. SIGTERM mid-stream drains gracefully: live streams still finish
     cleanly, the process prints "DRAINED done" and exits 0.

Run:  PYTHONPATH=src python scripts/server_smoke.py
(The Makefile `server-smoke` target and the CI server-smoke job wrap
this in a timeout.)

One process per device: this parent never imports JAX. The server child
holds the device while it serves; the virtual-clock reference runs in a
second child (`--reference`, fed the captured streams as JSON on stdin)
started only after the server child has exited.
"""
import asyncio
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading

import numpy as np

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
sys.path.insert(0, str(SRC))

from repro.obs.metrics import parse_prometheus                  # noqa: E402
from repro.server.client import astream, fetch, stream          # noqa: E402

N_CONCURRENT = 8
OUT_LEN = 12
PROMPT_LEN = 9
TTFT, TDS = 1.0, 4.8


def child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def start_server():
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.server", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=child_env())
    lines = []
    port_box = {}
    ready = threading.Event()

    def reader():
        for line in proc.stdout:
            print(f"[server] {line.rstrip()}", flush=True)
            lines.append(line)
            if line.startswith("LISTENING"):
                port_box["port"] = int(line.split()[1])
                ready.set()
        ready.set()

    threading.Thread(target=reader, daemon=True).start()
    if not ready.wait(timeout=300) or "port" not in port_box:
        proc.kill()
        raise SystemExit("server never printed LISTENING")
    return proc, port_box["port"], lines


def prompts_for(rids):
    return {rid: np.random.default_rng((7, rid)).integers(
        0, 1 << 14, PROMPT_LEN).tolist() for rid in rids}


def differential_round(port):
    """Stream N concurrent requests; return what the wire carried, for
    the virtual-clock reference child to compare against."""
    rids = list(range(N_CONCURRENT))
    prompts = prompts_for(rids)

    async def fan_out():
        return await asyncio.gather(*[
            astream("127.0.0.1", port,
                    {"prompt_tokens": prompts[rid], "max_tokens": OUT_LEN,
                     "rid": rid})
            for rid in rids])

    results = asyncio.run(fan_out())
    streams = []
    for rid, evs in zip(rids, results):
        kinds = [k for k, _ in evs]
        assert kinds[0] == "accepted", kinds
        assert kinds[-1] == "finish", kinds
        assert kinds.count("token") == OUT_LEN, kinds
        toks = [d for k, d in evs if k == "token"]
        streams.append({
            "rid": rid, "prompt": prompts[rid],
            "arrival": float(evs[0][1]["arrival"]),
            "emit_times": [float(d["t"]) for d in toks],
            "tokens": [int(d["token"]) for d in toks],
        })
    print(f"streamed {len(streams)} concurrent SSE responses")
    return streams


def reference_main():
    """`--reference`: virtual-clock reference run of the captured streams
    (JSON on stdin): identical engine build, identical prompts, the
    server's actual arrival stamps. Token ids must match exactly; the
    wall-clock TTFT/TDS/QoE must agree within the tolerance gates."""
    from repro.core import QoESpec
    from repro.core.request import ReqState, Request
    from repro.server import ServerConfig, build_engine
    from repro.serving import Tolerance, ToleranceSpec, compare_requests

    spec = QoESpec(ttft=TTFT, tds=TDS)
    # same CI-generous gates as tests/test_tolerance.py's in-process
    # differential: wide enough for shared-runner sleep jitter, tight
    # enough to catch a host that cannot keep the smoke-model schedule
    gates = ToleranceSpec(
        ttft_mean_diff=Tolerance(abs_tol=0.5),
        ttft_p95_diff=Tolerance(abs_tol=1.0),
        ttft_max_diff=Tolerance(abs_tol=2.0),
        tds_mean_diff=Tolerance(abs_tol=2.0, rel_tol=0.5),
        qoe_mean_diff=Tolerance(abs_tol=0.30),
        qoe_max_diff=Tolerance(abs_tol=0.60),
        qoe_mean_of=Tolerance(abs_tol=0.30),
    )
    streams = json.load(sys.stdin)
    cand = []
    for st in streams:
        r = Request(rid=st["rid"], arrival=st["arrival"],
                    prompt_len=PROMPT_LEN, output_len=OUT_LEN, spec=spec)
        r.emit_times = st["emit_times"]
        r.output_tokens = st["tokens"]
        r.generated = len(st["tokens"])
        r.state = ReqState.FINISHED
        cand.append(r)
    _, ref_eng = build_engine(ServerConfig(clock="virtual"))
    ref = [Request(rid=st["rid"], arrival=st["arrival"],
                   prompt_len=PROMPT_LEN, output_len=OUT_LEN, spec=spec,
                   prompt_tokens=np.asarray(st["prompt"], np.int32))
           for st in streams]
    ref_eng.run(ref, max_iterations=4000)
    rep = compare_requests(ref, cand, gates)
    print(rep.summary())
    rep.assert_ok()


def reference_round(streams):
    out = subprocess.run(
        [sys.executable, __file__, "--reference"],
        input=json.dumps(streams), text=True, env=child_env(), timeout=600)
    assert out.returncode == 0, f"reference run exited {out.returncode}"


def metrics_round(port):
    status, text = fetch("127.0.0.1", port, "/metrics")
    assert status == 200, status
    parsed = parse_prometheus(text)
    n = parsed[("requests_submitted_total", ())]
    assert n >= N_CONCURRENT, n
    assert parsed[("sse_events_flushed_total", ())] > 0
    print(f"/metrics parses: {len(parsed)} samples, "
          f"{int(n)} requests submitted")


def drain_round(proc, port):
    """SIGTERM with live streams: every stream must still finish."""
    results = {}
    barrier = threading.Barrier(N_CONCURRENT + 1)

    def client(i):
        evs = []
        for ev in stream("127.0.0.1", port,
                         {"prompt_len": 6, "max_tokens": 24,
                          "rid": 100 + i}):
            evs.append(ev)
            if ev[0] == "accepted":
                barrier.wait(timeout=60)
        results[i] = evs

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CONCURRENT)]
    for t in threads:
        t.start()
    barrier.wait(timeout=60)             # all N streams admitted and live
    proc.send_signal(signal.SIGTERM)
    for t in threads:
        t.join(timeout=120)
    for i, evs in sorted(results.items()):
        kinds = [k for k, _ in evs]
        assert kinds[-1] == "finish", (i, kinds)
        assert kinds.count("token") == 24, (i, kinds)
    print(f"drained {len(results)} live streams cleanly after SIGTERM")


def main():
    proc, port, lines = start_server()
    try:
        st, _ = fetch("127.0.0.1", port, "/healthz")
        assert st == 200
        streams = differential_round(port)
        metrics_round(port)
        drain_round(proc, port)
        code = proc.wait(timeout=60)
        assert code == 0, f"server exited {code}"
        assert any("DRAINED done" in ln for ln in lines), lines[-3:]
    finally:
        if proc.poll() is None:
            proc.kill()
    reference_round(streams)
    assert "jax" not in sys.modules, "the parent must leave the device alone"
    print("OK: server smoke passed (SSE framing, token identity, "
          "tolerance gates, /metrics, graceful drain)")


if __name__ == "__main__":
    if sys.argv[1:] == ["--reference"]:
        reference_main()
    else:
        main()
