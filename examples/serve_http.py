"""Serve over HTTP/SSE: the wire-protocol frontend, end to end.

Everything before PR 9 drove the serving stack in-process; this example
is the repo's serving loop as an actual *service*. It boots a wall-clock
`ServingServer` (the smoke-model engine in real time: paced to its
LatencyModel schedule on the CPU, at device speed on an accelerator),
talks to it the way any HTTP client would, and
shows the three wire surfaces:

1. `POST /v1/stream` — a prompt goes in as JSON, the response comes back
   as server-sent events mapping the `StreamHandle` lifecycle 1:1:
   `accepted`, then one `token` frame per emission (with the server
   emit time AND the §5 buffer-paced visible time), then `finish` with
   TTFT/TDS/QoE. Passing `"network": "satellite"` (or any scenario from
   `repro.core.NETWORK_SCENARIOS`) routes the visible-time pacing
   through that link model — the same token timeline, experienced
   through a 300 ms pipe.
2. `GET /metrics` — the live MetricsRegistry as Prometheus text.
3. Graceful drain — `shutdown(drain=True)` finishes live streams first;
   SIGTERM does the same for `python -m repro.server`.

The equivalent curl session against a standalone server:

    $ PYTHONPATH=src python -m repro.server --port 8080 &
    # ... wait for "LISTENING 8080" ...
    $ curl -N -X POST http://127.0.0.1:8080/v1/stream \\
           -H 'Content-Type: application/json' \\
           -d '{"prompt_len": 8, "max_tokens": 6}'
    $ curl http://127.0.0.1:8080/metrics | head
    $ kill -TERM %1          # graceful drain, exits after "DRAINED done"

Artifacts (out/): the captured SSE transcript, a Prometheus metrics
snapshot, and the server-side trace as JSONL.

Run:  PYTHONPATH=src python examples/serve_http.py
"""
import json
import pathlib

from repro.obs import TraceRecorder
from repro.server import ServerConfig, ServingServer, collect, fetch

OUT = pathlib.Path(__file__).resolve().parents[1] / "out"


def main():
    OUT.mkdir(exist_ok=True)
    print("=== 1. boot a wall-clock server (smoke engine, real-time "
          "pacing) ===")
    srv = ServingServer(ServerConfig(clock="wall", warmup=True))
    trace = TraceRecorder()          # the server-side event trace
    srv.backend.attach_observer(trace)
    port = srv.start()
    print(f"listening on 127.0.0.1:{port} "
          f"(clock={srv.backend.clock}, warmup done)\n")

    print("=== 2. stream one request over SSE ===")
    events = collect("127.0.0.1", port,
                     {"prompt_len": 8, "max_tokens": 10})
    for kind, data in events:
        print(f"  {kind:<9} {json.dumps(data)}")
    fin = events[-1][1]
    print(f"  -> TTFT {fin['ttft']:.3f}s, QoE {fin['qoe']:.3f}\n")

    print("=== 3. the same stream through a satellite link (§5 buffer + "
          "network model) ===")
    sat = collect("127.0.0.1", port,
                  {"prompt_len": 8, "max_tokens": 10,
                   "network": "satellite"})
    tok0 = next(d for k, d in sat if k == "token")
    print(f"  first token emitted at t={tok0['t']:.3f}s, visible at "
          f"t={tok0['visible']:.3f}s (>=300ms propagation)\n")

    print("=== 4. GET /metrics (Prometheus text) ===")
    _, prom = fetch("127.0.0.1", port, "/metrics")
    for line in prom.splitlines():
        if line.startswith(("requests_", "sse_", "connection_")):
            print(f"  {line}")
    print()

    print("=== 5. graceful drain ===")
    phase = srv.shutdown(drain=True)
    print(f"  drain phase: {phase}")

    (OUT / "serve_http_stream.json").write_text(
        json.dumps([{"event": k, **d} for k, d in events], indent=2) + "\n")
    (OUT / "serve_http_metrics.prom").write_text(prom)
    (OUT / "serve_http_trace.jsonl").write_text(trace.to_jsonl())
    print(f"\nartifacts: {OUT / 'serve_http_stream.json'}, "
          f"{OUT / 'serve_http_metrics.prom'}, "
          f"{OUT / 'serve_http_trace.jsonl'}")


if __name__ == "__main__":
    main()
