"""Quickstart: the Andes user timeline, through the unified serving API.

The paper defines Quality-of-Experience on the USER's timeline (§4):
first token promptly (TTFT), then tokens at a digestible pace (TDS), with
a client-side buffer (§5) re-smoothing server burstiness. `ServingClient`
is that abstraction as an API:

1. Submit a prompt with a QoE expectation (+ optional SLO contract).
2. Iterate the returned StreamHandle: each TokenEvent carries the server
   emit time AND the buffer-paced time the user actually sees it.
3. Read Eq. 1 QoE / TTFT off the handle when the stream ends.

The same client fronts the discrete-event simulator, this real JAX model
engine, its speculative variant, or a whole multi-replica cluster
(examples/serve_cluster.py).

A second thread (PR 6): the observability layer. Attaching a
`TraceRecorder` + `MetricsObserver` records every lifecycle event and
rolls up TTFT/TDS/QoE metrics WITHOUT changing a single emitted token or
timestamp (the tests pin that bit-for-bit); this script prints one
request's traced token timeline, dumps a metrics snapshot, and writes
the trace (JSONL + Perfetto-loadable Chrome JSON) and metrics
(Prometheus text + JSON) artifacts next to the working directory.

A third thread (PR 7): the scheduling-policy arena. Any policy behind
the `SchedulingPolicy` protocol — the paper's QoE knapsack, FCFS, the
VTC/WSC fairness counters, the burst-preemptive buffer-slack policy —
drives the same backends; step 6 runs a two-policy head-to-head on a
synchronized-burst adversarial trace and scores it with the arena's
fairness/goodput report (the full sweep is `make bench-arena`).

A fourth thread (PR 9): the same stack over an actual wire. `make
serve` boots an HTTP/SSE frontend (`python -m repro.server`) whose
`POST /v1/stream` maps this example's StreamHandle lifecycle 1:1 onto
server-sent events, in real time from a `clock="wall"` engine:

    $ PYTHONPATH=src python -m repro.server --port 8080 &
    $ curl -N -X POST http://127.0.0.1:8080/v1/stream \
           -d '{"prompt_len": 8, "max_tokens": 6}'
    $ curl http://127.0.0.1:8080/metrics | head   # live Prometheus text
    $ kill -TERM %1                               # graceful drain

See examples/serve_http.py for the full walkthrough (network-degraded
§5 pacing, metrics, drain; artifacts under out/) and
`serving/tolerance.py` for how wall-clock runs are verified against the
virtual-clock reference used here.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import json
import pathlib

import jax
import numpy as np

from repro.api import ServingClient, SLOContract, SubmitOptions
from repro.configs import get_smoke_config
from repro.core import LatencyModel, QoESpec, TPU_V5E, make_scheduler
from repro.models import Model
from repro.obs import (MetricsObserver, MetricsRegistry, TraceRecorder,
                       register_backend_gauges)
from repro.serving import ServingEngine

# --- 1. a tiny Llama-family model behind the Andes scheduler ----------------
cfg = get_smoke_config("llama3-8b")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
lat = LatencyModel(cfg, TPU_V5E)
engine = ServingEngine(model, params,
                       make_scheduler("andes", kv_capacity=160, lat=lat),
                       lat, num_slots=3, max_seq=64, capacity_tokens=160)

# --- 1b. observability: trace + metrics riding along, zero behavior change --
trace = TraceRecorder()                       # every lifecycle event, typed
registry = MetricsRegistry()
engine.attach_observer(trace)
engine.attach_observer(MetricsObserver(registry))
register_backend_gauges(registry, engine)     # live KV occupancy gauges

# --- 2. one client session; a burst of prompts with QoE expectations --------
client = ServingClient(engine)                # composes with the observers
rng = np.random.default_rng(0)
reading = QoESpec(ttft=1.0, tds=4.8)          # 1 s first token, reading pace
handles = []
for i in range(8):
    prompt = rng.integers(0, cfg.vocab_size, int(rng.integers(8, 24)))
    handles.append(client.submit(
        prompt,
        SubmitOptions(
            spec=reading, max_tokens=16, arrival=i * 0.02,  # bursty arrivals
            # a per-tenant SLO contract: what "served well" means, and how
            # much this tenant's QoE weighs in fleet pricing
            contract=SLOContract(ttft_target=2.0, qoe_floor=0.9, weight=1.0),
        ),
        on_preempt=lambda h, t: print(
            f"   (req {h.rid} preempted at t={t:.2f}s)"),
    ))

# --- 3. the user timeline: server emits vs buffer-paced visibility ----------
print(f"{'req':>4} {'TTFT':>6} {'QoE':>6}  visible at (buffer-paced, s)")
for h in handles:
    shown = [round(ev.visible_time, 2) for ev in h]   # iterating drives
    print(f"{h.rid:>4} {h.ttft():6.2f} {h.qoe():6.2f}  "
          f"{shown[:6]}{'...' if len(shown) > 6 else ''}")

print(f"\navg QoE {client.avg_qoe():.3f} | "
      f"{engine.preemptions} preemptions | "
      f"{engine.total_tokens} tokens generated")

# --- 4. what the trace saw: one request's token timeline --------------------
rid = handles[0].rid
print(f"\ntraced timeline of request {rid}:")
for ev in trace.events:
    if ev.rid == rid and ev.kind not in ("sync", "dispatch"):
        extra = {k: v for k, v in ev.data.items() if k != "scores"}
        print(f"   t={ev.t:7.3f}s  {ev.kind:<12} {extra}")

# --- 5. final metrics snapshot, and the artifacts on disk -------------------
print("\nmetrics snapshot:")
for name in ("requests_finished_total", "tokens_emitted_total",
             "weighted_attainment", "kv_peak_utilization"):
    print(f"   {name:<28} {registry.value(name):g}")
total_preempts = sum(v for _, _, v
                     in registry.get("preemptions_total").samples())
print(f"   preemptions_total            {total_preempts:g}")
ttft = registry.get("ttft_seconds")
print(f"   ttft_seconds                 count {ttft.count()} "
      f"mean {ttft.sum() / max(ttft.count(), 1):.2f}s")

out = pathlib.Path("out")           # gitignored: run artifacts stay out of
out.mkdir(exist_ok=True)            # the repo root / version control
trace.save_jsonl(out / "quickstart_trace.jsonl")
trace.save_chrome_trace(out / "quickstart_trace.perfetto.json")
(out / "quickstart_metrics.prom").write_text(registry.to_prometheus())
(out / "quickstart_metrics.json").write_text(
    json.dumps(registry.to_json(), indent=2) + "\n")
print("\nwrote out/quickstart_trace.jsonl / out/quickstart_trace.perfetto.json "
      "(load in ui.perfetto.dev) and out/quickstart_metrics.{prom,json}")

# --- 6. policy arena head-to-head: Andes vs FCFS on a synchronized burst ----
# Same trace, same simulator, two scheduling policies behind one protocol.
# The burst trace packs half the arrivals into rhythmic spikes — exactly
# where FCFS's head-of-line blocking hurts and the QoE knapsack shines.
# Scored at the arena's paper-scale latency model (OPT-66B on 4xA100) so
# the spikes actually contend; `make bench-arena` runs the full sweep.
from repro.configs import get_config
from repro.core import A100_4X, SchedulerConfig, fairness_report
from repro.serving.simulator import ServingSimulator, SimConfig
from repro.workload import make_adversarial_workload

ARENA_KV = 12_000
arena_lat = LatencyModel(get_config("opt-66b"), A100_4X)
print("\npolicy arena (burst trace, 150 requests):")
print(f"{'policy':>8} {'avg QoE':>8} {'goodput tok/s':>14} {'Jain':>6}")
for policy in ("fcfs", "andes"):
    sched = make_scheduler(policy, ARENA_KV, arena_lat, SchedulerConfig())
    sim = ServingSimulator(sched, arena_lat,
                           SimConfig(kv_capacity_tokens=ARENA_KV))
    res = sim.run(make_adversarial_workload("burst", 150, 6.0, seed=0))
    rep = fairness_report(res.requests, res.makespan)
    print(f"{policy:>8} {rep['avg_qoe']:8.3f} "
          f"{rep['goodput_tok_s']:14.1f} {rep['jains_index']:6.3f}")
print("full sweep (6 policies x 3 adversarial traces): make bench-arena")
