#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the cell's chips. The run:

1. finds the cell, its configuration file and its traffic mix by name;
   exits non-zero with no result unless JAX sees a TPU, and enough of them;
2. builds the served path as `repro.server.app.build_engine` builds it
   (registry arch, bf16 weights and KV, wall clock, the configuration's
   scheduler and engine size), except for the weights, which the
   benchmark makes from the seed on the device in one jitted call;
   warms every prefill shape the cell's traffic can form and the decode
   and swap programs, then starts the HTTP/SSE server;
3. opens the window: a child process that never imports JAX sends each
   request as `POST /v1/stream` when it is due and stamps every token
   frame as it reads it. With `--trace 1` the middle of the window is
   profiled, device and host threads alike;
4. closes the window, stops the server, reads the chip's peak memory,
   frees the engine, and checks what was served against the plain
   reference (bench/oracle.py);
5. prints the compared numbers with their limits on standard error, and
   as the last line of standard output one JSON object: `correct`,
   `attempted`, `failed`, `metrics`, `device` (and with `--trace 1`,
   `breakdown`), then `checks`.

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. Each metric is read by
`bench/metrics/<name>.py`.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

OUT_DIR = ROOT / ".bench_out"          # traces (listed in .gitignore)
TRACE_FROM, TRACE_TO = 0.4, 0.6        # traced share of the window
SLOT_KEY = 2 ** 31 - 1


def process_seconds() -> float:
    """Seconds since this process started, from the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.monotonic() - T_IMPORT


T_IMPORT = time.monotonic()


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: str                    # the mix's name
    config: Dict[str, Any]          # bench/configs/<config>.json
    mix: Dict[str, Any]             # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), traffic=w["traffic"],
        config=json.loads((ROOT / cfg["file"]).read_text()),
        mix=json.loads((ROOT / "bench" / "traffic" /
                        f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)])


def reader(metric: str):
    """`read(run)` of bench/metrics/<metric>.py."""
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- device
def require_chips(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)\n")
        raise SystemExit(3)


def peak_table(kind: str) -> Dict[str, Any]:
    """The chip's published peaks from bench/peaks.json; a device kind the
    table does not hold is an error, not a default."""
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        sys.stderr.write(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(peaks)})\n")
        raise SystemExit(3)
    return peaks[kind]


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ----------------------------------------------------------------- engine
def check_registry(cfg, config: Dict[str, Any]) -> None:
    """The program's registry config must be the configuration file's."""
    from bench import oracle

    kind = config["kind"]
    got = oracle.reference_module(kind).registry_view(cfg)
    want = dict(config["model"], kind=kind)
    diff = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if diff:
        raise SystemExit(f"registry config {cfg.name} differs from the "
                         f"benchmark's (file, program): {diff}")


def make_weights(cell: Cell, seed: int):
    """The served weights, made on the device from the seed in one jitted
    call, in bf16, in the layout the program serves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import oracle

    mod = oracle.reference_module(cell.config["kind"])
    m = cell.config["model"]
    key = jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, SLOT_KEY)))
    return jax.jit(lambda k: mod.init(m, k, jnp.bfloat16))(key)


def check_layout(params, model) -> None:
    import jax

    want = model.abstract_params()
    ok = jax.tree.structure(want) == jax.tree.structure(params) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)))
    if not ok:
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")


def build_engine(cell: Cell, params, model_cfg=None):
    """The engine `repro.server.app.build_engine` builds, with these
    weights."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import LatencyModel, hardware_for_device, make_scheduler
    from repro.models import Model
    from repro.serving import ServingEngine

    c, e = cell.config, cell.config["engine"]
    cfg = model_cfg or get_config(c["registry"])
    if model_cfg is None:
        check_registry(cfg, c)
    model = Model(cfg, param_dtype=jnp.bfloat16)
    check_layout(params, model)
    lat = LatencyModel(cfg, hardware_for_device(jax.devices()[0]))
    sched = make_scheduler(e["scheduler"], e["num_slots"] * e["max_seq"], lat)
    eng = ServingEngine(model, params, sched, lat, num_slots=e["num_slots"],
                        max_seq=e["max_seq"], clock="wall",
                        cache_dtype=jnp.bfloat16)
    return cfg, eng


def warm(eng, cfg, num_slots: int, max_seq: int,
         max_out: int) -> Dict[str, Any]:
    """Compile, before the window, every program the cell's traffic can
    drive: a prefill of every (row bucket, length bucket) pair up to the
    engine's slots, both decode paths, and the swap-out/in of every slot.
    Prefills go through the engine itself (`run`), r same-bucket prompts
    arriving together; the swap programs are called per slot. Traffic
    whose every output is one token (`max_out` 1) is served by batch-1
    prefills alone (the engine stages no one-token request into a shared
    prefill, decodes nothing and preempts nothing): only those are
    warmed."""
    import numpy as np

    from repro.core import QoESpec
    from repro.core.request import Request
    from repro.serving import engine as engine_mod

    rng = np.random.default_rng(0)
    grid = eng.hotpath_stats()["prefill_bucket_grid"]
    single = max_out <= 1
    rows = [1] if single else [1 << i for i in range(num_slots.bit_length())
                               if 1 << i <= num_slots]
    spec = QoESpec(ttft=1.0, tds=5.0)
    rid = -1
    for b in grid:
        plen = min(b, max_seq - 3)
        for r in rows:
            wl = []
            for i in range(r):
                wl.append(Request(
                    rid=rid, arrival=0.0, prompt_len=plen,
                    output_len=1 if single else 2 + (i == 0),
                    spec=spec, prompt_tokens=rng.integers(
                        0, cfg.vocab_size, plen).astype(np.int32)))
                rid -= 1
            eng.run(wl)
    read = getattr(engine_mod, "_read_slot", None)
    write = getattr(engine_mod, "_write_slot", None)
    if read is not None and write is not None and not single:
        import jax
        import jax.numpy as jnp

        cache = eng.cache
        for s in range(num_slots):
            row = jax.device_get(read(cache, s))
            cache = write(cache, jax.tree.map(jnp.asarray, row), s)
        eng.cache = jax.block_until_ready(cache)
    seen = set(map(tuple, eng.hotpath_stats()["prefill_shapes"]))
    want = {(r, b) for b in grid for r in rows}
    eng.reset()
    return {"prefill_shapes": len(seen), "missing": sorted(want - seen)}


# ------------------------------------------------------------------ window
@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: Cell
    seed: int
    seconds: float
    reqs: list                       # bench.traffic.Req, due in window
    recs: Dict[int, Dict[str, Any]]  # rid -> client record
    t0: float
    t_end: float
    setup_s: float
    obs: Any                         # bench.observe.Recorder
    peaks: Dict[str, Any]
    traced: Optional[Dict[str, Any]] = None
    syncs: int = 0


def open_client() -> subprocess.Popen:
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX")}
    return subprocess.Popen([sys.executable, str(ROOT / "bench" /
                                                 "client.py")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            env=env, text=True)


def run_window(srv, port: int, client: subprocess.Popen, reqs, t0: float,
               seconds: float, trace: bool) -> Dict[str, Any]:
    """Drive the window that opens at `t0`; returns the client's records
    and, when traced, the raw trace."""
    payloads = [{"rid": r.rid, "due": r.due, "payload": {
        "rid": r.rid, "prompt_tokens": r.prompt.tolist(),
        "max_tokens": r.output_len, "ttft": r.ttft, "tds": r.tds}}
        for r in reqs]
    client.stdin.write(json.dumps({"host": "127.0.0.1", "port": port,
                                   "t0": t0, "seconds": seconds,
                                   "requests": payloads}) + "\n")
    client.stdin.flush()
    traced = None
    if trace:
        traced = traced_span(t0 + TRACE_FROM * seconds,
                             t0 + TRACE_TO * seconds)
    line = client.stdout.readline()
    client.wait(timeout=60)
    if not line:
        raise RuntimeError("the load generator returned nothing")
    recs = {r["rid"]: r for r in json.loads(line)["requests"]}
    return {"t0": t0, "t_end": t0 + seconds, "recs": recs, "traced": traced}


def traced_span(a: float, b: float) -> Dict[str, Any]:
    """Profile [a, b] of the window, host threads included."""
    import shutil

    import jax

    from bench import devtrace

    OUT_DIR.mkdir(exist_ok=True)
    logdir = OUT_DIR / "trace"
    shutil.rmtree(logdir, ignore_errors=True)
    time.sleep(max(0.0, a - time.monotonic()))
    jax.profiler.start_trace(str(logdir))
    with jax.profiler.TraceAnnotation(devtrace.MARK):
        m0 = time.monotonic_ns()
        time.sleep(max(0.0, b - time.monotonic()))
        m1 = time.monotonic_ns()
    jax.profiler.stop_trace()
    return {"dir": logdir, "mono_ns": (m0, m1)}


def reduce_trace(traced: Dict[str, Any]) -> Dict[str, Any]:
    from bench import devtrace

    paths = sorted(traced["dir"].rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {traced['dir']}, "
                           f"found {len(paths)}")
    planes = devtrace.load(str(paths[0]))
    (OUT_DIR / "trace_summary.json").write_text(
        json.dumps(devtrace.summary(planes), indent=1))
    red = devtrace.reduce(planes)
    gaps = devtrace.name_gaps(red.idle, devtrace.dispatch_line(planes))
    return {"reduced": red, "gaps": gaps,
            "mono": tuple(x / 1e9 for x in traced["mono_ns"])}


# ------------------------------------------------------------- correctness
def compared_pairs(reqs, recs, preempted, seed, t_end):
    """The sample of finished requests the reference reads: (rids,
    [(prompt, served tokens)])."""
    import numpy as np

    from bench import oracle

    finished = {rid: len(r["tokens"]) for rid, r in recs.items()
                if r["end"] == "finish" and r["end_t"] <= t_end}
    rng = np.random.default_rng([int(seed), 17])
    by_rid = {r.rid: r for r in reqs}
    chosen = oracle.sample(finished, sorted(preempted), rng,
                           {rid: len(by_rid[rid].prompt) for rid in finished})
    return chosen, [(by_rid[rid].prompt, recs[rid]["tokens"])
                    for rid in chosen]


def check(cell: Cell, params, recs, engine_tokens, pairs, t_end,
          choose=None, gaps_out: Optional[list] = None
          ) -> Dict[str, Dict[str, float]]:
    """The compared numbers, each with its limit. `choose` puts a
    scorer in the program's place for the served gaps (the control,
    bench/control.py); `gaps_out` receives the gap of every token."""
    import numpy as np

    from bench import oracle
    from bench.reference import mm_f32

    wire = {rid: r["tokens"][:sum(1 for t in r["recv"] if t <= t_end)]
            for rid, r in recs.items()}
    scorer = oracle.make_scorer(cell.config["kind"], cell.config["model"],
                                mm_f32)
    vocab = cell.config["model"]["vocab_size"]
    gaps = oracle.served_gaps(scorer, params, pairs, vocab, choose)
    if gaps_out is not None:
        gaps_out.extend(gaps)
    flat = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    limits = cell.config["limits"]
    for rid in oracle.wire_mismatch_rids(wire, engine_tokens)[:3]:
        print(f"bench: wire mismatch rid {rid}: wire {wire[rid][:8]} "
              f"engine {(engine_tokens.get(rid) or [])[:8]} "
              f"end {recs[rid].get('end')}", file=sys.stderr)
    return {
        "wire_mismatch": {"value": oracle.wire_mismatches(wire,
                                                          engine_tokens),
                          "limit": 0},
        "out_of_vocab": {"value": oracle.out_of_vocab(pairs, vocab),
                         "limit": 0},
        "served_gap_max": {"value": float(flat.max()),
                           "limit": limits["served_gap_max"]},
        "tokens_compared": {"value": int(sum(len(g) for g in gaps)),
                            "limit": limits["min_tokens"], "at_least": True},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in checks.values())


# -------------------------------------------------------------------- main
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, model_cfg=None, out=sys.stdout,
             keep: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run of one cell. `require_tpu=False` and `model_cfg` (a small
    registry-free config) are for the CPU tests of the harness itself;
    `keep` receives the weights and the compared sample (bench/control.py
    reads them)."""
    client = open_client()
    try:
        return _run_cell(cell, seed, seconds, trace, client, require_tpu,
                         model_cfg, out, keep)
    finally:
        if client.poll() is None:
            client.kill()
        client.wait(timeout=10)


def _run_cell(cell, seed, seconds, trace, client, require_tpu, model_cfg,
              out, keep):
    import jax

    peaks: Dict[str, Any] = {}      # the CPU tests read no device metric
    if require_tpu:
        require_chips(cell.chips)
        peaks = peak_table(jax.devices()[0].device_kind)

    from bench import observe, traffic, window
    from repro.launch.compile_cache import enable_compile_cache
    from repro.server import ServerConfig, ServingServer

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_events: List[float] = []

    def on_event(event, *_, **__):
        # one per program compiled or read back from the persistent cache
        if event in ("/jax/compilation_cache/cache_misses",
                     "/jax/compilation_cache/cache_hits"):
            compile_events.append(time.monotonic())

    jax.monitoring.register_event_listener(on_event)

    e = cell.config["engine"]
    rate = (float(cell.config["knee_rps"][cell.traffic])
            * float(cell.mix["load"]))
    reqs = traffic.generate(cell.mix, rate=rate, seconds=seconds, seed=seed,
                            vocab=cell.config["model"]["vocab_size"],
                            max_seq=e["max_seq"])
    params = make_weights(cell, seed)
    cfg, eng = build_engine(cell, params, model_cfg)
    warmed = warm(eng, cfg, e["num_slots"], e["max_seq"],
                  max(r.output_len for r in reqs))
    obs = observe.Recorder()
    eng.attach_observer(obs)
    srv = ServingServer(ServerConfig(
        arch=cfg.name, full=True, clock="wall", scheduler=e["scheduler"],
        num_slots=e["num_slots"], max_seq=e["max_seq"], warmup=False,
        drain_timeout=0.0), backend=eng, model_cfg=cfg)
    port = srv.start()
    lead = 0.25                       # time for the client to read its plan
    t0 = time.monotonic() + lead
    setup_s = process_seconds() + lead
    try:
        win = run_window(srv, port, client, reqs, t0, seconds, trace)
    finally:
        srv.shutdown(drain=False, timeout=0.0)
    t0, t_end = win["t0"], win["t_end"]
    syncs = sum(n for t, n in obs.syncs if t0 <= t <= t_end)
    in_window = sum(1 for t in compile_events if t0 <= t <= t_end)
    print(f"bench: warmed {warmed['prefill_shapes']} prefill shapes "
          f"(missing {warmed['missing']}); programs loaded or compiled "
          f"inside the window: {in_window}", file=sys.stderr)

    device = (device_info(cell.chips) if require_tpu else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind, "count": 1,
               "memory_peak_bytes": 0})
    engine_tokens = {r.rid: list(r.output_tokens)
                     for r in eng.result().requests}
    preempted = {rid for t, rid in obs.preempts if t <= t_end}
    for leaf in jax.tree.leaves(eng.cache):
        leaf.delete()
    eng.cache = None
    eng.kv.host_store.clear()
    del srv, eng

    traced = reduce_trace(win["traced"]) if win["traced"] else None
    run = Run(cell=cell, seed=seed, seconds=seconds, reqs=reqs,
              recs=win["recs"], t0=t0, t_end=t_end, setup_s=setup_s,
              obs=obs, peaks=peaks, traced=traced,
              syncs=syncs)
    print(f"bench: generator late p99 "
          f"{reader('gen_late_p99_ms')(run):.3f} ms", file=sys.stderr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    chosen, pairs = compared_pairs(reqs, win["recs"], preempted, seed, t_end)
    checks = check(cell, params, win["recs"], engine_tokens, pairs, t_end)
    print(f"bench: compared {len(chosen)} finished requests, "
          f"{len(set(chosen) & preempted)} of them preempted and resumed",
          file=sys.stderr)
    if keep is not None:
        keep.update(params=params, pairs=pairs, run=run,
                    engine_tokens=engine_tokens)
    attempted, failed = window.attempted_failed(run)
    result = {"correct": passed(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if traced:
        red = traced["reduced"]
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {
            "device_ops": sorted(red.programs.items(),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": [list(g) for g in traced["gaps"]]}
    result["checks"] = checks
    for name, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name} {c['value']} {rel} {c['limit']}",
              file=sys.stderr)
    out.write(json.dumps(result) + "\n")
    out.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        raise SystemExit("--seconds must be positive")
    cell = load_cell(args.workload)
    run_cell(cell, args.seed, args.seconds, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
