"""Serving launcher: real engine (small models, CPU/TPU) or simulator
(paper-scale deployments).

  python -m repro.launch.serve --mode engine --arch llama3-8b --smoke \\
      --scheduler andes --requests 20
  python -m repro.launch.serve --mode sim --rate 3.6 --requests 1000 \\
      --scheduler andes
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import (
    A100_4X,
    LatencyModel,
    QoESpec,
    SchedulerConfig,
    hardware_for_device,
    make_scheduler,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import Request, ServingEngine
from repro.serving.simulator import ServingSimulator, SimConfig
from repro.workload import make_workload


def run_sim(args) -> None:
    cfg = get_config(args.arch)
    lat = LatencyModel(cfg, A100_4X)
    wl = make_workload(args.requests, args.rate, seed=args.seed,
                       dataset=args.dataset)
    sched = make_scheduler(args.scheduler, args.kv_capacity, lat,
                           SchedulerConfig(objective=args.objective))
    res = ServingSimulator(sched, lat,
                           SimConfig(kv_capacity_tokens=args.kv_capacity)).run(wl)
    q = res.qoes()
    print(f"scheduler={args.scheduler} rate={args.rate} n={args.requests}")
    print(f"  avg QoE        {res.avg_qoe():.3f}  (p10 {np.percentile(q,10):.2f}"
          f" p50 {np.percentile(q,50):.2f})")
    print(f"  TTFT p50/p90   {np.percentile(res.ttfts(),50):.2f}s /"
          f" {np.percentile(res.ttfts(),90):.2f}s")
    print(f"  throughput     {res.throughput():.1f} tok/s")
    print(f"  preemptions    {res.preemption_freq():.2f} /request")


def run_engine(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    # full configs run bf16 weights and KV cache: f32 does not fit a chip
    if args.smoke:
        cfg, dtype = get_smoke_config(args.arch), jnp.float32
    else:
        cfg, dtype = get_config(args.arch), jnp.bfloat16
    model = Model(cfg, param_dtype=dtype)
    params = model.init(jax.random.PRNGKey(args.seed))
    lat = LatencyModel(cfg, hardware_for_device(jax.devices()[0]))
    rng = np.random.default_rng(args.seed)
    wl = []
    for i in range(args.requests):
        plen = int(rng.integers(8, 32))
        wl.append(Request(
            rid=i, arrival=i * 1.0 / args.rate, prompt_len=plen,
            output_len=int(rng.integers(8, 24)),
            spec=QoESpec(ttft=1.0, tds=4.8),
            prompt_tokens=rng.integers(0, cfg.vocab_size, plen),
        ))
    sched = make_scheduler(args.scheduler, args.kv_capacity, lat,
                           SchedulerConfig(objective=args.objective))
    eng = ServingEngine(model, params, sched, lat, num_slots=args.slots,
                        max_seq=args.max_seq,
                        capacity_tokens=args.kv_capacity, cache_dtype=dtype)
    out = eng.run(wl)
    done = [r for r in out if r.generated >= r.output_len]
    print(f"engine: {len(done)}/{len(wl)} finished, "
          f"{eng.total_tokens} tokens, {eng.preemptions} preemptions, "
          f"avg QoE {np.mean([r.final_qoe() for r in done]):.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("sim", "engine"), default="sim")
    ap.add_argument("--arch", default="opt-66b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scheduler", default="andes",
                    choices=("fcfs", "round_robin", "andes", "andes_dp"))
    ap.add_argument("--objective", default="avg_qoe")
    ap.add_argument("--rate", type=float, default=3.3)
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--kv-capacity", type=int, default=65_000)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.mode == "sim":
        run_sim(args)
    else:
        if args.mode == "engine" and not args.smoke:
            print("note: full configs on CPU are slow; use --smoke")
        args.kv_capacity = min(args.kv_capacity, args.slots * args.max_seq)
        enable_compile_cache()
        run_engine(args)


if __name__ == "__main__":
    main()
