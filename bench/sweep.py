#!/usr/bin/env python3
"""Find a configuration's knee: the highest Poisson rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 2,2.5,3,3.5 \
        --seconds 30 --seed 1

Runs the cell's traffic at each fixed rate (req/s; the mix's `load` is
ignored), one window per rate, in one process, and prints one JSON line
per rate: tokens/s, QoE, TTFT p90, queue wait p90, and the requests
waiting for a slot at the window's middle and end. The knee is the
highest rate whose queue does not grow over the window. The benchmark's
own runs never run this; its reading goes into the configuration file's
`knee_rps`, under the mix's name.
"""
from __future__ import annotations

import argparse
import copy
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def waiting(obs, t: float) -> int:
    """Requests submitted by t that had no slot yet at t."""
    return sum(1 for rid, s in obs.submitted.items()
               if s <= t and obs.first_prefill.get(rid, float("inf")) > t)


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = bench_run.load_cell(args.workload)
    names = ["qoe_mean", "ttft_p90_s", "tokens_s", "queue_wait_p90_s",
             "preempt_per_req", "gen_late_p99_ms"]
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.config["knee_rps"] = {cell.traffic: rate}
        cell.mix["load"] = 1.0
        cell.end_to_end = [{"name": n, "unit": ""} for n in names]
        keep = {}
        result = bench_run.run_cell(cell, args.seed, args.seconds, False,
                                    out=io.StringIO(), keep=keep)
        run = keep["run"]
        mid = run.t0 + args.seconds / 2
        print(json.dumps({
            "rate": rate, "attempted": result["attempted"],
            "failed": result["failed"], "correct": result["correct"],
            **{k: v["value"] for k, v in result["metrics"].items()},
            "waiting_mid": waiting(run.obs, mid),
            "waiting_end": waiting(run.obs, run.t_end)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
