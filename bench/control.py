#!/usr/bin/env python3
"""Readings that set the limits on the served gaps, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 --control-seeds 3

In one process, for each seed: one run of the cell as the benchmark makes
it, whose compared numbers are the program's readings; and, for the
first `--control-seeds` seeds, the control: the plain reference put in
the program's place and computed in float8 (e4m3, scaled per row of every
activation and per column of every weight, each product rounded back to
float8: the step below the served bfloat16). At each position of the
same prompts and served tokens the control puts its own first token, and
the run's own comparison (`bench/run.py` `check`) reads the gap by which
the float32 reference's logit of that token lies below the reference's
best; `correct` is what the run would print with the control in the
program's place. The benchmark's own runs never run this. One JSON line
per seed.
"""
from __future__ import annotations

import argparse
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

E4M3_MAX = 448.0
SHARE_OVER = (0.05, 0.1, 0.25, 0.5, 1.0)


def fake_fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm_fp8(x, w):
    """A matrix product computed in float8: operands rounded on the way
    in, the product rounded on the way out (as the served path rounds
    every product to bfloat16)."""
    import jax
    import jax.numpy as jnp

    y = jnp.matmul(fake_fp8(x, -1), fake_fp8(w, 0),
                   precision=jax.lax.Precision.HIGHEST)
    return fake_fp8(y, -1)


def control_checks(cell, keep, gaps_out=None):
    """The run's comparison with the float8 reference in the program's
    place, on the run's own sample."""
    from bench import oracle
    from bench import run as bench_run

    low = oracle.make_scorer(cell.config["kind"], cell.config["model"],
                             mm_fp8)
    run = keep["run"]
    return bench_run.check(cell, keep["params"], run.recs,
                           keep["engine_tokens"], keep["pairs"], run.t_end,
                           choose=low, gaps_out=gaps_out)


def gap_stats(gaps) -> dict:
    """Widest, mean and 99th-percentile gap, and the share of tokens
    whose gap exceeds each of SHARE_OVER."""
    import numpy as np

    flat = np.concatenate(gaps)
    out = {"max": float(flat.max()), "mean": float(flat.mean()),
           "p99": float(np.quantile(flat, 0.99)), "n": int(flat.size)}
    out.update({f"share_over_{x}": float(np.mean(flat > x))
                for x in SHARE_OVER})
    return out


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        keep = {}
        result = bench_run.run_cell(cell, seed, args.seconds, False,
                                    out=io.StringIO(), keep=keep)
        run = keep["run"]
        sound = []
        bench_run.check(cell, keep["params"], run.recs,
                        keep["engine_tokens"], keep["pairs"], run.t_end,
                        gaps_out=sound)
        line = {"seed": seed, "correct": result["correct"],
                "program": {k: c["value"]
                            for k, c in result["checks"].items()},
                "program_gaps": gap_stats(sound),
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        if i < args.control_seeds:
            low = []
            checks = control_checks(cell, keep, low)
            line["control_correct"] = bench_run.passed(checks)
            line["control"] = {k: c["value"] for k, c in checks.items()}
            line["control_gaps"] = gap_stats(low)
        print(json.dumps(line), flush=True)
        del keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
