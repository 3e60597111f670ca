import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this lowers the phase's step function against
ShapeDtypeStruct inputs (no allocation), compiles it for the production
mesh, and records:
  * memory_analysis()      — per-device bytes (arguments / output / temp)
  * cost_analysis()        — per-device HLO FLOPs and bytes accessed
  * collective bytes       — parsed from the compiled HLO (hlo_stats)
  * derived roofline terms — compute / memory / collective seconds on
                             TPU v5e constants (benchmarks/roofline.py
                             renders the table from these JSONs)

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]

Shape/phase mapping: train_4k -> train_step, prefill_32k -> prefill,
decode_32k / long_500k -> serve_step (single token vs seq_len-deep cache).
long_500k uses the sliding-window decode variant for attention archs
(sub-quadratic; window from configs), full state for SSM/hybrid.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_shape
from repro.configs.base import INPUT_SHAPES
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    make_shardings,
    param_specs,
)
from repro.launch.hlo_stats import collective_stats, flop_stats
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train import build_train_step

# TPU v5e constants (system prompt / DESIGN.md §Roofline)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per link


def _abstract(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree
    )


def build_lowering(arch: str, shape_name: str, mesh, *,
                   window_long: bool = True, opt: int = 0,
                   microbatches: int | None = None):
    """Returns (lowered, meta) for the given combination.

    opt=0 is the paper-faithful baseline; opt=1 enables the beyond-paper
    optimizations from EXPERIMENTS.md §Perf (KV-head replication to the TP
    degree for serving shapes; reduced-microbatch FSDP for training).
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    window = None
    if shape_name == "long_500k" and cfg.attn_layer_ids():
        window = cfg.sliding_window          # sub-quadratic decode variant
    kv_repeat = 1
    tp = mesh.shape["model"]
    if opt >= 1 and cfg.num_kv_heads and shape.phase in ("prefill", "decode"):
        if tp % cfg.num_kv_heads == 0 and tp > cfg.num_kv_heads:
            r = tp // cfg.num_kv_heads             # hillclimb #1
            chips = mesh.devices.size
            data = chips // tp
            kv_dev = (shape.seq_len * shape.global_batch
                      * cfg.kv_bytes_per_token() * r) / chips
            # guards (from the blanket-apply sweep, EXPERIMENTS.md §Perf):
            #  - batch must shard on data (B=1 long-context gains nothing),
            #  - replicated KV must stay comfortably in HBM — when KV is
            #    already the memory bound (405B), replication regresses.
            if shape.global_batch % data == 0 and kv_dev < 8e9:
                kv_repeat = r
    # chunked MoE pays at long-sequence *prefill* (hillclimb #3); in
    # training the global dispatch amortizes better (blanket-apply sweep
    # showed 0.64-0.89x regressions) — so prefill only
    moe_chunk = 2048 if (opt >= 1 and cfg.kind == "moe"
                         and shape.phase == "prefill") else 0
    # opt 2: explicit shard_map expert-parallel dispatch (distributed/moe_ep)
    moe_ep = mesh if (opt >= 2 and cfg.kind == "moe"
                      and shape.phase in ("prefill", "train")) else None
    if moe_ep is not None:
        moe_chunk = 0
    model = Model(cfg, impl="ref", window=window, param_dtype=jnp.bfloat16,
                  kv_repeat=kv_repeat, moe_seq_chunk=moe_chunk,
                  moe_ep_mesh=moe_ep)

    params_abs = model.abstract_params()
    p_specs = param_specs(mesh, params_abs)
    p_shard = make_shardings(mesh, p_specs)
    specs = model.input_specs(shape)

    if shape.phase == "train":
        opt_abs = jax.eval_shape(init_opt_state, params_abs)
        o_specs = jax.tree.map(lambda _: None, opt_abs)
        # mu/nu shard like params; step replicated
        from jax.sharding import PartitionSpec as P
        o_specs = type(opt_abs)(
            step=P(), mu=p_specs, nu=jax.tree.map(lambda s: s, p_specs)
        )
        o_shard = make_shardings(mesh, o_specs)
        b_specs = batch_specs(mesh, specs, cfg)
        b_shard = make_shardings(mesh, b_specs)
        if microbatches is not None:
            micro = microbatches
        else:
            # 16-sample microbatches; hillclimb #2 showed fewer microbatches
            # barely moves the (activation-dominated) traffic while tripling
            # per-device activation memory — so opt keeps the same default
            micro = max(1, shape.global_batch // 16)
        step = build_train_step(model, OptimizerConfig(), remat=True,
                                microbatches=micro)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        lowered = fn.lower(params_abs, opt_abs, specs)

    elif shape.phase == "prefill":
        b_specs = batch_specs(mesh, specs, cfg)
        b_shard = make_shardings(mesh, b_specs)
        enc_seq = shape.seq_len // 4 if cfg.kind in ("encdec", "audio") else 0
        cache_abs = model.init_cache(
            shape.global_batch, shape.seq_len, enc_seq=enc_seq,
            dtype=jnp.bfloat16, abstract=True,
        )
        c_specs = cache_specs(mesh, cache_abs, cfg)
        c_shard = make_shardings(mesh, c_specs)

        def prefill_fn(params, batch):
            cache = jax.tree.map(
                lambda l: jnp.zeros(l.shape, l.dtype), cache_abs
            )
            return model.prefill(params, batch, cache)

        fn = jax.jit(
            prefill_fn,
            in_shardings=(p_shard, b_shard),
            out_shardings=(None, c_shard),
        )
        lowered = fn.lower(params_abs, specs)

    else:  # decode — serve_step: ONE token against a seq_len cache
        cache_abs = specs["cache"]
        c_specs = cache_specs(mesh, cache_abs, cfg)
        c_shard = make_shardings(mesh, c_specs)
        t_specs = batch_specs(mesh, {"tokens": specs["tokens"]}, cfg)
        t_shard = make_shardings(mesh, t_specs)["tokens"]
        fn = jax.jit(
            model.decode_step,
            in_shardings=(p_shard, t_shard, c_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(2,),
        )
        lowered = fn.lower(params_abs, specs["tokens"], cache_abs)

    return lowered, {"cfg": cfg, "shape": shape, "window": window}


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            mesh=None, verbose: bool = True, opt: int = 0,
            microbatches: int | None = None) -> dict:
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    lowered, meta = build_lowering(arch, shape_name, mesh, opt=opt,
                                   microbatches=microbatches)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    colls = collective_stats(hlo_text)
    fstats = flop_stats(hlo_text)
    # cost_analysis counts while (lax.scan) bodies ONCE — correct by the
    # trip-aware/naive dot-flop ratio from the HLO (hlo_stats docstring)
    corr = fstats.correction
    flops = float(cost.get("flops", 0.0)) * corr          # per device
    bytes_acc = float(cost.get("bytes accessed", 0.0)) * corr

    cfg = meta["cfg"]
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    shape = meta["shape"]
    if shape.phase == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.phase == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = colls.total_bytes / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": f"{'x'.join(str(s) for s in mesh.devices.shape)}"
                f" ({','.join(mesh.axis_names)})",
        "chips": int(chips),
        "phase": shape.phase,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "scan_trip_correction": corr,
        "trip_aware_dot_flops_per_device": fstats.trip_aware_dot_flops,
        "fused_bound_bytes_per_device": fstats.trip_aware_dot_bytes,
        "memory_s_fused_bound": fstats.trip_aware_dot_bytes / HBM_BW,
        "collective_bytes_per_device": colls.total_bytes,
        "collective_counts": colls.count_by_op,
        "collective_bytes_by_op": colls.bytes_by_op,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "roofline": {
            **{k: v for k, v in terms.items()},
            "dominant": dominant,
            "model_flops_global": model_flops,
            "useful_flops_ratio": model_flops / max(flops * chips, 1.0),
        },
        "window": meta["window"],
        "params": n_params,
        "active_params": n_active,
        "opt": opt,
    }
    if verbose:
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) / 1e9
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: "
              f"compile {t_compile:.0f}s | "
              f"args+temp+out {peak:.2f} GB/dev | "
              f"flops/dev {flops:.3e} | bytes/dev {bytes_acc:.3e} | "
              f"coll {colls.total_bytes:.3e} B | dominant={dominant}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", type=int, default=0,
                    help="0=paper-faithful baseline, 1=beyond-paper opts")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    combos = []
    if args.all:
        for a in ARCH_IDS:
            if a == "opt-66b":
                continue       # paper model is benchmark-only, not assigned
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in combos:
        tag = "multipod" if args.multi_pod else "pod"
        if args.opt:
            tag += f"_opt{args.opt}"
        path = os.path.join(args.out, f"{arch}_{shape}_{tag}.json")
        if os.path.exists(path):
            print(f"[dryrun] skip (exists): {path}")
            continue
        try:
            res = run_one(arch, shape, multi_pod=args.multi_pod, mesh=mesh,
                          opt=args.opt)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
        except Exception as e:  # noqa: BLE001 — report all failures at end
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"[dryrun] all {len(combos)} combinations lowered and compiled.")


if __name__ == "__main__":
    main()
