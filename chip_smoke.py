#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py

One process holds the chip; the only other threads are the server's own.
Each phase prints one line with its seconds and its numbers:

  device     platform, device_kind, device count and JAX version. Fails
             unless JAX's default device is a TPU (so `JAX_PLATFORMS=cpu`
             fails here).
  kernels    paged decode attention (page 16), decode attention and flash
             attention compiled for the chip (interpret=False) at
             granite-3-2b's heads in bf16: each program must hold a
             `tpu_custom_call` and agree with kernels/ref.py.
  served     `ServingServer` on granite-3-2b at its published widths (bf16
             weights and KV cache, wall clock, Andes scheduler) answers 8
             concurrent SSE streams over localhost; every stream must end
             in `finish` with its token count, /metrics must parse, and
             the server drains. Its numbers are a smoke run, not a
             benchmark.
  agreement  the server's engine is freed; a virtual-clock engine of the
             same build replays the same prompts at the server's arrival
             stamps and must emit the same tokens. For one prompt, the
             decode step's logits must agree with a full prefill's logits
             at the same positions.

The last line is `{"ok": true, "device": {...}}`; any failure exits
non-zero without it. JAX's persistent compile cache is on
(`repro.launch.compile_cache`), so a second run reads its compiles back.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "granite-3-2b"
SEED = 0
N_STREAMS = 8
# 8 prompts spread over 64..512 tokens, 32..64 output tokens each
PROMPT_LENS = np.linspace(64, 512, N_STREAMS).astype(int).tolist()
OUTPUT_LENS = np.linspace(32, 64, N_STREAMS).astype(int).tolist()
STREAM_TIMEOUT_S = 900.0

# bf16 tolerances. Kernels: |out - ref| <= atol + rtol * |ref| elementwise,
# the bound tests/test_kernels.py holds bf16 kernels to. Logits: the
# largest |decode - prefill| over the compared positions, relative to the
# largest |prefill| logit there; both paths read the same bf16 weights
# and KV, so only accumulation order differs.
KERNEL_TOL = 2e-2
LOGIT_REL_TOL = 2e-2
LOGIT_POSITIONS = 4
PAGE = 16


def log(name: str, seconds: float, **nums) -> None:
    items = " ".join(f"{k}={v}" for k, v in nums.items())
    print(f"[{name}] {seconds:.3f} s  {items}", flush=True)


def device_phase() -> dict:
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    d = devs[0]
    log("device", time.perf_counter() - t0, platform=d.platform,
        kind=repr(d.device_kind), count=len(devs), jax=jax.__version__)
    if d.platform != "tpu":
        raise RuntimeError(f"JAX found no TPU (default device: {d.platform})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# --------------------------------------------------------------- kernels
def _compile_and_run(fn, *args):
    """Compile `fn` for the default device; return (output, whether the
    program holds a Pallas TPU kernel)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    return out, "tpu_custom_call" in compiled.as_text()


def _paginate(k, v, lengths, page, rng):
    """Scatter contiguous (B, S, KV, hd) caches into a shuffled page pool;
    unowned table entries hold the sentinel P."""
    import jax.numpy as jnp

    b, s, kvh, hd = k.shape
    max_pages = s // page
    n = b * max_pages
    perm = rng.permutation(n)                # pool row j holds page perm[j]
    k_pool = k.reshape(n, page, kvh, hd)[perm]
    v_pool = v.reshape(n, page, kvh, hd)[perm]
    where = np.argsort(perm).reshape(b, max_pages)
    owned = np.arange(max_pages)[None] < -(-np.asarray(lengths) // page)[:, None]
    tables = np.where(owned, where, n).astype(np.int32)
    return k_pool, v_pool, jnp.asarray(tables)


def kernels_phase(*, interpret: bool = False) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.server.app import FULL_MAX_SEQ, FULL_SLOTS

    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b, s = FULL_SLOTS, FULL_MAX_SEQ
    keys = jax.random.split(jax.random.PRNGKey(SEED), 6)

    def rand(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    rng = np.random.default_rng(SEED)
    lengths = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    q1 = rand(keys[0], (b, h, hd))
    kc, vc = rand(keys[1], (b, s, kvh, hd)), rand(keys[2], (b, s, kvh, hd))
    k_pool, v_pool, tables = _paginate(kc, vc, lengths, PAGE, rng)
    qf = rand(keys[3], (1, s, h, hd))
    kf, vf = rand(keys[4], (1, s, kvh, hd)), rand(keys[5], (1, s, kvh, hd))
    decode_ref = ref.decode_attention_ref(q1, kc, vc, lengths)
    cases = {
        "paged_decode": (functools.partial(paged_decode_attention,
                                           interpret=interpret),
                         (q1, k_pool, v_pool, tables, lengths), decode_ref),
        "decode": (functools.partial(decode_attention, interpret=interpret),
                   (q1, kc, vc, lengths), decode_ref),
        "flash": (functools.partial(flash_attention, causal=True,
                                    interpret=interpret),
                  (qf, kf, vf), ref.attention_ref(qf, kf, vf, causal=True)),
    }
    nums = {}
    for name, (fn, args, expect) in cases.items():
        out, custom = _compile_and_run(fn, *args)
        out = np.asarray(out, np.float32)
        expect = np.asarray(expect, np.float32)
        err = float(np.max(np.abs(out - expect)))
        if not interpret and not custom:
            raise AssertionError(f"{name}: no tpu_custom_call in its program")
        if not np.allclose(out, expect, atol=KERNEL_TOL, rtol=KERNEL_TOL):
            raise AssertionError(f"{name}: max |out - ref| = {err} is outside "
                                 f"atol = rtol = {KERNEL_TOL}")
        nums[f"{name}_max_err"] = f"{err:.3g}"
    log("kernels", time.perf_counter() - t0, heads=f"{h}/{kvh}x{hd}",
        batch=b, seq=s, page=PAGE, dtype="bf16", tpu_custom_call="all",
        tol=KERNEL_TOL, **nums)


# ---------------------------------------------------------------- served
def traffic(vocab: int):
    """The smoke's requests: (rid, prompt token ids, output length)."""
    rng = np.random.default_rng(SEED)
    return [(rid, rng.integers(0, vocab, n).tolist(), out)
            for rid, (n, out) in enumerate(zip(PROMPT_LENS, OUTPUT_LENS))]


def served_phase(config):
    """Serve the smoke traffic through `ServingServer`; return the server
    (drained) and what each stream carried."""
    import jax

    from repro.obs.metrics import parse_prometheus
    from repro.server import ServingServer, astream, fetch

    t0 = time.perf_counter()
    srv = ServingServer(config)
    t_build = time.perf_counter() - t0
    t1 = time.perf_counter()
    port = srv.start()                       # warmup compiles here
    t_compile = time.perf_counter() - t1
    reqs = traffic(srv.model_cfg.vocab_size)

    async def fan_out():
        return await asyncio.wait_for(asyncio.gather(*[
            astream("127.0.0.1", port, {"prompt_tokens": toks,
                                        "max_tokens": out, "rid": rid})
            for rid, toks, out in reqs]), STREAM_TIMEOUT_S)

    t2 = time.perf_counter()
    try:
        results = asyncio.run(fan_out())
        t_wall = time.perf_counter() - t2
        streams = []
        for (rid, toks, out), evs in zip(reqs, results):
            kinds = [k for k, _ in evs]
            if not kinds or kinds[0] != "accepted" or kinds[-1] != "finish":
                raise AssertionError(f"stream {rid} did not finish: {evs[-3:]}")
            tokens = [d["token"] for k, d in evs if k == "token"]
            if len(tokens) != out or evs[-1][1]["n_tokens"] != out:
                raise AssertionError(f"stream {rid}: {len(tokens)} tokens, "
                                     f"wanted {out}")
            streams.append({"rid": rid, "prompt": toks, "tokens": tokens,
                            "arrival": float(evs[0][1]["arrival"])})
        status, text = fetch("127.0.0.1", port, "/metrics")
        samples = parse_prometheus(text)
        if status != 200 or not samples:
            raise AssertionError(f"/metrics: status {status}, "
                                 f"{len(samples)} samples")
    finally:
        phase = srv.shutdown(drain=True)
    if phase != "done":
        raise AssertionError(f"drain ended {phase!r}")
    stats = jax.devices()[0].memory_stats() or {}
    n_tok = sum(len(s["tokens"]) for s in streams)
    log("served", time.perf_counter() - t0, label="smoke-not-a-benchmark",
        arch=ARCH, kv_dtype=srv.backend.cache["k"].dtype,
        slots=config.num_slots,
        max_seq=config.max_seq, streams=len(streams), tokens=n_tok,
        build_s=f"{t_build:.3f}", compile_s=f"{t_compile:.3f}",
        wall_s=f"{t_wall:.3f}", metrics_samples=len(samples),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_in_use=stats.get("bytes_in_use", "not reported"), drain=phase)
    return srv, streams


def free_engine(engine) -> None:
    """Release the engine's device buffers now, so that a second engine
    never sits beside it on the chip."""
    import jax

    for leaf in jax.tree.leaves((engine.params, engine.cache)):
        leaf.delete()
    engine.params = engine.cache = None


# ------------------------------------------------------------- agreement
def agreement_phase(config, streams) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.request import Request
    from repro.server import build_engine

    t0 = time.perf_counter()
    _, eng = build_engine(dataclasses.replace(config, clock="virtual"))
    reqs = [Request(rid=s["rid"], arrival=s["arrival"],
                    prompt_len=len(s["prompt"]), output_len=len(s["tokens"]),
                    spec=config.default_spec,
                    prompt_tokens=np.asarray(s["prompt"], np.int32))
            for s in streams]
    eng.run(reqs)
    for s, r in zip(streams, reqs):
        got = [int(t) for t in r.output_tokens]
        if got != s["tokens"]:
            first = next(i for i, (a, b) in enumerate(zip(got, s["tokens"]))
                         if a != b) if len(got) == len(s["tokens"]) else -1
            raise AssertionError(
                f"stream {s['rid']}: virtual-clock tokens differ from the "
                f"wire at index {first}")
    t_tokens = time.perf_counter() - t0

    # decode-step logits against one full prefill at the same positions
    model, params = eng.model, eng.params
    st = streams[0]
    prompt = np.asarray(st["prompt"], np.int32)
    gen = np.asarray(st["tokens"][:LOGIT_POSITIONS], np.int32)
    n, k = len(prompt), len(gen)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(1, n + k, dtype=eng.cache["k"].dtype)
    _, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])}, cache)
    dec = []
    for t in gen:                  # logits at positions n .. n+k-1
        lg, cache = decode(params, jnp.asarray([t]), cache)
        dec.append(lg[0])
    dec = np.asarray(jnp.stack(dec), np.float32)
    seq = np.concatenate([prompt, gen])
    full, _ = prefill(params, {
        "tokens": jnp.asarray(np.tile(seq, (k, 1))),
        "lengths": jnp.asarray(n + 1 + np.arange(k), jnp.int32)},
        model.init_cache(k, n + k, dtype=eng.cache["k"].dtype))
    full = np.asarray(full, np.float32)
    if not (np.isfinite(dec).all() and np.isfinite(full).all()):
        raise AssertionError("non-finite logits")
    err = float(np.max(np.abs(dec - full)))
    scale = float(np.max(np.abs(full)))
    if err > LOGIT_REL_TOL * scale:
        raise AssertionError(f"decode vs prefill logits: max |diff| {err} > "
                             f"{LOGIT_REL_TOL} x max |logit| {scale}")
    same_top = int((dec.argmax(-1) == full.argmax(-1)).sum())
    log("agreement", time.perf_counter() - t0, streams=len(streams),
        tokens_identical="all", tokens_s=f"{t_tokens:.3f}",
        logit_positions=f"{n}..{n + k - 1}",
        logit_max_abs_diff=f"{err:.4g}", logit_max_abs=f"{scale:.4g}",
        rel_tol=LOGIT_REL_TOL, top1_agree=f"{same_top}/{k}")


# ------------------------------------------------------------------ main
def main() -> int:
    try:
        device = device_phase()
        import jax

        from repro.launch.compile_cache import enable_compile_cache
        from repro.server.app import FULL_MAX_SEQ, FULL_SLOTS, ServerConfig

        cache_dir = enable_compile_cache()
        hits = {"hits": 0, "misses": 0}

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                hits["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                hits["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        kernels_phase()
        config = ServerConfig(arch=ARCH, full=True, clock="wall",
                              num_slots=FULL_SLOTS, max_seq=FULL_MAX_SEQ)
        srv, streams = served_phase(config)
        free_engine(srv.backend)
        del srv
        agreement_phase(config, streams)
        log("compile-cache", 0.0, dir=cache_dir, **hits)
    except Exception:
        traceback.print_exc()
        print("chip smoke FAILED", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
