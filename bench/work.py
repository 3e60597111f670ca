"""Work counted from shapes: operations and bytes of the model's steps.

Everything here reads a configuration file's "model" block, never the
program, so any implementation of the same work is credited the same.
Counts are per token (FLOPs), per decode iteration and per flush of
prefills (FLOPs and bytes that the step cannot avoid: every weight once,
the live KV of each row, and each row's recurrent state in and out).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def shapes(m: dict, kind: str) -> Dict[str, int]:
    """Sizes the counts need, from the model kind's own `work_shapes`
    (bench/reference/<kind>.py)."""
    import importlib

    mod = importlib.import_module(f"bench.reference.{kind}")
    s = dict(mod.work_shapes(m))
    s.setdefault("state_bytes", 0)
    s.setdefault("scan_flops", 0)
    return s


def param_count(m: dict, kind: str) -> int:
    s = shapes(m, kind)
    return s["layer_params"] + s["embed_params"] + s["head_params"] + s["d"]


def weight_bytes(m: dict, kind: str) -> int:
    return param_count(m, kind) * BF16


def kv_bytes_per_token(m: dict, kind: str) -> int:
    s = shapes(m, kind)
    return 2 * s["attn_apps"] * s["kv_heads"] * s["head_dim"] * BF16


def flops_per_token(m: dict, kind: str, ctx: int) -> float:
    """Forward FLOPs of one token that attends over `ctx` positions."""
    s = shapes(m, kind)
    attn = 4 * s["attn_apps"] * s["heads"] * s["head_dim"] * ctx
    return 2.0 * s["matmul_params"] + attn + s["scan_flops"]


def prefill_flops(m: dict, kind: str, n: int) -> float:
    """A causal prompt of n tokens: every token through every layer
    (token i attends over i + 1 positions), and the vocabulary's head for
    the last token alone, the one whose logits choose the first token."""
    s = shapes(m, kind)
    attn = 4 * s["attn_apps"] * s["heads"] * s["head_dim"] * n * (n + 1) / 2
    head = 2.0 * s["vocab"]
    return (n * (2.0 * (s["matmul_params"] - s["vocab"]) + s["scan_flops"])
            + head + attn)


def prefill_flush(m: dict, kind: str, calls: int,
                  prompts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one flush of prefills: `calls` device calls (one
    per length bucket) over these prompt lengths. Bytes: every weight once
    per call, each prompt's KV written once, each row's recurrent state
    written once."""
    prompts = list(prompts)
    flops = sum(prefill_flops(m, kind, n) for n in prompts)
    s = shapes(m, kind)
    byts = (calls * weight_bytes(m, kind)
            + kv_bytes_per_token(m, kind) * sum(prompts)
            + len(prompts) * s["state_bytes"])
    return flops, float(byts)


def decode_iteration(m: dict, kind: str, rows: int,
                     ctx_sum: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode iteration needs for `rows` live rows whose
    caches held `ctx_sum` positions in all before the step. Bytes: every
    weight once (an untied embedding only for the rows' lookups), each
    row's KV over its context plus the new position, written once, and
    its recurrent state read and written."""
    s = shapes(m, kind)
    kv_tok = kv_bytes_per_token(m, kind)
    flops = (rows * (2.0 * s["matmul_params"] + s["scan_flops"])
             + 4 * s["attn_apps"] * s["heads"] * s["head_dim"]
             * (ctx_sum + rows))
    weights = weight_bytes(m, kind)
    if s["head_params"]:
        weights -= (s["embed_params"] - rows * s["d"]) * BF16
    byts = (weights + kv_tok * (ctx_sum + rows) + kv_tok * rows
            + 2 * rows * s["state_bytes"])
    return flops, float(byts)


def least_seconds(iterations: Iterable[Tuple[int, int]], m: dict, kind: str,
                  peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take for these decode iterations:
    per iteration the larger of FLOPs over peak and bytes over bandwidth."""
    total = 0.0
    for rows, ctx in iterations:
        f, b = decode_iteration(m, kind, rows, ctx)
        total += max(f / peak_flops, b / peak_bw)
    return total
