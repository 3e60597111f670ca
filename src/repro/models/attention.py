"""Attention blocks (GQA, optional QKV bias, RoPE, sliding window).

Weight layout per (stacked) layer:
  wq (din, H*hd), wk (din, KV*hd), wv (din, KV*hd), wo (H*hd, d)
  (din = cfg.d_attn_in: d, or 2d for Zamba2's concat(hidden, embedding);
  hd = cfg.head_dim, which need not be d / H)
  [bq (H*hd,), bk, bv when qkv_bias]

Three entry points:
  - ``attn_train``:   full-sequence self-attention (causal or not)
  - ``attn_prefill``: same math, also returns the k/v planes for the cache
  - ``attn_decode``:  one token against a static-slot cache (+ cache write)
KV heads are replicated up to the model-parallel degree at *sharding* time,
not here (see distributed/sharding.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels import ref
from repro.models.layers import normal, rope


def init_attn(rng, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    din = cfg.d_attn_in
    ks = jax.random.split(rng, 4)
    p = {
        "wq": normal(ks[0], (din, h * hd), dtype=dtype),
        "wk": normal(ks[1], (din, kv * hd), dtype=dtype),
        "wv": normal(ks[2], (din, kv * hd), dtype=dtype),
        "wo": normal(ks[3], (h * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _qkv(p, x, cfg: ModelConfig, positions, *, apply_rope=True, kv_repeat=1):
    b = x.shape[0]
    s = x.shape[1]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if kv_repeat > 1:
        # KV-head replication up to the TP degree (hillclimb #1,
        # EXPERIMENTS.md §Perf): keeps the cache write and the attention
        # reads fully local to each model shard at the cost of
        # kv_repeat x KV memory. GQA semantics unchanged: q head h maps to
        # repeated head (h // (H/kv))*r + j for any j, same values.
        k = jnp.repeat(k, kv_repeat, axis=2)
        v = jnp.repeat(v, kv_repeat, axis=2)
    if apply_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_train(
    p, x, cfg: ModelConfig, *,
    positions=None,
    causal: bool = True,
    window: Optional[int] = None,
    lengths=None,
    impl: str = "ref",
    kv_repeat: int = 1,
):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(p, x, cfg, positions, apply_rope=(cfg.kind != "audio"),
                   kv_repeat=kv_repeat)
    o = ops.attention(
        q, k, v, causal=causal, window=window, lengths=lengths, impl=impl,
        sm_scale=cfg.attn_scale,
    )
    return o.reshape(b, s, -1) @ p["wo"]


def attn_prefill(
    p, x, cfg: ModelConfig, *,
    positions=None,
    window: Optional[int] = None,
    lengths=None,
    impl: str = "ref",
    kv_repeat: int = 1,
):
    """Causal self-attention that also returns k/v for cache insertion."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(p, x, cfg, positions, apply_rope=(cfg.kind != "audio"),
                   kv_repeat=kv_repeat)
    o = ops.attention(
        q, k, v, causal=True, window=window, lengths=lengths, impl=impl,
        sm_scale=cfg.attn_scale,
    )
    return o.reshape(b, s, -1) @ p["wo"], k, v


def attn_decode(
    p, x_tok, k_cache, v_cache, lengths, cfg: ModelConfig, *,
    window: Optional[int] = None,
    impl: str = "ref",
    kv_repeat: int = 1,
):
    """One-token decode.

    x_tok (B, d); k_cache/v_cache (B, S, KV, hd) hold `lengths` (B,) valid
    tokens. Writes the new k/v at position `lengths`, attends over
    lengths+1 tokens. Returns (out (B, d), k_cache', v_cache')."""
    b, d = x_tok.shape
    x = x_tok[:, None, :]
    pos = lengths[:, None]                                     # (B, 1)
    q, k_new, v_new = _qkv(p, x, cfg, pos, apply_rope=(cfg.kind != "audio"),
                           kv_repeat=kv_repeat)

    def write(cache, new):
        # cache (B, S, KV, hd), new (B, 1, KV, hd) at per-request position
        def upd(c, n, i):
            return jax.lax.dynamic_update_slice(c, n, (i, 0, 0))
        return jax.vmap(upd)(cache, new, lengths)

    k_cache = write(k_cache, k_new.astype(k_cache.dtype))
    v_cache = write(v_cache, v_new.astype(v_cache.dtype))
    o = ops.decode_attention(
        q[:, 0], k_cache, v_cache, lengths + 1, window=window, impl=impl,
        sm_scale=cfg.attn_scale,
    )
    return o.reshape(b, -1) @ p["wo"], k_cache, v_cache


def attn_decode_at(
    p, x_tok, k_all, v_all, entry: int, lengths, cfg: ModelConfig, *,
    window: Optional[int] = None,
    impl: str = "ref",
    kv_repeat: int = 1,
):
    """`attn_decode` against entry `entry` of stacked caches k_all/v_all
    (E, B, S, KV * hd), heads flattened into one minor axis: the new k/v
    are written into the stacked arrays themselves, so no entry is sliced
    out and written back whole. Returns (out (B, d), k_all', v_all')."""
    b, d = x_tok.shape
    pos = lengths[:, None]                                     # (B, 1)
    q, k_new, v_new = _qkv(p, x_tok[:, None, :], cfg, pos,
                           apply_rope=(cfg.kind != "audio"),
                           kv_repeat=kv_repeat)
    rows = jnp.arange(b)
    k_all = k_all.at[entry, rows, lengths].set(
        k_new.reshape(b, -1).astype(k_all.dtype))
    v_all = v_all.at[entry, rows, lengths].set(
        v_new.reshape(b, -1).astype(v_all.dtype))
    o = _flat_decode_attention(q[:, 0], k_all[entry], v_all[entry],
                               lengths + 1, window=window,
                               sm_scale=cfg.attn_scale)
    return o.reshape(b, -1) @ p["wo"], k_all, v_all


def _flat_decode_attention(q, k, v, lengths, *, window=None, sm_scale=None):
    """`decode_attention_ref` over caches whose heads share one minor axis,
    k/v (B, S, KV * hd), as two plain matmuls that read each cache row as
    it is stored: q laid block-diagonally over the heads' columns gives
    every head's logits at once, and each head keeps its own block of the
    probabilities times V. q (B, H, hd) -> (B, H, hd)."""
    b, h, hd = q.shape
    kvh = k.shape[-1] // hd
    col = jnp.arange(kvh * hd)
    own = (col[:, None] // hd) == (jnp.arange(h)[None, :] // (h // kvh))
    qb = jnp.where(own[None], jnp.swapaxes(q[:, :, col % hd], 1, 2), 0)
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    logits = jnp.einsum("bsj,bjh->bhs", k, qb).astype(jnp.float32) * scale
    pos = jnp.arange(k.shape[1])[None, None, :]
    mask = pos < lengths[:, None, None]
    if window is not None:
        mask &= pos > (lengths - 1)[:, None, None] - window
    probs = jax.nn.softmax(jnp.where(mask, logits, ref.NEG_INF), axis=-1)
    o = jnp.einsum("bhs,bsj->bhj", probs.astype(v.dtype), v)
    o = o.reshape(b, h, kvh, hd)
    return jnp.take_along_axis(
        o, (jnp.arange(h) // (h // kvh))[None, :, None, None], axis=2)[:, :, 0]


def attn_decode_paged(
    p, x_tok, k_pool, v_pool, block_tables, lengths, cfg: ModelConfig, *,
    window: Optional[int] = None,
    impl: str = "ref",
    kv_repeat: int = 1,
):
    """One-token decode against a physically paged KV pool.

    x_tok (B, d); k_pool/v_pool (P, page, KV, hd) shared across slots;
    block_tables (B, max_pages) int32 names each slot's pages in order
    (entries >= P are sentinels). Writes the new k/v at page
    block_tables[b, lengths[b] // page], offset lengths[b] % page — the
    paged image of `attn_decode`'s row write — then attends through the
    table. Sentinel-targeted writes drop (a slot never touches pages it
    does not own) and a clamped page index past the table width resolves
    to the slot's own last entry, mirroring the dynamic_update_slice
    clamp of the contiguous path. Returns (out (B, d), k_pool', v_pool')."""
    b, d = x_tok.shape
    x = x_tok[:, None, :]
    pos = lengths[:, None]                                     # (B, 1)
    q, k_new, v_new = _qkv(p, x, cfg, pos, apply_rope=(cfg.kind != "audio"),
                           kv_repeat=kv_repeat)

    p_total, page = k_pool.shape[0], k_pool.shape[1]
    max_pages = block_tables.shape[1]
    pg_idx = jnp.minimum(lengths // page, max_pages - 1)
    pid = jnp.take_along_axis(block_tables, pg_idx[:, None], axis=1)[:, 0]
    off = lengths % page
    k_pool = k_pool.at[pid, off].set(
        k_new[:, 0].astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[pid, off].set(
        v_new[:, 0].astype(v_pool.dtype), mode="drop")
    o = ops.paged_decode_attention(
        q[:, 0], k_pool, v_pool, block_tables, lengths + 1,
        window=window, impl=impl, sm_scale=cfg.attn_scale,
    )
    return o.reshape(b, -1) @ p["wo"], k_pool, v_pool


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_attn_kv(p, enc_out, cfg: ModelConfig):
    """Precompute cross-attention k/v from encoder output (no RoPE)."""
    b, s, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(b, s, kv, hd)
    v = (enc_out @ p["wv"]).reshape(b, s, kv, hd)
    return k, v


def cross_attn_apply(p, x, k, v, enc_lengths, cfg: ModelConfig, *, impl="ref"):
    """x (B, Sq, d) attends over encoder memory k/v (B, Se, KV, hd)."""
    b, sq, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, sq, h, hd)
    o = ops.attention(
        q, k, v, causal=False, lengths=enc_lengths, impl=impl
    )
    return o.reshape(b, sq, -1) @ p["wo"]
