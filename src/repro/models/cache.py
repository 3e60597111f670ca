"""Decode caches: static-slot KV cache + SSM recurrent state.

A single pytree carries everything the decode step needs:

  length    (B,)                         valid context tokens per slot
  k, v      (L_attn, B, S_max, KV, hd)   attention archs (L_attn = number of
                                         attention *applications*: for the
                                         shared-block hybrids, rounds or
                                         hybrid layer ids; Zamba2's hybrid
                                         ids keep (.., S_max, KV * hd))
  ssm_h     (L_ssm, B, ...)              Mamba scan state (f32): Mamba-1
                                         (di, N), Mamba-2 (HD, N, NH)
  ssm_conv  (L_ssm, B, K-1, conv_dim)    Mamba conv lookback
  cross_k/v (L_dec, B, S_enc, KV, hd)    enc-dec cross-attention memory
  enc_length (B,)                        valid encoder positions

Static shapes are deliberate (TPU/XLA); token-granular *accounting* for the
scheduler happens in serving/kv_manager.py, not here. See DESIGN.md §3.

Paged KV comes in two depths. PR 8's *accounting-only* paging keeps the
contiguous layout above: pages and block tables are host-side constructs
in `KVSlotManager` that give the scheduler page-granular capacity
arithmetic, while each request still owns one fixed-depth device row.
*Physical* paging (`init_paged_cache`) makes the device see pages too:
`k`/`v` become a shared pool of fixed-size pages,

  k, v          (L_attn, P, page, KV, hd)   P = physical pool size
  block_tables  (B, max_pages)  i32         page ids per slot, ordered;
                                            entries >= P are sentinels

and a slot's context lives scattered across the pages its block-table
row names (entry ``i`` covers absolute positions [i*page, (i+1)*page)).
Decode writes land at (block_tables[b, length//page], length % page) via
`paged_write_tokens`; attention gathers through the table (the pallas
paged kernel resolves it at DMA-issue time). Now `evict_tail` and
release free real HBM rows and admission capacity IS the physical pool —
token-granular preemption moves memory, not just ledger entries. Every
jitted shape stays static: the pool, the table width, and `length` are
fixed; only table *values* change, uploaded by the engine when the
manager's tables move. `length` stays the single validity gate in both
layouts: chunked prefill commits a growing prefix (page by page when
physical) and re-pins `length` at each chunk, so a partially-prefilled
slot is always a valid context prefix to attention, and positions beyond
`length` — including whole sentinel-mapped pages — are never attended.

Speculative-decoding rollback contract (`with_lengths`): for attention
caches, `length` alone defines validity — attention never reads past it,
and decode/verify writes always land at the current `length`, so entries a
rejected proposal left beyond the accepted frontier are first overwritten
before they could ever be attended. Rolling back a speculation is therefore
just re-pinning `length` to the committed context; no KV movement. (SSM
recurrent state has no such positional gate — state at the accepted
position would need checkpointing — which is why the speculative engine is
restricted to attention-only architectures, see serving/speculative.py.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    *,
    enc_seq: int = 0,
    dtype=jnp.bfloat16,
    abstract: bool = False,
    kv_repeat: int = 1,
):
    """Build (or shape-describe, if abstract=True) a decode cache."""

    def arr(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    cache = {"length": arr((batch,), jnp.int32)}
    kv, hd = cfg.num_kv_heads * kv_repeat, cfg.head_dim

    n_attn = _num_attn_applications(cfg)
    if n_attn:
        # Zamba2 keeps each position's heads as one minor axis
        # (`attention.attn_decode_at`)
        heads = (kv * hd,) if cfg.hybrid_layer_ids else (kv, hd)
        cache["k"] = arr((n_attn, batch, max_seq) + heads, dtype)
        cache["v"] = arr((n_attn, batch, max_seq) + heads, dtype)

    n_ssm = len(cfg.ssm_layer_ids())
    if n_ssm:
        s = cfg.ssm
        di = cfg.d_inner
        if s.version == 2:
            nh = di // s.headdim
            cache["ssm_h"] = arr((n_ssm, batch, s.headdim, s.d_state, nh),
                                 jnp.float32)
            conv_dim = cfg.mamba2_conv_dim
        else:
            cache["ssm_h"] = arr((n_ssm, batch, di, s.d_state), jnp.float32)
            conv_dim = di
        cache["ssm_conv"] = arr((n_ssm, batch, s.d_conv - 1, conv_dim), dtype)

    if cfg.kind in ("encdec", "audio"):
        cache["cross_k"] = arr((cfg.num_layers, batch, enc_seq, kv, hd), dtype)
        cache["cross_v"] = arr((cfg.num_layers, batch, enc_seq, kv, hd), dtype)
        cache["enc_length"] = arr((batch,), jnp.int32)

    return cache


def supports_physical_paging(cfg: ModelConfig) -> bool:
    """Physical paging covers archs whose decode state is pure
    length-gated self-attention KV: recurrent state (ssm/hybrid) has no
    positional gate to page against, and encoder memory (encdec/audio)
    is a second, un-paged cache. Those run accounting-only paging."""
    return cfg.kind in ("dense", "vlm", "moe")


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    num_pages: int,
    page_size: int,
    max_seq: int,
    *,
    dtype=jnp.bfloat16,
    abstract: bool = False,
    kv_repeat: int = 1,
):
    """Build a physically paged decode cache (module docstring layout).

    `num_pages` is the physical pool size (admission capacity); sentinel
    table entries equal `num_pages` so unallocated writes drop and
    unallocated gathers clamp into masked territory."""
    assert supports_physical_paging(cfg), cfg.kind
    assert 0 < page_size, page_size

    def arr(shape, dt, fill=0):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        if fill:
            return jnp.full(shape, fill, dt)
        return jnp.zeros(shape, dt)

    kv, hd = cfg.num_kv_heads * kv_repeat, cfg.head_dim
    n_attn = _num_attn_applications(cfg)
    max_pages = -(-max_seq // page_size)
    return {
        "length": arr((batch,), jnp.int32),
        "k": arr((n_attn, num_pages, page_size, kv, hd), dtype),
        "v": arr((n_attn, num_pages, page_size, kv, hd), dtype),
        "block_tables": arr((batch, max_pages), jnp.int32, num_pages),
    }


def is_paged(cache) -> bool:
    """Static layout predicate: pytree structure, not data, decides the
    decode routing (a jitted step traces one branch per cache layout)."""
    return "block_tables" in cache


def paged_write_tokens(pool, block_tables, starts, seg, counts):
    """Scatter a contiguous token segment into the page pool.

    pool (n_attn, P, page, KV, hd); block_tables (B, max_pages);
    seg (n_attn, B, n, KV, hd) holds `counts[b]` valid tokens per slot,
    landing at absolute positions starts[b] .. starts[b]+counts[b].
    Positions beyond `counts` or past the table width are routed to the
    sentinel id and dropped by the scatter — a slot can never write a
    page it does not own. Returns the updated pool."""
    p_total, page = pool.shape[1], pool.shape[2]
    n = seg.shape[2]
    max_pages = block_tables.shape[1]
    pos = starts[:, None] + jnp.arange(n)[None]              # (B, n)
    pg_idx = pos // page
    pid = jnp.take_along_axis(
        block_tables, jnp.minimum(pg_idx, max_pages - 1), axis=1)
    valid = (jnp.arange(n)[None] < counts[:, None]) & (pg_idx < max_pages)
    pid = jnp.where(valid, pid, p_total)                     # -> dropped
    off = pos % page
    return pool.at[:, pid, off].set(seg.astype(pool.dtype), mode="drop")


def paged_gather_rows(pool, table_rows, max_seq):
    """Rebuild contiguous cache rows from the pool.

    pool (n_attn, P, page, KV, hd); table_rows (B, max_pages) ->
    (n_attn, B, max_seq, KV, hd). Sentinels clamp to an arbitrary pool
    page; callers only read positions < length (swap-out stores whole
    rows, but restore + attention re-mask by length, same as the stale
    tail of a contiguous row)."""
    p_total, page = pool.shape[1], pool.shape[2]
    rows = pool[:, jnp.minimum(table_rows, p_total - 1)]
    # (n_attn, B, max_pages, page, KV, hd) -> (n_attn, B, S', KV, hd)
    flat = rows.reshape(rows.shape[0], rows.shape[1], -1, *rows.shape[4:])
    return flat[:, :, :max_seq]


def with_block_tables(cache, tables):
    """Re-pin the device block tables (pure). The engine calls this when
    the KV manager's tables moved (allocate/grow/evict/release) — table
    VALUES are data, so no recompilation."""
    return dict(cache, block_tables=jnp.asarray(tables, jnp.int32))


def with_lengths(cache, lengths):
    """Re-pin the per-slot valid-context lengths (pure: returns a new dict).

    The serving engine calls this before every decode/verify iteration with
    each slot's committed context length — which is also the whole
    speculative-decoding rollback path (see module docstring)."""
    return dict(cache, length=jnp.asarray(lengths, jnp.int32))


def supports_length_rollback(cfg: ModelConfig) -> bool:
    """True when `length` alone defines cache validity, so decoding PAST a
    point and then re-pinning `length` is a complete rollback (module
    docstring: attention never reads beyond `length`, and the next write
    lands on the first stale position).

    This predicate gates every speculative execution strategy in the
    serving layer: the spec-decoding verify window (serving/speculative.py)
    and the engine's multi-step decode overshoot under EOS (the scan may
    compute iterations past an end-of-sequence token; committing stops at
    the EOS and `with_lengths` discards the rest). SSM/recurrent state has
    no positional gate — state at the rollback point would need per-position
    checkpointing — so those archs must never overshoot."""
    return cfg.kind not in ("ssm", "hybrid")


def _num_attn_applications(cfg: ModelConfig) -> int:
    if cfg.kind == "ssm":
        return 0
    if cfg.hybrid_layer_ids:
        return len(cfg.hybrid_layer_ids)
    if cfg.hybrid_attn_every:
        return cfg.num_layers // cfg.hybrid_attn_every
    return cfg.num_layers


def cache_bytes(cfg: ModelConfig, batch: int, max_seq: int, dtype_bytes=2) -> int:
    """Host-side size estimate (used by the KV manager and roofline)."""
    total = 0
    n_attn = _num_attn_applications(cfg)
    total += 2 * n_attn * batch * max_seq * cfg.num_kv_heads * cfg.head_dim * dtype_bytes
    total += batch * cfg.ssm_state_bytes()
    return total
