"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel's test sweeps
shapes/dtypes and asserts allclose against the function here. They are also
the implementation used on non-TPU backends and for the multi-pod dry-run
(XLA lowers them natively, which is what ``cost_analysis`` should see).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating KV heads (GQA)."""
    b, s, kv, hd = k.shape
    if kv == num_q_heads:
        return k
    assert num_q_heads % kv == 0, (num_q_heads, kv)
    return jnp.repeat(k, num_q_heads // kv, axis=2)


def attention_ref(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    q_offset: Optional[jax.Array] = None,   # (B,) absolute pos of q[0]
    lengths: Optional[jax.Array] = None,    # (B,) valid kv length
    window: Optional[int] = None,           # sliding window size
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Reference multi-head attention with GQA, causality, per-request
    lengths and an optional sliding window. Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = sm_scale if sm_scale is not None else (hd ** -0.5)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale

    kv_pos = jnp.arange(sk)[None, None, None, :]                 # (1,1,1,Sk)
    if q_offset is None:
        q_pos = jnp.arange(sq)
        q_pos = q_pos[None, None, :, None] + jnp.zeros((b, 1, 1, 1), q_pos.dtype)
    else:
        q_pos = q_offset[:, None, None, None] + jnp.arange(sq)[None, None, :, None]
    mask = jnp.ones(logits.shape, dtype=bool)
    if causal:
        mask &= kv_pos <= q_pos
    if lengths is not None:
        mask &= kv_pos < lengths[:, None, None, None]
    if window is not None:
        mask &= kv_pos > q_pos - window
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def decode_attention_ref(
    q: jax.Array,            # (B, H, hd) — single new token per request
    k: jax.Array,            # (B, S, KV, hd) KV cache
    v: jax.Array,            # (B, S, KV, hd)
    lengths: jax.Array,      # (B,) tokens already in cache (incl. current)
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Single-step decode attention against a static-slot KV cache."""
    out = attention_ref(
        q[:, None],
        k,
        v,
        causal=False,
        lengths=lengths,
        q_offset=lengths - 1,
        window=window,
        sm_scale=sm_scale,
    )
    return out[:, 0]


def paged_decode_attention_ref(
    q: jax.Array,             # (B, H, hd) — single new token per request
    k_pool: jax.Array,        # (P, page, KV, hd) physical page pool
    v_pool: jax.Array,        # (P, page, KV, hd)
    block_tables: jax.Array,  # (B, max_pages) int32 page ids; >= P = sentinel
    lengths: jax.Array,       # (B,) tokens in cache (incl. current)
    *,
    window: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention over a physically paged KV pool, jnp oracle.

    Gathers each request's pages back into a contiguous (B, S', KV, hd)
    view (S' = max_pages * page) and defers to `decode_attention_ref`.
    Sentinel table entries are clamped before the gather; whatever rows
    they alias are masked out by `lengths` (a request's block table always
    covers ceil(length / page) real pages, so every attended position maps
    to a page the request owns). When S' equals the contiguous cache depth
    the result is bit-identical to `decode_attention_ref` on the
    equivalent contiguous cache: masked positions contribute exact zeros
    (exp(NEG_INF - m) underflows to 0.0) and the reduction shapes match —
    the degenerate-oracle engine differentials rely on this.
    """
    b = q.shape[0]
    p_total, page = k_pool.shape[0], k_pool.shape[1]
    bt = jnp.minimum(block_tables, p_total - 1)
    n_pages = bt.shape[1]
    k = k_pool[bt].reshape(b, n_pages * page, *k_pool.shape[2:])
    v = v_pool[bt].reshape(b, n_pages * page, *v_pool.shape[2:])
    return decode_attention_ref(
        q, k, v, lengths, window=window, sm_scale=sm_scale
    )


def selective_scan_ref(
    x: jax.Array,      # (B, S, D)   — D = d_inner
    dt: jax.Array,     # (B, S, D)   — softplus'd timestep
    A: jax.Array,      # (D, N)      — negative (continuous-time)
    B: jax.Array,      # (B, S, N)
    C: jax.Array,      # (B, S, N)
    D: jax.Array,      # (D,)
) -> jax.Array:
    """Mamba-1 selective scan, sequential oracle.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t;   y_t = C_t . h_t + D*x_t
    Returns (B, S, D).
    """
    bsz, s, d = x.shape
    n = A.shape[1]

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs            # (B,D) (B,D) (B,N) (B,N)
        dA = jnp.exp(dt_t[..., None] * A[None])             # (B, D, N)
        dBx = dt_t[..., None] * b_t[:, None, :] * x_t[..., None]
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, d, n), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
        jnp.moveaxis(B, 1, 0).astype(jnp.float32),
        jnp.moveaxis(C, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * D[None, None]
    return y.astype(x.dtype)


def selective_scan_step_ref(
    h: jax.Array,      # (B, D, N) carried state
    x: jax.Array,      # (B, D)
    dt: jax.Array,     # (B, D)
    A: jax.Array,      # (D, N)
    B: jax.Array,      # (B, N)
    C: jax.Array,      # (B, N)
    D: jax.Array,      # (D,)
):
    """One decode step of the Mamba-1 recurrence. Returns (h', y)."""
    dA = jnp.exp(dt[..., None] * A[None])
    h = dA * h + dt[..., None] * B[:, None, :] * x[..., None]
    y = jnp.einsum("bdn,bn->bd", h, C) + x * D[None]
    return h, y


def ssd_ref(
    x: jax.Array,      # (B, S, NH, HD)
    dt: jax.Array,     # (B, S, NH)  — softplus'd
    A: jax.Array,      # (NH,)       — negative scalar per head
    B: jax.Array,      # (B, S, N)
    C: jax.Array,      # (B, S, N)
    D: jax.Array,      # (NH,)
) -> jax.Array:
    """Mamba-2 state-space-dual recurrence, sequential oracle.

    Per head: h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T,
    y_t = h_t C_t + D x_t.   Returns (B, S, NH, HD).
    """
    bsz, s, nh, hd = x.shape
    n = B.shape[-1]

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs   # (B,NH,HD) (B,NH) (B,N) (B,N)
        da = jnp.exp(dt_t * A[None])                        # (B, NH)
        dbx = dt_t[..., None, None] * x_t[..., None] * b_t[:, None, None, :]
        h = da[..., None, None] * h + dbx                   # (B,NH,HD,N)
        y = jnp.einsum("bhdn,bn->bhd", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, nh, hd, n), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
        jnp.moveaxis(B, 1, 0).astype(jnp.float32),
        jnp.moveaxis(C, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype)
