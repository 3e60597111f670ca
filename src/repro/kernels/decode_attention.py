"""Flash-decode: single-token attention against a static-slot KV cache.

This is the serving hot loop the Andes scheduler drives — every decode
iteration of every running request lands here. TPU-native shape: queries for
one request are reshaped to (KV, G, hd) where G = q_heads / kv_heads, so the
per-tile contraction is (G, hd) x (hd, block_k) — the GQA group becomes the
MXU's M dimension rather than a HBM-side KV replication. The KV sequence is
the innermost, *sequential* grid axis; online-softmax state (acc, row max,
row sum) persists in VMEM scratch.

Per-request cache lengths arrive via scalar prefetch (SMEM) so tiles wholly
past a request's length are skipped before their DMA result is used —
continuous batching means lengths are ragged across the batch, and this is
where the "token-granular accounting" of the scheduler meets the kernel.

Sliding window (``window``) implements the long-context decode variant:
only the last `window` cache positions are attended, making decode cost
O(window) instead of O(context) — the sub-quadratic path used by the
``long_500k`` shape for attention archs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    lengths_ref,                 # SMEM (B,) int32 — scalar prefetch
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    window: Optional[int],
    block_k: int,
    num_k_blocks: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    k_start = ki * block_k
    live = k_start < length
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k > length - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)        # (G, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)        # (block_k, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # (G, block_k)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window is not None:
            mask &= k_pos > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "sm_scale", "block_k", "interpret"),
)
def decode_attention(
    q: jax.Array,          # (B, H, hd) — one new token per request
    k: jax.Array,          # (B, S, KV, hd)
    v: jax.Array,          # (B, S, KV, hd)
    lengths: jax.Array,    # (B,) int32 — valid cache length incl. current tok
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, hd = q.shape
    _, s, kv, _ = k.shape
    assert h % kv == 0
    group = h // kv
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    block_k = min(block_k, s)
    pad_k = (-s) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    s_p = s + pad_k
    num_k_blocks = s_p // block_k

    # (B, H, hd) -> (B, KV, G, hd); (B, S, KV, hd) -> (B, KV, S, hd)
    qg = q.reshape(b, kv, group, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, kv, num_k_blocks)
    kernel = functools.partial(
        _decode_kernel,
        sm_scale=scale,
        window=window,
        block_k=block_k,
        num_k_blocks=num_k_blocks,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, hd), lambda b_, kv_, ki, *_: (b_, kv_, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda b_, kv_, ki, *_: (b_, kv_, ki, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda b_, kv_, ki, *_: (b_, kv_, ki, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, hd), lambda b_, kv_, ki, *_: (b_, kv_, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group, hd), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)

    return out.reshape(b, h, hd)
