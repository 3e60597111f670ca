"""Flash attention (prefill) as a Pallas TPU kernel.

TPU-native design notes (vs. the CUDA flash-attention the paper's baselines
use): the grid is (batch, q_heads, q_blocks, k_blocks) with the k-block axis
innermost and *sequential* ("arbitrary" dimension semantics); the online
softmax accumulator, row max and row sum live in VMEM scratch and persist
across the k-block axis. Block shapes default to (128, 128) so the
q·kᵀ and p·v contractions are MXU-shaped (128-aligned), and all tiles are
explicitly staged HBM→VMEM by BlockSpecs. GQA is handled in the k/v
index_map (query head h reads kv head h // group) so KV tiles are fetched
once per group, not repeated in HBM.

Causal + sliding-window masking is positional (iota within the tile);
fully-masked tiles are skipped with ``pl.when`` so the sequential k-axis
does no work above the diagonal.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    kv_len: int,
    num_k_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # A tile is live unless (a) causal and fully above the diagonal, or
    # (b) sliding window and fully left of every query's window.
    live = k_start < kv_len
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)        # (block_q, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)        # (block_k, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # (block_q, block_k)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (block_q, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> zeros
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "sm_scale", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,   # (B, Sq, H, hd)
    k: jax.Array,   # (B, Sk, KV, hd)
    v: jax.Array,   # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0
    group = h // kv
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k

    # (B, S, H, hd) -> (B, H, S, hd) so tiles are (seq, hd) planes
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    num_q_blocks = sq_p // block_q
    num_k_blocks = sk_p // block_k
    grid = (b, h, num_q_blocks, num_k_blocks)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        kv_len=sk,
        num_k_blocks=num_k_blocks,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)

    out = out.transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :sq]
    return out
