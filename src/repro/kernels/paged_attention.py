"""Paged flash-decode: single-token attention over a physical page pool.

The physically paged counterpart of ``decode_attention.py``: the KV cache
is no longer one contiguous row per batch slot but a shared pool of
fixed-size pages, ``k_pool``/``v_pool`` of shape (P, page, KV, hd), and
each request's context is scattered across the pages its **block table**
names (ordered: table entry ``i`` holds absolute positions
``[i*page, (i+1)*page)``). This is what makes token-granular preemption
cheap — ``evict_tail`` frees real HBM rows, and admission capacity is the
physical pool — at the price of one indirection on the decode hot path.

That indirection is exactly one extra scalar-prefetch input. The grid and
the online-softmax body are identical to the contiguous kernel (which is
reused verbatim); the only change is the k/v BlockSpec index map, which
reads the block table from SMEM and DMAs tile ``ki`` of request ``b``
from pool page ``block_tables[b, ki]`` instead of from row offset
``ki * block_k``. Scalar prefetch puts the table in SMEM *before* the
grid runs, so the gather is resolved at DMA-issue time — no gather op in
the dataflow, just data-dependent tile addressing.

Sentinel entries (ids >= P, marking pages past a request's allocation)
are clamped in the index map; tiles wholly past ``length`` are dead
(``k_start < length`` fails, same skip as the contiguous kernel) so a
clamped DMA's payload is never read. ``block_k`` IS the page size here —
pages are the DMA granularity by construction. For production TPU shapes
the page size should be a multiple of the dtype's sublane tile (8 for
f32, 16 for bf16); tests run tiny pages in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _decode_kernel


def _paged_decode_kernel(
    lengths_ref,                 # SMEM (B,) int32 — scalar prefetch
    bt_ref,                      # SMEM (B, max_pages) int32 — scalar prefetch
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    window: Optional[int],
    page_size: int,
    num_pages: int,
):
    # the block table is consumed entirely by the k/v index maps; the
    # compute body is the contiguous online-softmax kernel unchanged
    # (k_start = ki * page_size lines up because tables are ordered)
    del bt_ref
    _decode_kernel(
        lengths_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
        sm_scale=sm_scale, window=window, block_k=page_size,
        num_k_blocks=num_pages,
    )


@functools.partial(
    jax.jit,
    static_argnames=("window", "sm_scale", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,             # (B, H, hd) — one new token per request
    k_pool: jax.Array,        # (P, page, KV, hd) physical page pool
    v_pool: jax.Array,        # (P, page, KV, hd)
    block_tables: jax.Array,  # (B, max_pages) int32; entries >= P = sentinel
    lengths: jax.Array,       # (B,) int32 — valid context incl. current tok
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, hd = q.shape
    p_total, page, kv, _ = k_pool.shape
    assert h % kv == 0
    group = h // kv
    max_pages = block_tables.shape[1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    # (B, H, hd) -> (B, KV, G, hd); (P, page, KV, hd) -> (P, KV, page, hd)
    qg = q.reshape(b, kv, group, hd)
    kt = k_pool.transpose(0, 2, 1, 3)
    vt = v_pool.transpose(0, 2, 1, 3)

    grid = (b, kv, max_pages)
    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=scale,
        window=window,
        page_size=page,
        num_pages=max_pages,
    )

    def kv_map(b_, kv_, ki, len_ref, bt_ref):
        del len_ref
        # data-dependent tile address: the ki-th page of request b_.
        # Clamp sentinels (>= P) — those tiles are dead (k_start >= length)
        # so the aliased payload is never read, but the DMA must be legal.
        return (jnp.minimum(bt_ref[b_, ki], p_total - 1), kv_, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, hd), lambda b_, kv_, ki, *_: (b_, kv_, 0, 0)
                ),
                pl.BlockSpec((1, 1, page, hd), kv_map),
                pl.BlockSpec((1, 1, page, hd), kv_map),
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
            # pages of one request chain through the online softmax, so the
            # page axis is sequential; batch and kv heads stay parallel
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        qg,
        kt,
        vt,
    )

    return out.reshape(b, h, hd)
