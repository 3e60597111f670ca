"""Mamba-1 selective scan as a Pallas TPU kernel.

The GPU Mamba kernel is a fused warp-level scan; the TPU analogue is a
*chunked* scan: the grid is (batch, d_inner blocks, seq chunks) with the
chunk axis innermost and sequential, and the recurrent state h
(block_d, N) lives in VMEM scratch, persisting across chunks. Each chunk's
inputs (x, dt, B, C tiles) are staged HBM→VMEM by BlockSpecs; within the
chunk the recurrence runs as a `fori_loop` over time steps on the VPU
(elementwise exp/mul/add) with the (block_d, N) state resident in VMEM —
there is no HBM traffic for h at all, which is the entire point of the
paper-adjacent Mamba "hardware-aware" scan, re-expressed for the TPU memory
hierarchy instead of CUDA shared memory.

block_d defaults to 512 lanes so the (block_d, N=16) state tile is
(512, 16) fp32 = 32 KiB — comfortably VMEM-resident alongside the chunk
tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _scan_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref,
    h_ref,
    *,
    chunk: int,
):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)                    # (block_d, N)
    d_skip = d_ref[...].astype(jnp.float32)               # (1, block_d)

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)          # (block_d,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)        # (block_d,)
        b_t = b_ref[0, t, :].astype(jnp.float32)          # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)          # (N,)
        da = jnp.exp(dt_t[:, None] * a)                   # (block_d, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1) + d_skip[0] * x_t
        o_ref[0, t, :] = y_t.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret")
)
def selective_scan(
    x: jax.Array,    # (B, S, D)
    dt: jax.Array,   # (B, S, D)
    A: jax.Array,    # (D, N)
    B: jax.Array,    # (B, S, N)
    C: jax.Array,    # (B, S, N)
    D: jax.Array,    # (D,)
    *,
    chunk: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, d = x.shape
    n = A.shape[1]
    chunk = min(chunk, s)
    block_d = min(block_d, d)
    assert s % chunk == 0, (s, chunk)
    assert d % block_d == 0, (d, block_d)

    grid = (bsz, d // block_d, s // chunk)
    kernel = functools.partial(_scan_kernel, chunk=chunk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, di, si: (b_, si, di)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, di, si: (b_, si, di)),
            pl.BlockSpec((block_d, n), lambda b_, di, si: (di, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, di, si: (b_, si, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, di, si: (b_, si, 0)),
            pl.BlockSpec((1, block_d), lambda b_, di, si: (0, di)),
        ],
        out_specs=pl.BlockSpec(
            (1, chunk, block_d), lambda b_, di, si: (b_, si, di)
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, B, C, D.reshape(1, d))

    return out
