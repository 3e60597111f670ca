"""Dispatch wrappers over the Pallas kernels and their XLA references.

Model code calls these entry points with an ``impl`` string:

- ``"ref"``     — pure-jnp oracle (XLA-lowered). Used on CPU, in the multi-pod
                  dry-run (cost_analysis sees native HLO), and as ground truth.
- ``"pallas"``  — the Pallas TPU kernel. On a CPU backend it runs in
                  interpret mode automatically (correctness path for tests);
                  any backend other than CPU or TPU is refused.
- ``"chunked"`` — (scans only) chunked associative-scan in pure XLA: the
                  compile-friendly parallel form used for training/prefill at
                  scale; validated against the sequential oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _paged
from repro.kernels import selective_scan as _ss

DEFAULT_IMPL = "ref"


def _interpret() -> bool:
    """Pallas interpret mode on the CPU (the tests' correctness path);
    compiled kernels on a TPU; any other backend has no kernels here."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernels for backend {backend!r}")


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention(
    q, k, v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    lengths=None,
    q_offset=None,
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
):
    """Prefill/train attention. q (B,Sq,H,hd), k/v (B,Sk,KV,hd)."""
    if impl == "pallas" and lengths is None and q_offset is None:
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale,
            interpret=_interpret(),
        )
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, lengths=lengths,
        q_offset=q_offset, sm_scale=sm_scale,
    )


def decode_attention(
    q, k, v, lengths,
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
):
    """Single-token decode attention. q (B,H,hd), k/v (B,S,KV,hd)."""
    if impl == "pallas":
        return _dec.decode_attention(
            q, k, v, lengths, window=window, sm_scale=sm_scale,
            interpret=_interpret(),
        )
    return _ref.decode_attention_ref(
        q, k, v, lengths, window=window, sm_scale=sm_scale
    )


def paged_decode_attention(
    q, k_pool, v_pool, block_tables, lengths,
    *,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
):
    """Single-token decode attention over a physical KV page pool.

    q (B,H,hd); k_pool/v_pool (P,page,KV,hd); block_tables (B,max_pages)
    int32 (entries >= P are sentinels past a request's allocation)."""
    if impl == "pallas":
        return _paged.paged_decode_attention(
            q, k_pool, v_pool, block_tables, lengths,
            window=window, sm_scale=sm_scale, interpret=_interpret(),
        )
    return _ref.paged_decode_attention_ref(
        q, k_pool, v_pool, block_tables, lengths,
        window=window, sm_scale=sm_scale,
    )


# ---------------------------------------------------------------------------
# Mamba-1 selective scan
# ---------------------------------------------------------------------------

def selective_scan(x, dt, A, B, C, D, *, impl: str = DEFAULT_IMPL, chunk: int = 128):
    if impl == "pallas":
        bd = 512
        d = x.shape[-1]
        while d % bd:
            bd //= 2
        ch = chunk
        while x.shape[1] % ch:
            ch //= 2
        return _ss.selective_scan(
            x, dt, A, B, C, D, chunk=ch, block_d=bd, interpret=_interpret()
        )
    if impl == "chunked":
        return _selective_scan_chunked(x, dt, A, B, C, D, chunk=chunk)
    return _ref.selective_scan_ref(x, dt, A, B, C, D)


def _selective_scan_chunked(x, dt, A, B, C, D, *, chunk: int = 128):
    """Chunked associative formulation in pure XLA.

    Within a chunk the linear recurrence h_t = a_t h_{t-1} + b_t is solved
    with `lax.associative_scan` (log-depth, vectorizes on the VPU); chunks
    are chained with a `lax.scan` carrying only the (B, D, N) boundary state.
    Peak intermediate is (B, chunk, D, N) instead of (B, S, D, N).
    """
    bsz, s, d = x.shape
    n = A.shape[1]
    while s % chunk:
        chunk //= 2
    nchunks = s // chunk

    def to_chunks(t):  # (B, S, ...) -> (nchunks, B, chunk, ...)
        return jnp.moveaxis(
            t.reshape(bsz, nchunks, chunk, *t.shape[2:]), 1, 0
        )

    xc, dtc, bc, cc = map(to_chunks, (x, dt, B, C))

    def chunk_step(h0, inputs):
        xk, dtk, bk, ck = inputs                       # (B, chunk, ...)
        dtk = dtk.astype(jnp.float32)
        da = jnp.exp(dtk[..., None] * A[None, None])   # (B, chunk, D, N)
        dbx = (dtk * xk.astype(jnp.float32))[..., None] * bk[:, :, None, :]
        # prepend carry as step 0 with a == 1? fold via first element:
        dbx = dbx.at[:, 0].add(da[:, 0] * h0)
        aa, bb = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], l[1] * r[0] + r[1]),
            (da, dbx), axis=1,
        )
        h_last = bb[:, -1]
        yk = jnp.einsum("bcdn,bcn->bcd", bb, ck.astype(jnp.float32))
        return h_last, yk

    h0 = jnp.zeros((bsz, d, n), jnp.float32)
    _, ys = jax.lax.scan(chunk_step, h0, (xc, dtc, bc, cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, d)
    y = y + x.astype(jnp.float32) * D[None, None]
    return y.astype(x.dtype)


def selective_scan_step(h, x, dt, A, B, C, D):
    """Decode-step recurrence (always XLA; it is a handful of elementwise ops)."""
    return _ref.selective_scan_step_ref(h, x, dt, A, B, C, D)
