"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Every kernel runs in interpret mode on CPU (the kernel body executes in
Python) and must match its ref.py oracle to tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.selective_scan import selective_scan


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA
    (1, 200, 4, 1, 32),      # MQA + ragged seq (padding path)
    (2, 64, 2, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_flash_attention_sweep(b, s, h, kv, hd, dtype, causal):
    q = rand(0, (b, s, h, hd), dtype)
    k = rand(1, (b, s, kv, hd), dtype)
    v = rand(2, (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_flash_attention_sliding_window():
    q = rand(0, (2, 256, 4, 64), jnp.float32)
    k = rand(1, (2, 256, 2, 64), jnp.float32)
    v = rand(2, (2, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=50, block_q=64,
                          block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=50)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,hd", [
    (1, 128, 4, 4, 64),
    (3, 300, 8, 2, 64),      # ragged + padding
    (2, 512, 16, 1, 32),     # MQA deep cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_decode_attention_sweep(b, s, h, kv, hd, dtype):
    q = rand(0, (b, h, hd), dtype)
    k = rand(1, (b, s, kv, hd), dtype)
    v = rand(2, (b, s, kv, hd), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, s + 1, b), jnp.int32
    )
    out = decode_attention(q, k, v, lengths, block_k=64, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_decode_attention_window():
    b, s, h, kv, hd = 2, 256, 8, 4, 64
    q = rand(0, (b, h, hd), jnp.float32)
    k = rand(1, (b, s, kv, hd), jnp.float32)
    v = rand(2, (b, s, kv, hd), jnp.float32)
    lengths = jnp.array([256, 100], jnp.int32)
    out = decode_attention(q, k, v, lengths, window=32, block_k=64,
                           interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


@pytest.mark.parametrize("h,kv,window", [
    (4, 4, None),            # MHA, as Zamba2's shared blocks
    (8, 2, None),            # GQA
    (8, 2, 32),
])
def test_flat_decode_attention_matches_ref(h, kv, window):
    """Decode attention over caches whose heads share one minor axis
    (models/attention.py, Zamba2's K/V) == the reference over
    (B, S, KV, hd), ragged lengths included."""
    from repro.models.attention import _flat_decode_attention
    b, s, hd = 3, 96, 24
    q = rand(0, (b, h, hd), jnp.float32)
    k = rand(1, (b, s, kv, hd), jnp.float32)
    v = rand(2, (b, s, kv, hd), jnp.float32)
    lengths = jnp.array([96, 40, 1], jnp.int32)
    out = _flat_decode_attention(q, k.reshape(b, s, -1), v.reshape(b, s, -1),
                                 lengths, window=window, sm_scale=0.3)
    expect = ref.decode_attention_ref(q, k, v, lengths, window=window,
                                      sm_scale=0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# selective scan (Mamba-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d,n", [
    (1, 128, 64, 16),
    (2, 256, 128, 16),
    (1, 64, 256, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.slow
def test_selective_scan_sweep(b, s, d, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 1).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n), dtype)
    C = jax.random.normal(ks[4], (b, s, n), dtype)
    D = jnp.ones((d,)) * 0.3
    out = selective_scan(x, dt, A, B, C, D, chunk=64, block_d=64, interpret=True)
    expect = ref.selective_scan_ref(x, dt, A, B, C, D)
    scale = float(jnp.max(jnp.abs(expect))) + 1e-6
    assert float(jnp.max(jnp.abs(out - expect))) / scale < 1e-5


def test_chunked_scan_matches_sequential():
    """XLA chunked associative form == sequential oracle."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, s, d, n = 2, 192, 64, 16
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jnp.ones((d,)) * 0.3
    out = ops.selective_scan(x, dt, A, B, C, D, impl="chunked", chunk=64)
    expect = ref.selective_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


def test_ssd_chunked_matches_sequential():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, nh, hd, n = 2, 128, 4, 32, 16
    x = jax.random.normal(ks[0], (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jnp.ones((nh,)) * 0.3
    out = ops.ssd(x, dt, A, B, C, D, impl="chunked", chunk=32)
    expect = ref.ssd_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-4, rtol=2e-3)


def test_scan_step_consistency():
    """Sequential decode steps == full-sequence scan."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, d, n = 1, 32, 16, 8
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    D = jnp.ones((d,)) * 0.3
    full = ref.selective_scan_ref(x, dt, A, B, C, D)
    h = jnp.zeros((b, d, n))
    for t in range(s):
        h, y = ops.selective_scan_step(h, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (physical page pool)
# ---------------------------------------------------------------------------

def _paginate(k, v, lengths, page, seed=0):
    """Scatter contiguous (B, S, KV, hd) caches into a shuffled page pool.

    Returns (k_pool, v_pool, block_tables) with page assignment randomized
    across requests (physical page order must not matter) and unowned pool
    rows filled with noise (masking must make them invisible)."""
    b, s, kvh, hd = k.shape
    max_pages = -(-s // page)
    pad = max_pages * page - s
    kp = np.pad(np.asarray(k, np.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = np.pad(np.asarray(v, np.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    needed = [-(-int(L) // page) for L in np.asarray(lengths)]
    p_total = sum(needed) + 3                      # + never-owned noise pages
    rng = np.random.default_rng(seed)
    ids = list(rng.permutation(p_total))
    k_pool = rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32)
    v_pool = rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32)
    tables = np.full((b, max_pages), p_total, np.int32)    # sentinel = P
    for bi in range(b):
        for pi in range(needed[bi]):
            pid = ids.pop()
            tables[bi, pi] = pid
            k_pool[pid] = kp[bi, pi * page:(pi + 1) * page]
            v_pool[pid] = vp[bi, pi * page:(pi + 1) * page]
    dt = k.dtype
    return (jnp.asarray(k_pool, dt), jnp.asarray(v_pool, dt),
            jnp.asarray(tables))


def test_paged_decode_ref_matches_contiguous_bitwise():
    """When max_pages * page == S the paged gather rebuilds the exact
    contiguous view, so the oracle is bit-identical to the contiguous
    oracle — the property the engine's degenerate page-size differentials
    stand on."""
    b, s, h, kv, hd = 3, 64, 4, 2, 32
    q = rand(0, (b, h, hd), jnp.float32)
    k = rand(1, (b, s, kv, hd), jnp.float32)
    v = rand(2, (b, s, kv, hd), jnp.float32)
    lengths = jnp.array([64, 17, 40], jnp.int32)
    for page in (1, 8, 16, 64):                    # all divide S
        k_pool, v_pool, bt = _paginate(k, v, lengths, page, seed=page)
        out = ref.paged_decode_attention_ref(q, k_pool, v_pool, bt, lengths)
        expect = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("b,s,h,kv,hd,page", [
    (1, 128, 4, 4, 64, 16),
    (3, 300, 8, 2, 64, 32),     # ragged lengths + non-divisible S
    (2, 512, 16, 1, 32, 128),   # MQA deep cache, big pages
    (2, 64, 4, 2, 64, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(b, s, h, kv, hd, page, dtype):
    q = rand(0, (b, h, hd), dtype)
    k = rand(1, (b, s, kv, hd), dtype)
    v = rand(2, (b, s, kv, hd), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, s + 1, b), jnp.int32
    )
    k_pool, v_pool, bt = _paginate(k, v, lengths, page)
    from repro.kernels.paged_attention import paged_decode_attention
    out = paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                 interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_paged_decode_attention_window():
    b, s, h, kv, hd, page = 2, 256, 8, 4, 64, 32
    q = rand(0, (b, h, hd), jnp.float32)
    k = rand(1, (b, s, kv, hd), jnp.float32)
    v = rand(2, (b, s, kv, hd), jnp.float32)
    lengths = jnp.array([256, 100], jnp.int32)
    k_pool, v_pool, bt = _paginate(k, v, lengths, page)
    from repro.kernels.paged_attention import paged_decode_attention
    out = paged_decode_attention(q, k_pool, v_pool, bt, lengths, window=32,
                                 interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_paged_decode_ops_dispatch():
    b, s, h, kv, hd, page = 2, 64, 4, 2, 32, 16
    q = rand(0, (b, h, hd), jnp.float32)
    k = rand(1, (b, s, kv, hd), jnp.float32)
    v = rand(2, (b, s, kv, hd), jnp.float32)
    lengths = jnp.array([30, 64], jnp.int32)
    k_pool, v_pool, bt = _paginate(k, v, lengths, page)
    via_ref = ops.paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                         impl="ref")
    via_pallas = ops.paged_decode_attention(q, k_pool, v_pool, bt, lengths,
                                            impl="pallas")
    expect = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(via_ref), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(via_pallas), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Kernels interpret on the CPU, compile on a TPU, and any other
    backend is refused rather than silently interpreted."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda b=backend: b)
        assert ops._interpret() is want
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()
