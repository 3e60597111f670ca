"""Mamba-1 and Mamba-2 blocks (train/prefill scan + single-step decode).

State carried per request (the SSM analogue of the KV cache — constant
size, which changes the Andes knapsack weight, see DESIGN.md §4):
  Mamba-1: conv buffer (d_conv-1, d_inner) + scan state (d_inner, N)
  Mamba-2: conv buffer (d_conv-1, d_inner + 2GN) + scan state (NH, HD, N),
           G = ngroups
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import normal, rms_norm


# ---------------------------------------------------------------------------
# Causal depthwise conv1d
# ---------------------------------------------------------------------------

def _causal_conv(x: jax.Array, w: jax.Array, prev=None):
    """x (B, S, C), w (K, C) depthwise causal conv.

    prev: optional (B, K-1, C) left context (for chunk/decode continuity).
    Returns (y (B, S, C), new_prev (B, K-1, C))."""
    k = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)              # (B, S+K-1, C)
    # depthwise conv as sum of shifted scalings (K is tiny: 4)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k)
    )
    new_prev = xp[:, -(k - 1):, :] if k > 1 else prev
    return y, new_prev


def _conv_step(x_tok: jax.Array, w: jax.Array, prev: jax.Array):
    """One-token conv. x_tok (B, C), prev (B, K-1, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([prev, x_tok[:, None, :]], axis=1)   # (B, K, C)
    y = jnp.einsum("bkc,kc->bc", xp, w)
    return y, xp[:, 1:, :]


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------

def init_mamba1(rng, cfg: ModelConfig, dtype):
    d, s = cfg.d_model, cfg.ssm
    di = cfg.d_inner
    dt_rank = max(d // 16, 1)
    ks = jax.random.split(rng, 6)
    return {
        "in_proj": normal(ks[0], (d, 2 * di), dtype=dtype),
        "conv_w": normal(ks[1], (s.d_conv, di), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": normal(ks[2], (di, dt_rank + 2 * s.d_state), dtype=dtype),
        "dt_proj": normal(ks[3], (dt_rank, di), std=dt_rank ** -0.5, dtype=dtype),
        "dt_bias": jnp.full((di,), -2.0, dtype),   # softplus(-2)≈0.13
        "A_log": jnp.log(
            jnp.broadcast_to(jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (di, s.d_state))
        ).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": normal(ks[4], (di, d), dtype=dtype),
    }


def _mamba1_bcd(p, xc, cfg):
    """Project conv output to (dt, B, C)."""
    s = cfg.ssm
    dt_rank = max(cfg.d_model // 16, 1)
    dbc = xc @ p["x_proj"]
    dt_r = dbc[..., :dt_rank]
    B = dbc[..., dt_rank : dt_rank + s.d_state]
    C = dbc[..., dt_rank + s.d_state :]
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])
    return dt, B, C


def mamba1_apply(p, x, cfg: ModelConfig, *, impl="chunked"):
    """Full-sequence Mamba-1 block. x (B, S, d) -> (B, S, d)."""
    xz = x @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    xc, _ = _causal_conv(x_in, p["conv_w"])
    xc = jax.nn.silu(xc + p["conv_b"])
    dt, B, C = _mamba1_bcd(p, xc, cfg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ops.selective_scan(
        xc, dt, A, B, C, p["D"].astype(jnp.float32),
        impl=impl, chunk=cfg.ssm.chunk,
    )
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"]


def mamba1_prefill(p, x, cfg: ModelConfig, lengths, *, impl="chunked"):
    """Like apply, but also returns decode state at position lengths-1.

    Right-padded prompts: state must be taken at each request's last valid
    token. We zero dt beyond `lengths` so padding is a no-op for the
    recurrence (exp(0*A)=1, 0*B*x=0) — then the final state is correct."""
    b, s, _ = x.shape
    xz = x @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    xc, _ = _causal_conv(x_in, p["conv_w"])
    xc = jax.nn.silu(xc + p["conv_b"])
    dt, B, C = _mamba1_bcd(p, xc, cfg)
    eff_len = lengths if lengths is not None else jnp.full((b,), s)
    if lengths is not None:
        valid = (jnp.arange(s)[None] < lengths[:, None])[..., None]
        dt = jnp.where(valid, dt, 0.0)
    # conv buffer must hold the last K-1 *valid* inputs per request
    conv_prev = _gather_last(x_in, eff_len, p["conv_w"].shape[0] - 1)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    # need the final h: rerun scan capturing last state via chunked impl
    y, h_last = _scan_with_state(xc, dt, A, B, C, p["D"], cfg, impl)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], {"h": h_last, "conv": conv_prev}


def _gather_last(x, lengths, k):
    """Last k valid rows of x (B, S, C) given per-request lengths."""
    b, s, c = x.shape
    idx = lengths[:, None] - k + jnp.arange(k)[None]          # (B, k)
    idx = jnp.clip(idx, 0, s - 1)
    gathered = jnp.take_along_axis(x, idx[..., None], axis=1)  # (B, k, C)
    valid = (lengths[:, None] - k + jnp.arange(k)[None]) >= 0
    return jnp.where(valid[..., None], gathered, 0.0).astype(x.dtype)


def _scan_with_state(xc, dt, A, B, C, D, cfg, impl):
    """Selective scan that also returns the final state (for prefill)."""
    bsz, s, d = xc.shape
    n = A.shape[1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        dA = jnp.exp(dt_t[..., None] * A[None])
        h = dA * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, d, n), jnp.float32)
    xs = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (xc, dt, B, C)
    )
    h_last, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + xc.astype(jnp.float32) * D[None, None].astype(jnp.float32)
    return y.astype(xc.dtype), h_last


def mamba1_decode(p, x_tok, state, cfg: ModelConfig):
    """One-token decode. x_tok (B, d); state {"h": (B,di,N), "conv": (B,K-1,di)}."""
    xz = x_tok @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    xc, conv = _conv_step(x_in, p["conv_w"], state["conv"])
    xc = jax.nn.silu(xc + p["conv_b"])
    dt, B, C = _mamba1_bcd(p, xc, cfg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    h, y = ops.selective_scan_step(
        state["h"], xc.astype(jnp.float32), dt.astype(jnp.float32), A,
        B.astype(jnp.float32), C.astype(jnp.float32), p["D"].astype(jnp.float32),
    )
    y = y.astype(x_tok.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"], {"h": h, "conv": conv}


# ---------------------------------------------------------------------------
# Mamba-2
#
# B and C come in `ngroups` groups of d_state; head h reads group
# h // (heads / ngroups), and the gated RMS norm normalizes each group's
# d_inner / ngroups channels on its own (Zamba2). One group is plain
# Mamba-2. The mixer and its recurrence run under the named scopes
# `zamba2.mamba` and `zamba2.ssd`, which the benchmark reads off the
# device trace.
# ---------------------------------------------------------------------------

def init_mamba2(rng, cfg: ModelConfig, dtype):
    d, s = cfg.d_model, cfg.ssm
    di = cfg.d_inner
    nh = di // s.headdim
    conv_dim = cfg.mamba2_conv_dim
    ks = jax.random.split(rng, 4)
    return {
        "in_proj": normal(ks[0], (d, di + conv_dim + nh), dtype=dtype),
        "conv_w": normal(ks[1], (s.d_conv, conv_dim), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.full((nh,), -2.0, dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)).astype(dtype),
        "D": jnp.ones((nh,), dtype),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": normal(ks[2], (di, d), dtype=dtype),
    }


def _mamba2_split(p, x, cfg):
    di = cfg.d_inner
    nh = di // cfg.ssm.headdim
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + cfg.mamba2_conv_dim]
    dt = zxbcdt[..., -nh:]
    return z, xbc, dt, nh


def _mamba2_inputs(p, xbc_c, dt, cfg):
    """Split the conv output into x (.., NH, HD), B and C (.., G, N), and
    the time step: softplus(dt + bias), floored at the config's dt_min."""
    s = cfg.ssm
    di, gn = cfg.d_inner, s.ngroups * s.d_state
    lead = xbc_c.shape[:-1]
    x_in = xbc_c[..., :di].reshape(*lead, di // s.headdim, s.headdim)
    B = xbc_c[..., di : di + gn].reshape(*lead, s.ngroups, s.d_state)
    C = xbc_c[..., di + gn :].reshape(*lead, s.ngroups, s.d_state)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    if s.dt_min:
        dt = jnp.maximum(dt, s.dt_min)
    return x_in, B, C, dt


def _gated_norm(y, z, p, cfg):
    """RMS norm of y * silu(z) over each group's d_inner / ngroups
    channels, then the per-channel scale."""
    g = cfg.ssm.ngroups
    v = y * jax.nn.silu(z)
    if g == 1:
        return rms_norm(v, p["norm_scale"], cfg.norm_eps)
    lead, di = v.shape[:-1], v.shape[-1]
    vg = v.astype(jnp.float32).reshape(*lead, g, di // g)
    vg = vg * jax.lax.rsqrt(jnp.mean(jnp.square(vg), -1, keepdims=True)
                            + cfg.norm_eps)
    return (vg.reshape(*lead, di)
            * p["norm_scale"].astype(jnp.float32)).astype(v.dtype)


def _per_head(bc, nh):
    """(.., G, N) -> (.., NH, N): each group serves NH / G heads in turn."""
    return jnp.repeat(bc, nh // bc.shape[-2], axis=-2)


def _ssd_step(h, x, dt, A, B, C):
    """One step of the recurrence, f32, without the D skip: h (B,HD,N,NH),
    heads minor (64-wide HD and N would each be padded to a 128-lane tile
    on a TPU), x (B,NH,HD), dt (B,NH), A (NH,), B/C (B,G,N). Returns
    (h', y (B,NH,HD))."""
    nh = x.shape[1]
    Bh = jnp.swapaxes(_per_head(B, nh), 1, 2)[:, None]        # (B,1,N,NH)
    Ch = jnp.swapaxes(_per_head(C, nh), 1, 2)[:, None]
    da = jnp.exp(dt * A[None])[:, None, None, :]
    dtx = (dt[:, None, :] * jnp.swapaxes(x, 1, 2))[:, :, None, :]
    h = da * h + dtx * Bh
    return h, jnp.swapaxes(jnp.sum(h * Ch, axis=2), 1, 2)


def mamba2_apply(p, x, cfg: ModelConfig, *, impl="chunked", lengths=None,
                 return_state=False):
    """Full-sequence Mamba-2 (SSD) block; optionally returns decode state."""
    with jax.named_scope("zamba2.mamba"):
        return _mamba2_apply(p, x, cfg, impl, lengths, return_state)


def _mamba2_apply(p, x, cfg, impl, lengths, return_state):
    s = cfg.ssm
    di = cfg.d_inner
    b, slen, _ = x.shape
    z, xbc, dt, nh = _mamba2_split(p, x, cfg)
    conv_prev = None
    if return_state:
        eff_len = lengths if lengths is not None else jnp.full((b,), slen)
        conv_prev = _gather_last(xbc, eff_len, p["conv_w"].shape[0] - 1)
    xbc_c, _ = _causal_conv(xbc, p["conv_w"])
    xbc_c = jax.nn.silu(xbc_c + p["conv_b"])
    x_in, B, C, dt = _mamba2_inputs(p, xbc_c, dt, cfg)
    if lengths is not None:
        valid = jnp.arange(slen)[None] < lengths[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)
    with jax.named_scope("zamba2.ssd"):
        if return_state or s.ngroups > 1:
            y, h_last = _ssd_with_state(x_in, dt, A, B, C, D)
        else:
            y = ops.ssd(x_in, dt, A, B[..., 0, :], C[..., 0, :], D,
                        impl=impl, chunk=s.chunk)
            h_last = None
    y = _gated_norm(y.reshape(b, slen, di), z, p, cfg)
    out = y @ p["out_proj"]
    if return_state:
        return out, {"h": h_last, "conv": conv_prev}
    return out


def _ssd_with_state(x, dt, A, B, C, D):
    """The recurrence over a sequence, one token at a time, from a zero
    state; returns (y, final state)."""
    bsz, s, nh, hd = x.shape
    n = B.shape[-1]

    def step(h, inp):
        return _ssd_step(h, *inp[:2], A, *inp[2:])

    h0 = jnp.zeros((bsz, hd, n, nh), jnp.float32)
    xs = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (x, dt, B, C)
    )
    h_last, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_last


def mamba2_prefill(p, x, cfg: ModelConfig, lengths, *, impl="chunked"):
    return mamba2_apply(p, x, cfg, impl=impl, lengths=lengths, return_state=True)


def mamba2_decode(p, x_tok, state, cfg: ModelConfig):
    """One-token decode. x_tok (B, d)."""
    with jax.named_scope("zamba2.mamba"):
        di = cfg.d_inner
        b = x_tok.shape[0]
        z, xbc, dt, nh = _mamba2_split(p, x_tok[:, None, :], cfg)
        z, xbc, dt = z[:, 0], xbc[:, 0], dt[:, 0]
        xbc_c, conv = _conv_step(xbc, p["conv_w"], state["conv"])
        xbc_c = jax.nn.silu(xbc_c + p["conv_b"])
        x_in, B, C, dt = _mamba2_inputs(p, xbc_c, dt, cfg)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        f32 = lambda t: t.astype(jnp.float32)
        with jax.named_scope("zamba2.ssd"):
            h, y = _ssd_step(state["h"], f32(x_in), f32(dt), A, f32(B),
                             f32(C))
            y = y + f32(x_in) * f32(p["D"])[None, :, None]
        y = y.reshape(b, di).astype(x_tok.dtype)
        y = _gated_norm(y, z, p, cfg)
        return y @ p["out_proj"], {"h": h, "conv": conv}
