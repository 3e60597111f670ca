"""Shared neural-net building blocks (pure functional JAX)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def normal(rng, shape, std=0.02, dtype=jnp.float32):
    return (jax.random.normal(rng, shape) * std).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x (..., S, H, hd), positions broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(rng, d_model: int, d_ff: int, gated: bool, dtype):
    ks = jax.random.split(rng, 3)
    p = {
        "up": normal(ks[0], (d_model, d_ff), dtype=dtype),
        "down": normal(ks[1], (d_ff, d_model), dtype=dtype),
    }
    if gated:
        p["gate"] = normal(ks[2], (d_model, d_ff), dtype=dtype)
    return p


def mlp_apply(p, x: jax.Array, act: str = "silu", adapter=None) -> jax.Array:
    """Gated MLP act(x W_gate) * (x W_up) W_down with act "silu" (SwiGLU)
    or "gelu" (GeGLU, the exact erf form); a plain GeLU MLP when p has no
    gate. `adapter` (a (d, r), b (r, 2 d_ff)) adds the low-rank (x a) b to
    [gate | up]."""
    if "gate" not in p:
        return jax.nn.gelu(x @ p["up"]) @ p["down"]
    gate, up = x @ p["gate"], x @ p["up"]
    if adapter is not None:
        a, b = adapter
        gu = (x @ a) @ b
        f = gate.shape[-1]
        gate, up = gate + gu[..., :f], up + gu[..., f:]
    g = jax.nn.gelu(gate, approximate=False) if act == "gelu" \
        else jax.nn.silu(gate)
    return (g * up) @ p["down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(rng, vocab: int, d_model: int, dtype):
    return {"table": normal(rng, (vocab, d_model), std=1.0, dtype=dtype)}


def embed_apply(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(params, h: jax.Array) -> jax.Array:
    """h (..., d) -> logits (..., V). Uses tied table if no lm_head."""
    if "lm_head" in params:
        return h @ params["lm_head"]["kernel"]
    return h @ params["embed"]["table"].T
