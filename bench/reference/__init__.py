"""Plain float32 forwards, one module per model kind (`<kind>.py`).

Each module gives `init(m, key, dtype)`, the weights the benchmark makes
from the seed in the layout the program serves; `forward(params, m,
tokens, mm)`, the logits at every position of one sequence;
`work_shapes(m)`, the sizes bench/work.py counts operations and bytes
from; and `registry_view(cfg)`, the program's registry config in the
configuration file's keys, which a run checks against the file. `m` is the
"model" block of a configuration file; `mm(x, w)` is the one matrix
product every weight goes through, so a lower-precision control can
replace it. Nothing here imports the program.
"""
import jax
import jax.numpy as jnp


def mm_f32(x, w):
    """float32 product at the highest precision the chip offers."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def normal(key, shape, std, dtype):
    """Normal draws made directly in the served type (no float32 copy)."""
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta):
    """Rotary embedding over positions 0..S-1; x (S, H, hd), halves
    rotated as (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, m, mm):
    """Causal GQA self-attention with RoPE over one sequence x (S, d)."""
    s = x.shape[0]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    q = rope(mm(x, p["wq"]).reshape(s, h, hd), m["rope_theta"])
    k = rope(mm(x, p["wk"]).reshape(s, kv, hd), m["rope_theta"])
    v = mm(x, p["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v,
                   precision=jax.lax.Precision.HIGHEST)
    return mm(o.reshape(s, h * hd), p["wo"])


def gated_mlp(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def attn_init(key, m, dtype):
    d, h, kv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd = d // h
    ks = jax.random.split(key, 4)
    return {"wq": normal(ks[0], (d, h * hd), 0.02, dtype),
            "wk": normal(ks[1], (d, kv * hd), 0.02, dtype),
            "wv": normal(ks[2], (d, kv * hd), 0.02, dtype),
            "wo": normal(ks[3], (h * hd, d), 0.02, dtype)}


def mlp_init(key, d, f, dtype):
    ks = jax.random.split(key, 3)
    return {"up": normal(ks[0], (d, f), 0.02, dtype),
            "down": normal(ks[1], (f, d), 0.02, dtype),
            "gate": normal(ks[2], (d, f), 0.02, dtype)}


def attn_mlp_sizes(m):
    """Parameters of one attention block and one SwiGLU MLP, the head
    size, and the vocabulary's embedding."""
    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    hd = d // h
    return {"d": d, "head_dim": hd, "heads": h, "kv_heads": kv,
            "attn": d * h * hd + 2 * d * kv * hd + h * hd * d,
            "mlp": 3 * d * m["intermediate_size"],
            "vocab": m["vocab_size"] * d}
