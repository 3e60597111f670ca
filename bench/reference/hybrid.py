"""Hybrid Mamba-2 decoder with one weight-shared attention block
(zamba2-2.7b, as the program builds it): the layers run in rounds of
(attn_every - 1) Mamba-2 layers followed by the shared pre-norm
attention + SwiGLU block; untied output head."""
import jax
import jax.numpy as jnp

from bench.reference import attention, attn_init, attn_mlp_sizes, \
    gated_mlp, mlp_init, normal, rms_norm
from bench.reference import dense


def _sizes(m):
    d, n = m["hidden_size"], m["mamba_d_state"]
    di = m["mamba_expand"] * d
    nh = di // m["mamba_headdim"]
    return d, di, n, nh, m["mamba_d_conv"]


def mamba_init(key, m, dtype):
    d, di, n, nh, kc = _sizes(m)
    ks = jax.random.split(key, 3)
    return {
        "in_proj": normal(ks[0], (d, 2 * di + 2 * n + nh), 0.02, dtype),
        "conv_w": normal(ks[1], (kc, di + 2 * n), 0.1, dtype),
        "conv_b": jnp.zeros((di + 2 * n,), dtype),
        "dt_bias": jnp.full((nh,), -2.0, dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)
                         ).astype(dtype),
        "D": jnp.ones((nh,), dtype),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": normal(ks[2], (di, d), 0.02, dtype),
    }


def init(m, key, dtype):
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    every = m["attn_every"]
    rounds, per = m["num_hidden_layers"] // every, every - 1
    ks = jax.random.split(key, 5)
    mamba = jax.vmap(jax.vmap(lambda k: mamba_init(k, m, dtype)))(
        jax.random.split(ks[2], (rounds, per)))
    return {
        "embed": {"table": normal(ks[0], (v, d), 0.02, dtype)},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal(ks[1], (d, v), 0.02, dtype)},
        "rounds": {"norm_scale": jnp.ones((rounds, per, d), dtype),
                   "mamba": mamba},
        "shared": {"attn_norm_scale": jnp.ones((d,), dtype),
                   "attn": attn_init(ks[3], m, dtype),
                   "mlp_norm_scale": jnp.ones((d,), dtype),
                   "mlp": mlp_init(ks[4], d, f, dtype)},
    }


def mamba2(x, p, m, mm):
    """One Mamba-2 block over a sequence x (S, d), state from zero:
    in-projection to (z, xBC, dt); causal depthwise conv and SiLU on xBC;
    per-head recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t + D x_t; gated RMS norm; out-projection."""
    s = x.shape[0]
    d, di, n, nh, kc = _sizes(m)
    hd = m["mamba_headdim"]
    f32 = lambda a: a.astype(jnp.float32)
    zxbcdt = mm(x, p["in_proj"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * n], \
        zxbcdt[:, 2 * di + 2 * n:]
    xp = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), xbc.dtype), xbc])
    w = f32(p["conv_w"])
    conv = sum(xp[i:i + s] * w[i] for i in range(kc))
    xbc = jax.nn.silu(conv + f32(p["conv_b"]))
    xs = xbc[:, :di].reshape(s, nh, hd)
    B, C = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))
    A = -jnp.exp(f32(p["A_log"]))
    D = f32(p["D"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hdn,n->hd", h, c_t,
                       precision=jax.lax.Precision.HIGHEST) + D[:, None] * x_t
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n), jnp.float32),
                        (xs, dt, B, C))
    y = rms_norm(y.reshape(s, di) * jax.nn.silu(z), p["norm_scale"],
                 m["rms_norm_eps"])
    return mm(y, p["out_proj"])


def forward(params, m, tokens, mm):
    eps = m["rms_norm_eps"]
    sh = params["shared"]
    h = params["embed"]["table"][tokens].astype(jnp.float32)

    def mamba_layer(h, lp):
        norm, p = lp
        return h + mamba2(rms_norm(h, norm, eps), p, m, mm), None

    def round_fn(h, rp):
        h, _ = jax.lax.scan(mamba_layer, h, (rp["norm_scale"], rp["mamba"]))
        h = h + attention(rms_norm(h, sh["attn_norm_scale"], eps),
                          sh["attn"], m, mm)
        h = h + gated_mlp(rms_norm(h, sh["mlp_norm_scale"], eps),
                          sh["mlp"], mm)
        return h, None

    h, _ = jax.lax.scan(round_fn, h, params["rounds"])
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    return mm(h, params["lm_head"]["kernel"])


def work_shapes(m):
    s = attn_mlp_sizes(m)
    d, di, n, nh, kc = _sizes(m)
    hd = m["mamba_headdim"]
    rounds = m["num_hidden_layers"] // m["attn_every"]
    mamba_layers = rounds * (m["attn_every"] - 1)
    in_proj = d * (2 * di + 2 * n + nh)
    conv_dim = di + 2 * n
    mamba = in_proj + di * d + kc * conv_dim + conv_dim + 3 * nh + di
    return dict(
        s, attn_apps=rounds,
        layer_params=mamba_layers * (mamba + d)
        + s["attn"] + s["mlp"] + 2 * d,
        matmul_params=mamba_layers * (in_proj + di * d)
        + rounds * (s["attn"] + s["mlp"]) + s["vocab"],
        embed_params=s["vocab"],
        head_params=0 if m["tie_word_embeddings"] else s["vocab"],
        # per row: f32 scan state and bf16 conv lookback of every layer
        state_bytes=mamba_layers * (nh * hd * n * 4 + (kc - 1) * conv_dim * 2),
        # per token: state decay, update and read-out, ~5 FLOPs per element
        scan_flops=mamba_layers * 5 * nh * hd * n)


def registry_view(cfg):
    return dict(dense.registry_view(cfg),
                mamba_d_state=cfg.ssm.d_state, mamba_d_conv=cfg.ssm.d_conv,
                mamba_expand=cfg.ssm.expand, mamba_headdim=cfg.ssm.headdim,
                attn_every=cfg.hybrid_attn_every)
