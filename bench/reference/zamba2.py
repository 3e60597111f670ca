"""Zamba2 as published (Zamba2-7B-Instruct's config; the equations of
transformers' `modeling_zamba2.py`): every layer is a Mamba-2 layer with
B and C in groups; at each hybrid layer id one of `num_mem_blocks`
shared blocks, taken in turn, reads concat(hidden, original embedding)
(RMSNorm over 2d, attention of heads of `attention_head_dim` with RoPE
and softmax scale (head_dim / 2) ** -0.5, RMSNorm, a GeGLU MLP whose
gate/up projection has that application's own low-rank adapter), and its
output, through that application's linear, is added to the Mamba layer's
input before the layer's norm; the residual is the hidden state alone.
Tied embeddings. The layers run one at a time in a Python loop over the
hybrid groups, each Mamba-2 recurrence token by token from a zero state.
"""
import jax
import jax.numpy as jnp

from bench.reference import normal, rms_norm, rope

HI = jax.lax.Precision.HIGHEST


def _sizes(m):
    d, n, g = m["hidden_size"], m["mamba_d_state"], m["mamba_ngroups"]
    di = m["mamba_expand"] * d
    nh = di // m["mamba_headdim"]
    return d, di, n, g, nh, di + 2 * g * n


def mamba_init(key, m, dtype):
    d, di, n, g, nh, conv = _sizes(m)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": normal(ks[0], (d, di + conv + nh), 0.02, dtype),
        "conv_w": normal(ks[1], (m["mamba_d_conv"], conv), 0.1, dtype),
        "conv_b": normal(ks[2], (conv,), 0.02, dtype),
        "dt_bias": jnp.full((nh,), -2.0, dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)
                         ).astype(dtype),
        "D": jnp.ones((nh,), dtype),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": normal(ks[3], (di, d), 0.02, dtype),
    }


def init(m, key, dtype):
    """Weights in the program's layout: the Mamba layers stacked on a
    leading axis, the shared blocks on another, and each application's
    adapter (a, b) and linear on a third."""
    d, f, v = m["hidden_size"], m["ffn_hidden_size"], m["vocab_size"]
    L, M = m["num_hidden_layers"], m["num_mem_blocks"]
    A, r = len(m["hybrid_layer_ids"]), m["adapter_rank"]
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["attention_head_dim"])
    din = m["attention_hidden_size"]
    ks = jax.random.split(key, 12)
    stack = lambda fn, k, n: jax.vmap(fn)(jax.random.split(k, n))
    w = lambda k, shape: normal(k, shape, 0.02, dtype)
    return {
        "embed": {"table": w(ks[0], (v, d))},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "layers": {"norm_scale": jnp.ones((L, d), dtype),
                   "mamba": stack(lambda k: mamba_init(k, m, dtype), ks[1],
                                  L)},
        "shared": {
            "attn_norm_scale": jnp.ones((M, din), dtype),
            "attn": {"wq": w(ks[2], (M, din, h * hd)),
                     "wk": w(ks[3], (M, din, kv * hd)),
                     "wv": w(ks[4], (M, din, kv * hd)),
                     "wo": w(ks[5], (M, h * hd, d))},
            "mlp_norm_scale": jnp.ones((M, d), dtype),
            "mlp": {"up": w(ks[6], (M, d, f)), "down": w(ks[7], (M, f, d)),
                    "gate": w(ks[8], (M, d, f))},
        },
        "hybrid": {"adapter_a": w(ks[9], (A, d, r)),
                   "adapter_b": w(ks[10], (A, r, 2 * f)),
                   "linear": w(ks[11], (A, d, d))},
    }


def mamba2(x, p, m, mm):
    """One Mamba-2 mixer over a sequence x (S, d), state from zero:
    in-projection to (gate z, xBC, dt); causal depthwise conv with bias and
    SiLU on xBC; dt = max(softplus(dt + bias), time_step_min); per head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t,
    with B and C of the head's group; y * silu(z) normalized per group;
    out-projection."""
    s = x.shape[0]
    d, di, n, g, nh, conv = _sizes(m)
    hd, kc = m["mamba_headdim"], m["mamba_d_conv"]
    f32 = lambda a: a.astype(jnp.float32)
    zxbcdt = mm(x, p["in_proj"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + conv], zxbcdt[:, di + conv:]
    xp = jnp.concatenate([jnp.zeros((kc - 1, conv), xbc.dtype), xbc])
    w = f32(p["conv_w"])
    xbc = jax.nn.silu(sum(xp[i:i + s] * w[i] for i in range(kc))
                      + f32(p["conv_b"]))
    xs = xbc[:, :di].reshape(s, nh, hd)
    per_head = lambda t: jnp.repeat(t.reshape(s, g, n), nh // g, axis=1)
    B, C = per_head(xbc[:, di:di + g * n]), per_head(xbc[:, di + g * n:])
    dt = jnp.maximum(jax.nn.softplus(dt + f32(p["dt_bias"])),
                     m["time_step_min"])
    A = -jnp.exp(f32(p["A_log"]))
    D = f32(p["D"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hdn,hn->hd", h, c_t, precision=HI) + D[:, None] * x_t
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n), jnp.float32),
                        (xs, dt, B, C))
    y = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + m["rms_norm_eps"])
    return mm(y.reshape(s, di) * f32(p["norm_scale"]), p["out_proj"])


def attention(x, p, m, mm):
    """Causal attention over one sequence x (S, 2d) with RoPE on heads of
    `attention_head_dim` and softmax scale (head_dim / 2) ** -0.5."""
    s = x.shape[0]
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["attention_head_dim"])
    q = rope(mm(x, p["wq"]).reshape(s, h, hd), m["rope_theta"])
    k = rope(mm(x, p["wk"]).reshape(s, kv, hd), m["rope_theta"])
    v = mm(x, p["wv"]).reshape(s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * (hd / 2) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return mm(o.reshape(s, h * hd), p["wo"])


def shared_block(x, emb, blk, a, b, linear, m, mm):
    """One application: the shared block on concat(x, emb), then the
    application's linear."""
    eps = m["rms_norm_eps"]
    f = m["ffn_hidden_size"]
    y = attention(rms_norm(jnp.concatenate([x, emb], -1),
                           blk["attn_norm_scale"], eps), blk["attn"], m, mm)
    y = rms_norm(y, blk["mlp_norm_scale"], eps)
    gu = mm(mm(y, a), b)
    gate = mm(y, blk["mlp"]["gate"]) + gu[:, :f]
    up = mm(y, blk["mlp"]["up"]) + gu[:, f:]
    y = mm(jax.nn.gelu(gate, approximate=False) * up, blk["mlp"]["down"])
    return mm(y, linear)


def forward(params, m, tokens, mm):
    eps = m["rms_norm_eps"]
    layers, M = params["layers"], m["num_mem_blocks"]
    emb = params["embed"]["table"][tokens].astype(jnp.float32)
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)

    def mamba_layer(h, lp, extra=0.0):
        return h + mamba2(rms_norm(h + extra, lp["norm_scale"], eps),
                          lp["mamba"], m, mm)

    def plain(h, lo, hi):
        if hi <= lo:
            return h
        h, _ = jax.lax.scan(lambda h, lp: (mamba_layer(h, lp), None), h,
                            jax.tree.map(lambda t: t[lo:hi], layers))
        return h

    h, lo = emb, 0
    for k, i in enumerate(m["hybrid_layer_ids"]):
        h = plain(h, lo, i)
        hyb = params["hybrid"]
        t = shared_block(h, emb, at(params["shared"], k % M),
                         hyb["adapter_a"][k], hyb["adapter_b"][k],
                         hyb["linear"][k], m, mm)
        h = mamba_layer(h, at(layers, i), t)
        lo = i + 1
    h = plain(h, lo, m["num_hidden_layers"])
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    return mm(h, params["embed"]["table"].T)


def work_shapes(m):
    """Sizes bench/work.py counts from. Each shared block is stored once
    and runs once per application, so its weights count once in the
    parameters and `attn_apps` times in the matrix products."""
    d, di, n, g, nh, conv = _sizes(m)
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["attention_head_dim"])
    din, f, r = (m["attention_hidden_size"], m["ffn_hidden_size"],
                 m["adapter_rank"])
    L, M, A = (m["num_hidden_layers"], m["num_mem_blocks"],
               len(m["hybrid_layer_ids"]))
    kc, vocab = m["mamba_d_conv"], m["vocab_size"] * d
    in_proj = d * (di + conv + nh)
    mamba = in_proj + di * d + kc * conv + conv + 3 * nh + di + d
    attn = din * h * hd + 2 * din * kv * hd + h * hd * d
    mlp = 3 * d * f
    app = d * r + r * 2 * f + d * d
    return dict(
        d=d, head_dim=hd, heads=h, kv_heads=kv, attn=attn, mlp=mlp,
        vocab=vocab, attn_apps=A,
        layer_params=L * mamba + M * (attn + mlp + din + d) + A * app,
        matmul_params=L * (in_proj + di * d) + A * (attn + mlp + app) + vocab,
        embed_params=vocab,
        head_params=0 if m["tie_word_embeddings"] else vocab,
        # per row: f32 scan state and bf16 conv lookback of every layer
        state_bytes=L * (nh * m["mamba_headdim"] * n * 4 + (kc - 1) * conv * 2),
        # per token: state decay, update and read-out, ~5 FLOPs per element
        scan_flops=L * 5 * nh * m["mamba_headdim"] * n)


def registry_view(cfg):
    s = cfg.ssm
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "attention_head_dim": cfg.head_dim,
        "attention_hidden_size": cfg.d_attn_in,
        "ffn_hidden_size": cfg.d_ff, "intermediate_size": cfg.d_ff,
        "hidden_act": cfg.mlp_act, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "hybrid_layer_ids": list(cfg.hybrid_layer_ids),
        "num_mem_blocks": cfg.num_mem_blocks,
        "adapter_rank": cfg.adapter_rank,
        "mamba_d_state": s.d_state, "mamba_d_conv": s.d_conv,
        "mamba_expand": s.expand, "mamba_headdim": s.headdim,
        "mamba_ngroups": s.ngroups,
        "n_mamba_heads": cfg.d_inner // s.headdim,
        "time_step_min": s.dt_min,
        "max_position_embeddings": cfg.max_position,
        "kind": "zamba2" if cfg.hybrid_layer_ids else cfg.kind,
    }
