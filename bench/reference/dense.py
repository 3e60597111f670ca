"""Dense decoder (granite-3-2b): pre-norm GQA attention with RoPE and a
SwiGLU MLP in every layer, tied embeddings, RMS norms."""
import jax
import jax.numpy as jnp

from bench.reference import attention, attn_init, attn_mlp_sizes, \
    gated_mlp, mlp_init, normal, rms_norm


def init(m, key, dtype):
    """Weights in the program's layout: layers stacked on a leading axis."""
    L, d, f, v = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    k_emb, k_attn, k_mlp = jax.random.split(key, 3)
    stack = lambda fn, k: jax.vmap(fn)(jax.random.split(k, L))
    return {
        "embed": {"table": normal(k_emb, (v, d), 0.02, dtype)},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "blocks": {
            "attn_norm_scale": jnp.ones((L, d), dtype),
            "attn": stack(lambda k: attn_init(k, m, dtype), k_attn),
            "mlp_norm_scale": jnp.ones((L, d), dtype),
            "mlp": stack(lambda k: mlp_init(k, d, f, dtype), k_mlp),
        },
    }


def forward(params, m, tokens, mm):
    """Logits (S, vocab) in float32 for one sequence of token ids (S,).
    Layers run one at a time, each upcast inside the scan body."""
    eps = m["rms_norm_eps"]
    h = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(h, bp):
        h = h + attention(rms_norm(h, bp["attn_norm_scale"], eps),
                          bp["attn"], m, mm)
        h = h + gated_mlp(rms_norm(h, bp["mlp_norm_scale"], eps),
                          bp["mlp"], mm)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["blocks"])
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    return mm(h, params["embed"]["table"].T)


def work_shapes(m):
    s = attn_mlp_sizes(m)
    L, d = m["num_hidden_layers"], s["d"]
    return dict(s, attn_apps=L,
                layer_params=L * (s["attn"] + s["mlp"] + 2 * d),
                matmul_params=L * (s["attn"] + s["mlp"]) + s["vocab"],
                embed_params=s["vocab"],
                head_params=0 if m["tie_word_embeddings"] else s["vocab"])


def registry_view(cfg):
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings,
            "kind": cfg.kind}
