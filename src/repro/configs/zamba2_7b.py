"""Zamba2 7B (Instruct) — Mamba-2 with two shared attention blocks.
[hf:Zyphra/Zamba2-7B-Instruct]

Every layer is a grouped Mamba-2 layer (2 groups, 112 heads of 64, state
64). At the hybrid layer ids one of two shared blocks, taken in turn,
reads concat(hidden, original embedding): RMSNorm over 2 x d, attention
of 32 heads of 224 over that 7168-wide input (softmax scale
(head_dim / 2) ** -0.5, RoPE), RMSNorm, a GeGLU MLP with this
application's own rank-128 adapter on gate/up; then a per-application
linear whose output joins the Mamba layer's input. Tied embeddings.

CONFIG is the first pipeline stage of the 81-layer model over four chips
(layers 0-23, closing the fourth hybrid layer), as the benchmark serves
it on one chip; PUBLISHED_LAYERS and PUBLISHED_HYBRID_IDS keep the whole
model's pattern.
"""
from repro.configs.base import ModelConfig, SSMConfig

PUBLISHED_LAYERS = 81
PUBLISHED_HYBRID_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = ModelConfig(
    name="zamba2-7b",
    kind="hybrid",
    num_layers=24,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    tie_embeddings=True,
    max_position=1024,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, version=2, headdim=64,
                  chunk=256, ngroups=2, dt_min=0.001),
    hybrid_layer_ids=(6, 11, 17, 23),
    num_mem_blocks=2,
    adapter_rank=128,
    mlp_act="gelu",
    source="hf:Zyphra/Zamba2-7B-Instruct",
)


def smoke_config() -> ModelConfig:
    """The same wiring at a CPU size: 4 layers with hybrid ids 0 and 3
    (both shared blocks once, one application with no plain layer before
    it); grouped Mamba-2, a head size that is not d_model / heads."""
    return ModelConfig(
        name="zamba2-7b-smoke",
        kind="hybrid",
        num_layers=4,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        d_ff=128,
        vocab_size=256,
        tie_embeddings=True,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, version=2, headdim=16,
                      chunk=256, ngroups=2, dt_min=0.001),
        hybrid_layer_ids=(0, 3),
        num_mem_blocks=2,
        adapter_rank=8,
        mlp_act="gelu",
        source="hf:Zyphra/Zamba2-7B-Instruct",
    )
