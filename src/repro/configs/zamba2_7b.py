"""zamba2-7b [hybrid]: 81 Mamba2 layers, d_model=3584 (d_inner 7168, 112
SSM heads of 64, ssm_state 64, two B/C groups, conv 4 with bias) and two
shared transformer blocks (32 query and 32 key-value heads of 224 over
concat(h, emb) = 7168, RoPE θ 1e4, gelu-gated MLP 14336 with a rank-128
adapter per application) alternating over the 13 hybrid layers
[6, 11, 17, ..., 77]; vocab 32000, tied embeddings, context 4096
[hf Zyphra/Zamba2-7B-Instruct config.json]."""
from repro.models.api import ModelConfig

ARCH_ID = "zamba2-7b"

# the published hybrid_layer_ids
HYBRID_LAYERS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


def full() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="zamba2",
        n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
        d_ff=14336, vocab=32000,
        ssm_state=64, d_inner=7168, ssm_groups=2,
        hybrid_layers=HYBRID_LAYERS, n_shared_blocks=2, adapter_rank=128,
        rope_theta=10000.0, tie_embeddings=True,
    )


def smoke() -> ModelConfig:
    """The published structure at a CPU size: 16 layers with hybrid
    points at irregular gaps, each of the two shared blocks applied twice,
    two SSM groups of two heads, a tied head."""
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="zamba2",
        n_layers=16, d_model=128, n_heads=4, n_kv_heads=4, head_dim=64,
        d_ff=256, vocab=256,
        ssm_state=16, d_inner=256, ssm_groups=2,
        hybrid_layers=(2, 5, 9, 14), n_shared_blocks=2, adapter_rank=64,
        rope_theta=10000.0, tie_embeddings=True, remat="none",
    )
