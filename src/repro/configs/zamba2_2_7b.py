"""zamba2-2.7b [hybrid]: 54 Mamba2 layers, d_model=2560 (d_inner 5120, 80
SSM heads of 64, ssm_state 64, one B/C group) and two shared transformer
blocks (32 heads of 160 over concat(h, emb), gelu-gated MLP 10240 with a
rank-128 adapter per application) alternating over the hybrid layers
[6, 12, 18, 24, 30, 36, 42, 47, 51]; vocab 32000, tied embeddings
[arXiv:2411.15242; hf Zyphra/Zamba2-2.7B]."""
from repro.models.api import ModelConfig

ARCH_ID = "zamba2-2.7b"


def full() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="zamba2",
        n_layers=54, d_model=2560, n_heads=32, n_kv_heads=32, head_dim=160,
        d_ff=10240, vocab=32000,
        ssm_state=64, d_inner=5120, ssm_groups=1,
        hybrid_layers=(6, 12, 18, 24, 30, 36, 42, 47, 51),
        n_shared_blocks=2, adapter_rank=128,
        rope_theta=10000.0, tie_embeddings=True,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="zamba2",
        n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab=256,
        ssm_state=16, d_inner=256, hybrid_layers=(1, 3), n_shared_blocks=2,
        adapter_rank=32, remat="none",
    )
