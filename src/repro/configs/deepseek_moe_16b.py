"""deepseek-moe-16b — 28L d_model=2048 16H (MHA kv=16) vocab=102400; one
leading dense layer (d_ff=10944), then 27 MoE layers of 64 fine-grained
routed experts (d_ff=1408) top-6 + 2 shared experts [arXiv:2401.06066; hf].
Expert-parallel sharding (64 % 16 == 0)."""
from repro.configs.base import ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b", family="moe",
        n_layers=28, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=10944, vocab_size=102400, rope_theta=10000.0,
        n_dense_layers=1,
        moe=MoEConfig(n_experts=64, top_k=6, n_shared_experts=2,
                      d_ff_expert=1408, expert_sharding="expert"),
    )
