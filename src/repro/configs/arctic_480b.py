"""arctic-480b [moe]: 35L d_model=7168 56H (GQA kv=8) d_ff=4864
vocab=32000, MoE 128 experts top-2 + dense residual
[hf:Snowflake/snowflake-arctic-base; hf].
"""
from repro.models import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="arctic-480b", family="moe",
    n_layers=35, d_model=7168, n_heads=56, n_kv_heads=8,
    d_ff=4864, vocab=32000,
    moe=MoEConfig(n_experts=128, top_k=2, capacity_factor=1.25,
                  dense_residual=True, dense_ff=4864),
)

SMOKE = CONFIG.replace(
    name="arctic-smoke", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=96, vocab=512, param_dtype="float32", compute_dtype="float32",
    remat="none",
    moe=MoEConfig(n_experts=8, top_k=2, dense_residual=True, dense_ff=96),
)
