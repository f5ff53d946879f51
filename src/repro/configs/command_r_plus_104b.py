"""command-r-plus-104b [dense]: 64L d_model=12288 96H (GQA kv=8)
d_ff=33792 vocab=256000 — GQA, no-bias
[hf:CohereForAI/c4ai-command-r-v01; unverified].
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="command-r-plus-104b", family="dense",
    n_layers=64, d_model=12288, n_heads=96, n_kv_heads=8,
    d_ff=33792, vocab=256000,
    use_bias=False, tie_embeddings=True, rope_theta=75e6,
)

SMOKE = CONFIG.replace(
    name="command-r-plus-smoke", n_layers=2, d_model=96, n_heads=6,
    n_kv_heads=2, d_ff=256, vocab=512, param_dtype="float32",
    compute_dtype="float32", remat="none",
)
