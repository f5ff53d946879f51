"""pixtral-12b [vlm]: 40L d_model=5120 32H (GQA kv=8) d_ff=14336
vocab=131072 — pixtral-ViT + mistral-nemo backbone
[hf:mistralai/Pixtral-12B-2409; unverified].

Backbone only per the assignment: the ViT frontend is a stub (input_specs
provides precomputed patch embeddings, merged as a sequence prefix).
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="pixtral-12b", family="vlm",
    n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8,
    d_ff=14336, vocab=131072, head_dim=128, rope_theta=1e6,
)

SMOKE = CONFIG.replace(
    name="pixtral-smoke", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=160, vocab=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", remat="none",
)
