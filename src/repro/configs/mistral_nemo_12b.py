"""mistral-nemo-12b [dense]: 40L d_model=5120 32H (GQA kv=8) d_ff=14336
vocab=131072 — 128k ctx [hf:mistralai/Mistral-Nemo-Base-2407; hf].
head_dim=128 (not d_model/n_heads=160) per the HF config.
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="mistral-nemo-12b", family="dense",
    n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8,
    d_ff=14336, vocab=131072, head_dim=128, rope_theta=1e6,
)

SMOKE = CONFIG.replace(
    name="mistral-nemo-smoke", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=160, vocab=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", remat="none",
)
