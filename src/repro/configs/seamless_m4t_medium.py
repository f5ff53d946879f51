"""seamless-m4t-medium [audio]: 12L d_model=1024 16H (GQA kv=16) d_ff=4096
vocab=256206 — enc-dec, multimodal [arXiv:2308.11596; hf].

Backbone only per the assignment: the speech frontend is a stub
(input_specs provides precomputed frame embeddings).  12 encoder + 12
decoder layers.  vocab 256206 is not divisible by the 16-way model axis;
the unembed stays replicated on that dim (relaxed sharding) — the model is
small enough that this costs <0.6 GB/chip.
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="seamless-m4t-medium", family="encdec",
    n_layers=12, n_encoder_layers=12, d_model=1024, n_heads=16,
    n_kv_heads=16, d_ff=4096, vocab=256206, tie_embeddings=True,
)

SMOKE = CONFIG.replace(
    name="seamless-smoke", n_layers=2, n_encoder_layers=2, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=128, vocab=512,
    param_dtype="float32", compute_dtype="float32", remat="none",
)
