"""zamba2-7b [hybrid]: 81 Mamba2 layers at d_model=3584 with two shared
attention+MLP blocks — Zamba2-7B as published (arXiv:2411.15242;
https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json).

The shared blocks A and B run alternately at the 13 layers of
``hybrid_layer_ids``, each such site with its own rank-128 MLP adapter and
``linear``; attention reads concat(x, x0) at 7168 with 32 heads of 224;
Mamba2 has 112 heads of 64 in 2 groups, state 64.  ``models/hybrid.py``
writes the layer equations down.
"""
from repro.models import HybridConfig, ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14336, vocab=32000, rope_theta=10000.0, tie_embeddings=True,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, chunk=256,
                  n_groups=2),
    hybrid=HybridConfig(
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
        num_mem_blocks=2, adapter_rank=128),
)

SMOKE = CONFIG.replace(
    name="zamba2-smoke", n_layers=6, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=0, d_ff=128, vocab=512, param_dtype="float32",
    compute_dtype="float32", remat="none",
    ssm=SSMConfig(d_state=16, chunk=16, head_dim=16, n_groups=2),
    hybrid=HybridConfig(hybrid_layer_ids=(2, 3, 5), num_mem_blocks=2,
                        adapter_rank=8),
)
