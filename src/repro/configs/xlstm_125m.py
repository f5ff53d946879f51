"""xlstm-125m [ssm]: 12L d_model=768 4H (GQA kv=4) d_ff=0 vocab=50304.

sLSTM + mLSTM blocks, alternating (6 pairs) [arXiv:2405.04517; unverified].
d_ff=0: the xLSTM cells carry their own up/down projections.
"""
from repro.models import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="xlstm-125m", family="ssm",
    n_layers=12, d_model=768, n_heads=4, n_kv_heads=4,
    d_ff=0, vocab=50304,
    ssm=SSMConfig(chunk=256, slstm_every=2),
)

SMOKE = CONFIG.replace(
    name="xlstm-125m-smoke", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, vocab=512, param_dtype="float32",
    compute_dtype="float32", remat="none", ssm=SSMConfig(chunk=16),
)
