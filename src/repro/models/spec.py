"""Model configuration — one dataclass covering all assigned families.

Families:
  dense   — decoder-only transformer (GQA, optional qk_norm, no-bias)
  moe     — dense backbone with MoE FFN (top-k, optional dense residual)
  ssm     — xLSTM (alternating mLSTM / sLSTM blocks)
  hybrid  — Zamba2 (Mamba2 backbone + alternating shared attention blocks)
  encdec  — encoder-decoder (seamless: audio frontend stub + text decoder)
  vlm     — pixtral (ViT frontend stub + dense decoder backbone)
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    dense_ff: int = 0             # width of the parallel dense FFN (0: = d_ff)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64        # recurrent state per head/channel
    d_conv: int = 4          # depthwise conv width (mamba)
    expand: int = 2          # d_inner = expand * d_model
    head_dim: int = 64       # mamba2 head dim
    chunk: int = 256         # chunked-scan block length
    slstm_every: int = 2     # xlstm: every k-th block is sLSTM (rest mLSTM)
    n_groups: int = 1        # mamba2: groups sharing B, C and the gated norm


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Zamba2's keys: the layers in ``hybrid_layer_ids`` first run a shared
    attention+MLP block (block ``k % num_mem_blocks`` at the k-th of them),
    each such site with its own rank-``adapter_rank`` MLP adapter."""

    hybrid_layer_ids: tuple[int, ...] = ()
    num_mem_blocks: int = 2
    adapter_rank: int = 128
    # read by nothing: configuration files of the other families written
    # before the published keys above still name them
    attn_every: int = 6
    concat_embedding: bool = True

    def __post_init__(self):
        # a JSON list arrives here; the config must stay hashable
        object.__setattr__(self, "hybrid_layer_ids",
                           tuple(int(i) for i in self.hybrid_layer_ids))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str              # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # attention details
    head_dim: int = 0        # 0 -> d_model // n_heads
    qk_norm: bool = False
    use_bias: bool = False
    rope_theta: float = 1e6
    sliding_window: int = 0  # 0: full attention
    # family extensions
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    hybrid: HybridConfig = HybridConfig()
    n_encoder_layers: int = 0   # encdec only
    tie_embeddings: bool = False
    # numerics / execution policy (overridable per run)
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: str = "full"          # full | dots | none
    attn_impl: str = "flash_xla" # flash_xla | pallas | reference
    flash_block_q: int = 512
    flash_block_kv: int = 1024
    # sub-quadratic? (drives long_500k applicability)
    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(1, self.n_kv_heads)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str                # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                # train | prefill | decode
    seq_len: int
    global_batch: int
    microbatches: int = 1    # gradient-accumulation splits (train only)


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}
