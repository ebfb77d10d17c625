"""Model + shape configuration dataclasses (one <arch>.py per assigned
architecture imports and instantiates these)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | enc_dec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int                 # 0 for attention-free families
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    # multi-head latent attention (DeepSeek-V2 MLA); on when kv_lora_rank > 0
    kv_lora_rank: int = 0         # width of the cached latent c_kv
    qk_nope_head_dim: int = 0     # per-head query/key width without RoPE
    qk_rope_head_dim: int = 0     # RoPE'd width, one key stream for all heads
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0            # routed experts the router scores over
    top_k: int = 0
    moe_d_ff: int = 0             # expert width (0: d_ff)
    n_shared_experts: int = 0     # always-on experts, one SwiGLU of
                                  # n_shared_experts * moe_d_ff
    first_k_dense: int = 0        # leading layers with a dense SwiGLU FFN
    moe_score: str = "softmax"    # softmax | sigmoid (sigmoid: DeepSeek-V3
                                  # selection by score + a correction bias)
    moe_route_scale: float = 1.0  # scale of the routed weights, which are
                                  # the chosen scores normalized to sum 1
    experts_held: int = 0         # the expert share: routed experts
                                  # r*experts_held .. (r+1)*experts_held-1
                                  # live here, r = expert_rank (0: all)
    expert_rank: int = 0          # which share of experts_held this is
    # hybrid (RecurrentGemma): repeating layer pattern
    layer_pattern: Tuple[str, ...] = ()   # e.g. ("rec", "rec", "local")
    local_window: int = 2048
    # enc-dec (whisper)
    enc_layers: int = 0
    dec_layers: int = 0
    # modality frontend stub
    frontend: str = "none"       # none | patch | frame
    n_frontend_tokens: int = 0
    # the paper's technique: block-sparse FFN weights
    ffn_block_sparse: bool = False
    ffn_block: int = 128          # a multiple of 128 on backend="pallas"
    ffn_density: float = 0.25
    # misc
    dtype: str = "bfloat16"
    remat: bool = True
    attn_chunk: int = 1024
    seq_shard: bool = False   # sequence-parallel activations (Megatron SP):
                              # layer-boundary residuals shard T on `model`
    kv_cache_dtype: str = "bfloat16"   # "int8" = quantized KV (beyond-paper:
                              # halves the decode memory-bound roofline term)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 (Megatron-style TP padding)."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this model holds."""
        return self.experts_held or self.n_experts

    @property
    def first_expert(self) -> int:
        """The first routed expert this model holds."""
        return self.expert_rank * self.experts_held

    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    def layer_kind(self, i: int) -> str:
        """Block kind for layer i: attn | moe | rec | local | rwkv."""
        if self.family == "ssm":
            return "rwkv"
        if self.layer_pattern:
            return self.layer_pattern[i % len(self.layer_pattern)]
        if self.n_experts and i >= self.first_k_dense:
            return "moe"
        return "attn"

    def attn_param_count(self) -> int:
        """Parameters of one attention block's projections."""
        d, h = self.d_model, self.n_heads
        if self.kv_lora_rank:
            r, dr = self.kv_lora_rank, self.qk_rope_head_dim
            return (d * h * (self.qk_nope_head_dim + dr) + d * (r + dr)
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        hd = self.hd
        return d * (h * hd) + 2 * d * (self.n_kv * hd) + (h * hd) * d

    def param_count(self) -> int:
        """Approximate total parameters (for roofline MODEL_FLOPS)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        per_layer = 0
        n_layers = self.n_layers if not self.enc_layers else (
            self.enc_layers + self.dec_layers)
        for i in range(n_layers):
            kind = self.layer_kind(i)
            attn = self.attn_param_count()
            if kind == "moe":
                eff = self.expert_d_ff
                per_layer += (attn + self.n_experts * 3 * d * eff
                              + self.n_shared_experts * 3 * d * eff
                              + d * self.n_experts)
            elif kind == "rec":
                per_layer += 4 * d * d + 3 * d * ff  # rglru block + mlp
            elif kind == "rwkv":
                per_layer += 5 * d * d + 2 * d * ff
            elif kind == "local":
                per_layer += attn + 3 * d * ff
            else:
                per_layer += attn + 3 * d * ff
        emb = v * d * (1 if self.tie_embeddings else 2)
        return per_layer + emb

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if not self.n_experts:
            return self.param_count()
        d, ff = self.d_model, self.expert_d_ff
        total = self.param_count()
        moe_layers = sum(1 for i in range(self.n_layers)
                         if self.layer_kind(i) == "moe")
        inactive = moe_layers * (self.n_experts - self.top_k) * 3 * d * ff
        return total - inactive


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int
    accum_steps: int = 1         # gradient-accumulation microbatches (train)


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}
