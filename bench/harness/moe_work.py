"""Operations and bytes of a DeepSeek-V3-block model (latent attention,
leading dense layers, routed and shared experts), from its configuration
file alone, in Hugging Face config keys.

Like ``work.py`` these are the benchmark's yardstick: nothing here reads
what the program builds.  The routed experts' work depends on the routing,
so it is counted per routed row: the program's counters say how many rows
were routed to the held experts and how many experts were loaded.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: bytes of a bf16 element, what the MXU consumes: the least any
#: implementation must move
BF16 = 2


def moe_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_row_flops(cfg: Dict) -> float:
    """One row through one routed expert: up, gate and down."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_load_bytes(cfg: Dict) -> float:
    """One routed expert's up, gate and down weights, once, in bf16."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def expert_row_bytes(cfg: Dict) -> float:
    """One routed row's activations in and out of the three projections
    (up and gate read d and write ff, down reads ff and writes d), bf16."""
    return 3.0 * (cfg["hidden_size"] + cfg["moe_intermediate_size"]) * BF16


def token_flops(cfg: Dict, position: np.ndarray) -> np.ndarray:
    """Model FLOPs of one forward token at each 0-based ``position``,
    without the routed experts: the latent attention's projections and its
    causal attention over ``position + 1`` keys (scores over the nope and
    RoPE widths, values over ``v_head_dim``) in every layer, the dense
    layers' SwiGLU, each MoE layer's router and shared experts, and the
    head.  Embedding lookup, norms and softmax are not counted."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    layers = cfg["num_hidden_layers"]
    proj = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    dense_ffn = 3 * d * cfg["intermediate_size"]
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    router = d * cfg["published"]["n_routed_experts"]
    per_token = (2.0 * layers * proj
                 + 2.0 * cfg["first_k_dense_replace"] * dense_ffn
                 + 2.0 * moe_layers(cfg) * (shared + router)
                 + 2.0 * d * cfg["vocab_size"])
    attn = 2.0 * layers * h * (dn + dr + dv) * (
        np.asarray(position, np.float64) + 1)
    return per_token + attn
