"""Operations and bytes that a call needs, from shapes and patterns alone.

These counts are the benchmark's yardstick: they never read a schedule,
a plan or anything else the program builds, so a change to the program
cannot move them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class SpgemmWork:
    a_blocks: int
    b_blocks: int
    c_blocks: int
    products: int          # block products: pairs (A[i,k], B[k,j]) both stored
    flops: float           # 2 * bm * bk * bn per block product
    bytes: float           # A, B and C stored blocks once each

    def least_seconds(self, flops_per_s: float, bytes_per_s: float) -> float:
        """Roofline bound: the larger of compute and memory time."""
        return max(self.flops / flops_per_s, self.bytes / bytes_per_s)


def spgemm_work(a_brow: Sequence[int], a_bcol: Sequence[int],
                b_brow: Sequence[int], b_bcol: Sequence[int],
                block: Sequence[int], itemsize: int) -> SpgemmWork:
    """Counts for C = A @ B with BSR patterns given as block coordinates
    and blocks of shape ``(bm, bk)`` for A and ``(bk, bn)`` for B."""
    bm, bk, bn = block
    a_brow, a_bcol = np.asarray(a_brow, np.int64), np.asarray(a_bcol, np.int64)
    b_brow, b_bcol = np.asarray(b_brow, np.int64), np.asarray(b_bcol, np.int64)
    # group B's blocks by their row k; every A block (i, k) meets each
    order = np.argsort(b_brow, kind="stable")
    kk = b_brow[order]
    starts = np.searchsorted(kk, a_bcol, side="left")
    ends = np.searchsorted(kk, a_bcol, side="right")
    counts = ends - starts
    products = int(counts.sum())
    i = np.repeat(a_brow, counts)
    offs = np.arange(products) - np.repeat(np.cumsum(counts) - counts, counts)
    j = b_bcol[order][np.repeat(starts, counts) + offs]
    ncols = int(b_bcol.max()) + 1 if b_bcol.size else 1
    c_blocks = int(np.unique(i * ncols + j).size)
    return SpgemmWork(
        a_blocks=int(a_brow.size), b_blocks=int(b_brow.size),
        c_blocks=c_blocks, products=products,
        flops=2.0 * bm * bk * bn * products,
        bytes=float((a_brow.size * bm * bk + b_brow.size * bk * bn
                     + c_blocks * bm * bn) * itemsize))


def decoder_flops_per_token(cfg: Dict, ffn_blocks: Sequence[int],
                            position: np.ndarray) -> np.ndarray:
    """Model FLOPs of one forward token at each 0-based ``position`` of a
    decoder-only transformer in Hugging Face config keys
    (``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
    ``num_hidden_layers``, ``vocab_size``).  The FFN counts only its stored
    blocks (``ffn_blocks``: per projection, blocks of ``ffn_block``
    squared); attention counts the causal context, ``position + 1`` keys.
    Embedding lookup, norms and softmax are not counted."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    layers = cfg["num_hidden_layers"]
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = sum(ffn_blocks) * cfg["ffn_block"] ** 2
    dense = 2.0 * layers * (proj + ffn) + 2.0 * d * cfg["vocab_size"]
    attn = 4.0 * layers * h * hd * (np.asarray(position, np.float64) + 1)
    return dense + attn
