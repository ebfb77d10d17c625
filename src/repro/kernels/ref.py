"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the mathematical ground truth the kernels are validated
against (interpret mode on CPU, shape/dtype sweeps in tests).  No Pallas, no
fancy control flow — just jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Block-sparse matmuls
# ---------------------------------------------------------------------------


def bsr_to_dense(blocks, brow, bcol, grid_m, grid_k):
    """Scatter BSR blocks into a dense matrix (jnp)."""
    nb, bm, bk = blocks.shape
    out = jnp.zeros((grid_m * bm, grid_k * bk), dtype=blocks.dtype)
    def body(i, acc):
        r, c = brow[i], bcol[i]
        return jax.lax.dynamic_update_slice(
            acc,
            (jax.lax.dynamic_slice(acc, (r * bm, c * bk), (bm, bk))
             + blocks[i]).astype(acc.dtype),
            (r * bm, c * bk))
    return jax.lax.fori_loop(0, nb, body, out)


def dequant_blocks_ref(blocks, scales):
    """fp32 blocks from a quantized payload + scales (no-op for
    ``scales=None``) — the oracle-side mirror of the kernels' in-kernel
    dequantization.  1-D scales are per block, 2-D are per block row
    (rowwise mode)."""
    blocks = blocks.astype(jnp.float32)
    if scales is None:
        return blocks
    scales = scales.astype(jnp.float32)
    if scales.ndim == 2:
        return blocks * scales[:, :, None]
    return blocks * scales[:, None, None]


def spmm_ref(blocks, brow, bcol, grid_m, grid_k, b_dense,
             transpose_lhs: bool = False, scales=None):
    """C = BSR(A) @ B (or BSR(A)ᵀ @ B), computed densely.

    ``brow``/``bcol``/``grid_m``/``grid_k`` always describe the *stored* A;
    ``transpose_lhs`` contracts along its rows instead (the backward-pass
    oracle reads the forward storage, mirroring the kernel's zero-copy
    transpose mode).  ``scales`` dequantizes a quantized block payload.
    """
    a = bsr_to_dense(dequant_blocks_ref(blocks, scales), brow, bcol,
                     grid_m, grid_k)
    if transpose_lhs:
        a = a.T
    return (a.astype(jnp.float32) @ b_dense.astype(jnp.float32))


def spgemm_ref(a_blocks, a_brow, a_bcol, a_grid, b_blocks, b_brow, b_bcol,
               b_grid, c_brow, c_bcol, a_scales=None, b_scales=None):
    """C blocks (at the symbolic pattern positions) of BSR(A) @ BSR(B)."""
    gm, gk = a_grid
    gk2, gn = b_grid
    bm = a_blocks.shape[1]
    bk = a_blocks.shape[2]
    bn = b_blocks.shape[2]
    a = bsr_to_dense(dequant_blocks_ref(a_blocks, a_scales), a_brow, a_bcol,
                     gm, gk)
    b = bsr_to_dense(dequant_blocks_ref(b_blocks, b_scales), b_brow, b_bcol,
                     gk2, gn)
    c = a.astype(jnp.float32) @ b.astype(jnp.float32)
    def gather(i):
        return jax.lax.dynamic_slice(c, (c_brow[i] * bm, c_bcol[i] * bn), (bm, bn))
    return jax.vmap(gather)(jnp.arange(c_brow.shape[0]))


def moe_gemm_ref(x, w, chunk_expert, chunk_rows):
    """Grouped GEMM: rows of x are chunked; chunk c uses expert weight
    w[chunk_expert[c]].  x: (C*rows, d_in), w: (E, d_in, d_out).  Every
    chunk is computed (a skipped chunk's rows are zero, so are its
    products); float32 at the highest precision."""
    n_chunks = chunk_expert.shape[0]
    d_out = w.shape[-1]
    def per_chunk(c):
        xs = jax.lax.dynamic_slice(x, (c * chunk_rows, 0), (chunk_rows, x.shape[1]))
        return jnp.dot(xs.astype(jnp.float32),
                       w[chunk_expert[c]].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    out = jax.vmap(per_chunk)(jnp.arange(n_chunks))
    return out.reshape(n_chunks * chunk_rows, d_out)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def mha_ref(q, k, v, *, causal: bool = True, window: int | None = None,
            scale: float | None = None):
    """Multi-head attention oracle.

    q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D) with H % Hkv == 0 (GQA).
    ``window`` masks keys further than `window` positions behind the query
    (local attention). Query positions are assumed to be the last Tq
    positions of the Tk-long context (decode/prefill consistent).
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(tq)[:, None] + (tk - tq)
    k_pos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrence)
# ---------------------------------------------------------------------------


def rg_lru_ref(x, a_gate, x_gate, a_param, h0=None, c: float = 8.0):
    """RG-LRU oracle:  h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)

    a_t = exp(-c · softplus(a_param) ⊙ σ(a_gate_t)),  i_t = σ(x_gate_t).
    x, a_gate, x_gate: (B, T, D); a_param: (D,). Returns (out, h_T).
    """
    log_a = -c * jax.nn.softplus(a_param)[None, None, :] * jax.nn.sigmoid(a_gate)
    a = jnp.exp(log_a.astype(jnp.float32))
    gated_x = (jax.nn.sigmoid(x_gate) * x).astype(jnp.float32)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    xb = beta * gated_x
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), jnp.float32)
    def step(h, inp):
        a_t, xb_t = inp
        h = a_t * h + xb_t
        return h, h
    hT, hs = jax.lax.scan(step, h0, (a.transpose(1, 0, 2), xb.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2), hT


# ---------------------------------------------------------------------------
# RWKV6-style time mix (data-dependent decay linear attention)
# ---------------------------------------------------------------------------


def rwkv6_ref(r, k, v, w, u, state0=None):
    """RWKV-6 (Finch) time-mix oracle.

    r,k,v: (B, T, H, D); w: (B, T, H, D) data-dependent log-decay (<0);
    u: (H, D) bonus. State S: (B, H, D, D). Returns (out (B,T,H,D), S_T).
    out_t = r_t · (S + u ⊙ (k_tᵀ v_t));  S ← diag(e^{w_t}) S + k_tᵀ v_t.
    """
    b, t, h, d = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, d, d), jnp.float32)
    rf = r.astype(jnp.float32).transpose(1, 0, 2, 3)
    kf = k.astype(jnp.float32).transpose(1, 0, 2, 3)
    vf = v.astype(jnp.float32).transpose(1, 0, 2, 3)
    wf = w.astype(jnp.float32).transpose(1, 0, 2, 3)
    uf = u.astype(jnp.float32)
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp           # (B,H,D)
        kv = jnp.einsum("bhi,bhj->bhij", k_t, v_t)
        out = jnp.einsum("bhi,bhij->bhj", r_t, S + uf[None, :, :, None] * kv)
        S = jnp.exp(w_t)[..., None] * S + kv
        return S, out
    S_T, outs = jax.lax.scan(step, state0, (rf, kf, vf, wf))
    return outs.transpose(1, 0, 2, 3), S_T
