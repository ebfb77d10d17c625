"""Core neural layers (pure JAX, pytree params — no flax).

Conventions:
* params are nested dicts of jnp arrays; init fns take a PRNG key;
* activations default to bf16 compute with f32 norms/softmax/loss;
* attention is an IO-aware *chunked* (flash-style) jnp implementation that
  lowers to a lax.scan over KV blocks — memory-safe at 32k+ context and
  differentiable everywhere.  The Pallas kernels in ``repro.kernels`` are the
  TPU-optimized serving path; both are validated against the same oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Dtype = jnp.dtype


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in, d_out, *, bias=False, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x):
    y = jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def sparse_dense_init(key, d_in, d_out, *, block=128, density=0.25,
                      policy="segment", dtype=jnp.float32):
    """Block-sparse drop-in for :func:`dense_init` via :mod:`repro.api`.

    Returns ``(plan, params)``: the static :class:`~repro.api.SegmentPlan`
    (pass it to :func:`sparse_dense_apply`; it is a pytree, safe to close
    over or thread through jit) and the trainable blocks in the plan's
    storage layout (original BSR block order).

    Both dims must be multiples of ``block`` — the Segment grid is exact,
    so a ragged edge would silently widen the output with untrained
    padding blocks.
    """
    from repro.api import plan_matmul
    from repro.core.formats import BSR
    if d_in % block or d_out % block:
        raise ValueError(f"d_in={d_in} and d_out={d_out} must be multiples "
                         f"of block={block}")
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key))[-1])
    w = BSR.random(rng, (d_out, d_in), (block, block), density,
                   dtype=np.float32)
    plan = plan_matmul(w, policy=policy, with_grad=True)
    scale = 1.0 / np.sqrt(d_in)
    return plan, {"blocks": (plan.lhs_blocks * scale).astype(dtype)}


def sparse_dense_apply(plan, p, x):
    """``x: (..., d_in) → (..., d_out)`` through the Segment SpMM executor."""
    from repro.api import apply_plan
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = apply_plan(plan.with_values(p["blocks"]), x2.T).T
    return y.reshape(*shape[:-1], -1).astype(x.dtype)


def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float = 10000.0):
    """x: (..., T, H, D) rotated along D with positions (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]                          # (..., T, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention in pure jnp — lax.scan over KV blocks
# ---------------------------------------------------------------------------


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_len=None, chunk=1024):
    """q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D). Returns (B, Tq, H, D) f32-acc.

    Online-softmax over KV chunks: peak memory O(Tq·chunk) per head instead
    of O(Tq·Tk).  ``q_offset`` is the absolute position of q[0]; ``kv_len``
    masks padded keys.  Both accept a shared scalar or a per-row ``(B,)``
    vector (continuous batching: every slot at its own position).
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    kv_len = tk if kv_len is None else kv_len
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    chunk = min(chunk, tk)
    pad = (-tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (tk + pad) // chunk
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    q_pos = q_off[:, None] + jnp.arange(tq)[None, :]          # (B, Tq)

    # reshape kv to (n_chunks, B, chunk, Hkv, D) for scan
    ks = k.reshape(b, n_chunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_chunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        ci, k_c, v_c = inp
        if rep > 1:
            k_c = jnp.repeat(k_c, rep, axis=2)
            v_c = jnp.repeat(v_c, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = (k_pos[None, None, :] < kv_len[:, None, None])  # (B, 1, chunk)
        if causal:
            mask = mask & (k_pos[None, None, :] <= q_pos[..., None])
        if window is not None:
            mask = mask & (k_pos[None, None, :] > q_pos[..., None] - window)
        s = jnp.where(mask[:, None], s, -1e30)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    a0 = jnp.zeros((b, h, tq, d), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), ks, vs))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (GQA + RoPE + optional bias / local window)
# ---------------------------------------------------------------------------


def attention_init(key, d_model, n_heads, n_kv, head_dim, *, qkv_bias=False,
                   dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": dense_init(k2, d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": dense_init(k3, d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": dense_init(k4, n_heads * head_dim, d_model, dtype=dtype),
    }


def _decode_mask(b, tq, tk, *, q_offset, kv_len, causal, window):
    """(B, Tq, Tk) validity mask; ``q_offset``/``kv_len`` may be shared
    scalars or per-row ``(B,)`` vectors (per-slot positions)."""
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    q_pos = q_off[:, None] + jnp.arange(tq)[None, :]          # (B, Tq)
    k_pos = jnp.arange(tk)
    mask = k_pos[None, None, :] < kvl[:, None, None]
    if causal:
        mask = mask & (k_pos[None, None, :] <= q_pos[..., None])
    if window is not None:
        mask = mask & (k_pos[None, None, :] > q_pos[..., None] - window)
    return mask


def _direct_attention(q, k, v, *, q_offset, kv_len, causal, window):
    """Unchunked masked attention (decode path, Tq ≤ 8).

    Keeps K/V in their cache dtype and accumulates in f32 via
    ``preferred_element_type`` — an explicit .astype(f32) on the per-layer
    cache slice gets hoisted out of the layer scan by XLA and materializes
    the *entire* stacked cache in f32."""
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    from repro.sharding import act_constrain
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = act_constrain(s, "scores_t")   # keep KV timeline sequence-sharded
    mask = _decode_mask(b, tq, tk, q_offset=q_offset, kv_len=kv_len,
                        causal=causal, window=window)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _direct_attention_q8(q, kq, ks, vq, vs, *, q_offset, kv_len, causal,
                         window):
    """Decode attention over an int8 KV cache with factored scales.

    q: (B,t,H,D); kq/vq: (B,T,Hkv,D) int8; ks/vs: (B,T,Hkv) f32.
    s = (q·kqᵀ) ⊙ ks  and  out = (p ⊙ vs)·vq — the int8 tensors feed the
    dots directly (native int8×bf16 on TPU), no dequantized copy."""
    from repro.sharding import act_constrain
    b, tq, h, d = q.shape
    tk, hkv = kq.shape[1], kq.shape[2]
    rep = h // hkv
    if rep > 1:
        kq = jnp.repeat(kq, rep, axis=2)
        vq = jnp.repeat(vq, rep, axis=2)
        ks = jnp.repeat(ks, rep, axis=2)
        vs = jnp.repeat(vs, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.bfloat16),
                   kq.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = s * ks.transpose(0, 2, 1)[:, :, None, :]        # column-wise dequant
    s = act_constrain(s, "scores_t")
    mask = _decode_mask(b, tq, tk, q_offset=q_offset, kv_len=kv_len,
                        causal=causal, window=window)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = p * vs.transpose(0, 2, 1)[:, :, None, :]         # fold v scales into p
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(jnp.bfloat16),
                     vq.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def ring_decode_attention(q, ck, cv, k_pos, pos, window):
    """Attention over a ring-buffer KV cache.

    q: (B,Tq,H,D); ck/cv: (B,W,Hkv,D); k_pos: (B,W) absolute position held
    by each ring slot (may differ per batch row — continuous batching);
    pos: (B,) absolute position of q[:, 0].  Each query attends only to
    slots in its own (q_pos-window, q_pos] — causal within a multi-token
    write, and slots still holding a previous occupant's junk (k_pos ahead
    of this row's timeline or negative) are masked out."""
    b, tq, h, d = q.shape
    hkv = ck.shape[2]
    rep = h // hkv
    if rep > 1:
        ck = jnp.repeat(ck, rep, axis=2)
        cv = jnp.repeat(cv, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) / np.sqrt(d)
    q_pos = pos[:, None] + jnp.arange(tq)[None, :]            # (B, Tq)
    valid = ((k_pos[:, None, :] <= q_pos[..., None])
             & (k_pos[:, None, :] > q_pos[..., None] - window)
             & (k_pos[:, None, :] >= 0))                       # (B, Tq, W)
    s = jnp.where(valid[:, None], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p_attn, cv.astype(jnp.float32))
    return out.astype(q.dtype)


def kv_cache_write(buf, new, pos):
    """Write ``new`` (B, t, …) into ``buf`` (B, T, …) at time-axis offset
    ``pos`` — a shared scalar (lockstep decode: one contiguous block write)
    or a per-row ``(B,)`` vector (continuous batching: every slot writes at
    its own position; vmapped dynamic-update, one row-local write each)."""
    if getattr(pos, "ndim", 0):
        return jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, 0)
        )(buf, new, pos)
    return jax.lax.dynamic_update_slice_in_dim(buf, new, pos, axis=1)


def attention_apply(p, x, *, n_heads, n_kv, head_dim, positions,
                    causal=True, window=None, rope_theta=10000.0,
                    kv_ctx=None, cache=None, cache_pos=None, chunk=1024,
                    ring=False):
    """Self-attention (or cross-attention when ``kv_ctx`` is given).

    ``cache``: optional dict(k, v) of (B, T_max, n_kv, hd) — decode mode:
    writes current kv at ``cache_pos`` and attends over the whole cache.
    ``cache_pos`` is a shared scalar or a per-row ``(B,)`` vector — the
    latter is the continuous-batching path where every slot sits at its own
    absolute position.  With ``ring=True`` the cache is a window-sized ring
    buffer (local attention decode: O(window) memory at any context
    length).  Returns (out, new_cache).
    """
    from repro.sharding import act_constrain
    b, t, _ = x.shape
    q = act_constrain(
        dense_apply(p["wq"], x).reshape(b, t, n_heads, head_dim), "heads")
    src = x if kv_ctx is None else kv_ctx
    k = act_constrain(
        dense_apply(p["wk"], src).reshape(b, src.shape[1], n_kv, head_dim),
        "heads")
    v = act_constrain(
        dense_apply(p["wv"], src).reshape(b, src.shape[1], n_kv, head_dim),
        "heads")
    if kv_ctx is None and rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    new_cache = None
    if cache is not None and ring:
        w = cache["k"].shape[1]
        pos_v = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        # scatter each token into its ring slot (handles per-row positions
        # and writes that wrap around the ring, which a block
        # dynamic_update_slice would clamp at the edge)
        slot_idx = jnp.mod(pos_v[:, None] + jnp.arange(t)[None, :], w)
        rows = jnp.arange(b)[:, None]
        ck = cache["k"].at[rows, slot_idx].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot_idx].set(v.astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        last = pos_v + (t - 1)
        idx = jnp.arange(w)
        k_pos = last[:, None] - jnp.mod(last[:, None] - idx[None, :], w)
        out = ring_decode_attention(q, ck, cv, k_pos, pos_v, window or w)
    elif cache is not None and "k_s" in cache:
        # int8-quantized KV cache (beyond-paper, see EXPERIMENTS §Perf):
        # per-position, per-head symmetric scales. Halves the decode
        # memory-bound roofline term (the KV read is the floor). Scales
        # factor OUT of both attention einsums — column-wise for QK^T,
        # folded into p for PV — so no dequantized cache copy is ever
        # materialized.
        def quant(x_):
            scale = jnp.max(jnp.abs(x_.astype(jnp.float32)), axis=-1) / 127.0
            scale = jnp.maximum(scale, 1e-8)
            q_ = jnp.clip(jnp.round(x_.astype(jnp.float32) / scale[..., None]),
                          -127, 127).astype(jnp.int8)
            return q_, scale
        kq, ks_new = quant(k)
        vq, vs_new = quant(v)
        ck = kv_cache_write(cache["k"], kq, cache_pos)
        cv = kv_cache_write(cache["v"], vq, cache_pos)
        cks = kv_cache_write(cache["k_s"], ks_new, cache_pos)
        cvs = kv_cache_write(cache["v_s"], vs_new, cache_pos)
        new_cache = {"k": ck, "v": cv, "k_s": cks, "v_s": cvs}
        if t > 8:
            # a guard, not an assert: serving stacks routinely run under
            # ``python -O``, which strips asserts — and a silently oversized
            # query here would attend with garbage positions, not crash
            raise ValueError(
                f"int8 KV cache path supports decode-sized queries (t <= 8), "
                f"got t={t}; chunk the prefill (Engine does this via "
                f"prefill_buckets) or use the fp32 cache for long queries")
        out = _direct_attention_q8(q, ck, cks, cv, cvs,
                                   q_offset=cache_pos, kv_len=cache_pos + t,
                                   causal=causal, window=window)
    elif cache is not None:
        # decode: insert at cache_pos (per-row or shared), attend over the
        # full cache masked to each row's own valid length
        ck = kv_cache_write(cache["k"], k.astype(cache["k"].dtype), cache_pos)
        cv = kv_cache_write(cache["v"], v.astype(cache["v"].dtype), cache_pos)
        new_cache = {"k": ck, "v": cv}
        if t <= 8:
            # single-token decode: direct masked attention — scores are
            # (B, H, t, T): tiny, and the T axis keeps its sequence-parallel
            # sharding (the chunked scan's reshape would force a reshard)
            out = _direct_attention(q, ck, cv, q_offset=cache_pos,
                                    kv_len=cache_pos + t, causal=causal,
                                    window=window)
        else:
            out = chunked_attention(q, ck, cv, causal=causal, window=window,
                                    q_offset=cache_pos, kv_len=cache_pos + t,
                                    chunk=chunk)
    else:
        out = chunked_attention(q, k, v, causal=causal and kv_ctx is None,
                                window=window, q_offset=0, chunk=chunk)
    out = out.reshape(b, t, n_heads * head_dim)
    return dense_apply(p["wo"], out), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "up": dense_init(k1, d_model, d_ff, dtype=dtype),
        "gate": dense_init(k2, d_model, d_ff, dtype=dtype),
        "down": dense_init(k3, d_ff, d_model, dtype=dtype),
    }


def swiglu_apply(p, x):
    from repro.sharding import act_constrain
    h = jax.nn.silu(act_constrain(dense_apply(p["gate"], x), "ffn")) \
        * act_constrain(dense_apply(p["up"], x), "ffn")
    return dense_apply(p["down"], h)


# ---------------------------------------------------------------------------
# embeddings & loss
# ---------------------------------------------------------------------------


def embedding_init(key, vocab, d_model, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embedding_apply(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def lm_head_apply(p, x):
    """Tied or untied head: x (B,T,D) @ table^T → (B,T,V)."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, p["table"].astype(x.dtype))


def cross_entropy(logits, targets, mask=None):
    """Mean token NLL, numerically stable, vocab-shard friendly.

    Uses one-hot contraction (psum-friendly when vocab is sharded) rather
    than take_along_axis (which would gather across shards); the f32 logits
    and the one-hot both carry explicit vocab-sharded constraints so the
    (B, T, V) intermediates never materialize unsharded.
    """
    from repro.sharding import act_constrain
    logits = act_constrain(logits.astype(jnp.float32), "logits")
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = act_constrain(
        jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32),
        "logits")
    true_logit = jnp.sum(logits * onehot, axis=-1)
    nll = lse - true_logit
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
