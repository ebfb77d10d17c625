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


def held_in(a, dt):
    """``a`` held in dtype ``dt``: an array cast, or a
    ``jax.ShapeDtypeStruct`` restated (shape trees build serving programs
    from ``jax.eval_shape`` alone)."""
    if isinstance(a, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(a.shape, dt, sharding=a.sharding)
    return a.astype(dt)


def dense_compute(p, dt):
    """:func:`dense_apply`'s weights in the compute dtype ``dt``: it reads
    ``w`` and ``b`` only cast to its input's dtype, which is ``dt``."""
    return {k: held_in(v, dt) for k, v in p.items()}


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
    """q/k: (B, Tq|Tk, H|Hkv, D); v: (B, Tk, Hkv, Dv). Returns
    (B, Tq, H, Dv), accumulated in f32.

    Online-softmax over KV chunks: peak memory O(Tq·chunk) per head instead
    of O(Tq·Tk).  ``q_offset`` is the absolute position of q[0]; ``kv_len``
    masks padded keys.  Both accept a shared scalar or a per-row ``(B,)``
    vector (continuous batching: every slot at its own position).
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
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
    vs = v.reshape(b, n_chunks, chunk, hkv, dv).transpose(1, 0, 2, 3, 4)

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
    a0 = jnp.zeros((b, h, tq, dv), jnp.float32)
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


def attention_compute(p, dt):
    """:func:`attention_apply`'s weights in the compute dtype ``dt``: all
    four projections are dense."""
    return {k: dense_compute(v, dt) for k, v in p.items()}


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


def _direct_attention(q, k, v, *, q_offset, kv_len, causal, window,
                      k_scale=None, v_scale=None):
    """Unchunked masked attention over one layer's cache as stored (decode
    path, Tq ≤ 8).

    q: (B, t, H, D); k/v: (B, T, Hkv·D) in the cache dtype — bf16, or int8
    with per-position, per-head f32 scales ``k_scale``/``v_scale``
    (B, T, Hkv) that factor out of both dots (column-wise for QKᵀ, folded
    into p for PV), so no dequantized copy is made.

    Both dots read k and v in their stored layout, with f32 accumulation
    via ``preferred_element_type``: QKᵀ contracts the merged minor axis
    against a block-diagonal query (each head's D values in its kv group's
    block, zeros elsewhere), and PV forms every head against the whole
    minor axis and keeps the head's own block.  The zero blocks add exact
    zeros, so the sums are the per-head dots; the cache slice is neither
    relaid out per head nor copied to f32 (an explicit .astype(f32) on it
    gets hoisted out of the layer scan by XLA and materializes the *entire*
    stacked cache in f32)."""
    from repro.sharding import act_constrain
    b, tq, h, d = q.shape
    tk, f = k.shape[1], k.shape[2]
    hkv = f // d
    rep = h // hkv
    dt = jnp.bfloat16 if k.dtype == jnp.int8 else k.dtype
    # own[G, g]: block G of the minor axis belongs to kv group g
    own = jnp.eye(hkv, dtype=bool)[:, None, :, None]
    # qbd[b, q, G·D + e, g·rep + r] = q[b, q, g·rep + r, e] where G == g
    qx = jnp.moveaxis(q.astype(dt).reshape(b, tq, hkv, rep, d), 4, 2)
    qbd = jnp.where(own, qx[:, :, None], 0).reshape(b, tq, f, h)
    s = jnp.einsum("bkf,bqfh->bhqk", k.astype(dt), qbd,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    if k_scale is not None:
        s = s * jnp.repeat(k_scale, rep, axis=2).transpose(0, 2, 1)[:, :, None]
    s = act_constrain(s, "scores_t")   # keep KV timeline sequence-sharded
    mask = _decode_mask(b, tq, tk, q_offset=q_offset, kv_len=kv_len,
                        causal=causal, window=window)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * jnp.repeat(v_scale, rep, axis=2).transpose(0, 2, 1)[:, :, None]
    y = jnp.einsum("bhqk,bkf->bhqf", p.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)
    # y[b, g·rep + r, q, G·D + e] → the head's own block, G == g
    y = y.reshape(b, hkv, rep, tq, hkv, d)
    out = jnp.where(own[:, :, None], y, 0).sum(axis=4)
    out = out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
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


def kv_cache_write(buf, new, layer, pos):
    """Write ``new`` (B, t, F) into layer ``layer`` of the layer-stacked
    cache ``buf`` (L, B, T, F) at time index ``pos``; returns the updated
    stack.  Only the B·t new token rows move: inside the layer scan the
    stack is the scan's carry, so the donated cache is updated in place.

    ``pos`` is a shared scalar (lockstep decode or a prefill chunk: one
    contiguous ``dynamic_update_slice``), a per-row ``(B,)`` offset
    (continuous batching: every slot writes at its own position) or a
    ``(B, t)`` index per token (a ring buffer's slots); the last two are
    one scatter of the B·t rows."""
    new = new.astype(buf.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(buf, new[None],
                                            (layer, zero, pos, zero))
    b, t = new.shape[:2]
    if pos.ndim == 1:
        pos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    return buf.at[layer, jnp.arange(b)[:, None], pos].set(new)


def _quantize_kv(x):
    """Per-position, per-head symmetric int8: (…, D) → int8 (…, D) and f32
    scales (…)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def attention_apply(p, x, *, n_heads, n_kv, head_dim, positions,
                    causal=True, window=None, rope_theta=10000.0,
                    kv_ctx=None, cache=None, cache_layer=None, cache_pos=None,
                    chunk=1024, ring=False):
    """Self-attention (or cross-attention when ``kv_ctx`` is given).

    ``cache``: optional layer-stacked decode cache, dict(k, v) of
    (L, B, T_max, n_kv·hd) (int8: plus scales ``k_s``/``v_s`` of
    (L, B, T_max, n_kv)) — decode mode: writes the current kv into layer
    ``cache_layer`` at ``cache_pos``, then attends over that layer's whole
    cache.  The token write and the attention read use this one layout.
    ``cache_pos`` is a shared scalar or a per-row ``(B,)`` vector — the
    latter is the continuous-batching path where every slot sits at its own
    absolute position.  With ``ring=True`` the cache is a window-sized ring
    buffer (local attention decode: O(window) memory at any context
    length).  Returns (out, new_cache), the new cache being the updated
    stack.
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
    if cache is None:
        out = chunked_attention(q, k, v, causal=causal and kv_ctx is None,
                                window=window, q_offset=0, chunk=chunk)
        out = out.reshape(b, t, n_heads * head_dim)
        return dense_apply(p["wo"], out), None

    t_max = cache["k"].shape[2]
    pos_v = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    new = {"k": k, "v": v}
    if "k_s" in cache:
        # int8-quantized KV cache (beyond-paper, see EXPERIMENTS §Perf):
        # per-position, per-head symmetric scales. Halves the decode
        # memory-bound roofline term (the KV read is the floor).
        (new["k"], new["k_s"]), (new["v"], new["v_s"]) = (_quantize_kv(k),
                                                          _quantize_kv(v))
    # a ring buffer takes each token at its own slot (per-row positions and
    # writes that wrap around the ring, which a block
    # dynamic_update_slice would clamp at the edge)
    at = (jnp.mod(pos_v[:, None] + jnp.arange(t)[None, :], t_max) if ring
          else cache_pos)
    new_cache = {name: kv_cache_write(cache[name], a.reshape(b, t, -1),
                                      cache_layer, at)
                 for name, a in new.items()}
    cur = {name: jax.lax.dynamic_index_in_dim(a, cache_layer, 0,
                                              keepdims=False)
           for name, a in new_cache.items()}
    if ring:
        ck = cur["k"].reshape(b, t_max, n_kv, head_dim)
        cv = cur["v"].reshape(b, t_max, n_kv, head_dim)
        if "k_s" in cur:
            ck = ck.astype(jnp.float32) * cur["k_s"][..., None]
            cv = cv.astype(jnp.float32) * cur["v_s"][..., None]
        last = pos_v + (t - 1)
        idx = jnp.arange(t_max)
        k_pos = last[:, None] - jnp.mod(last[:, None] - idx[None, :], t_max)
        out = ring_decode_attention(q, ck, cv, k_pos, pos_v, window or t_max)
    elif t <= 8:
        # single-token decode: direct masked attention over the full cache
        # masked to each row's own valid length — scores are (B, H, t, T):
        # tiny, and the T axis keeps its sequence-parallel sharding (the
        # chunked scan's reshape would force a reshard)
        out = _direct_attention(q, cur["k"], cur["v"], q_offset=cache_pos,
                                kv_len=cache_pos + t, causal=causal,
                                window=window, k_scale=cur.get("k_s"),
                                v_scale=cur.get("v_s"))
    elif "k_s" in cur:
        # a guard, not an assert: serving stacks routinely run under
        # ``python -O``, which strips asserts — and a silently oversized
        # query here would attend with garbage positions, not crash
        raise ValueError(
            f"int8 KV cache path supports decode-sized queries (t <= 8), "
            f"got t={t}; chunk the prefill (Engine does this via "
            f"prefill_buckets) or use the fp32 cache for long queries")
    else:
        out = chunked_attention(
            q, cur["k"].reshape(b, t_max, n_kv, head_dim),
            cur["v"].reshape(b, t_max, n_kv, head_dim), causal=causal,
            window=window, q_offset=cache_pos, kv_len=cache_pos + t,
            chunk=chunk)
    out = out.reshape(b, t, n_heads * head_dim)
    return dense_apply(p["wo"], out), new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2 MLA, no query LoRA)
# ---------------------------------------------------------------------------


def latent_width(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Columns of one token's cached latent ``[c | k_rope | 0...]``: padded
    with zeros to a whole number of 128-wide lanes.  The TPU lays out an
    array whose minor axis is not lane-aligned with another axis minor, and
    the decode scan would then copy the whole cache in and out of that
    layout every step."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128


def mla_init(key, d_model, n_heads, *, kv_lora_rank, qk_nope_head_dim,
             qk_rope_head_dim, v_head_dim, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dq = qk_nope_head_dim + qk_rope_head_dim
    return {
        "wq": dense_init(k1, d_model, n_heads * dq, dtype=dtype),
        "wkv_a": dense_init(k2, d_model, kv_lora_rank + qk_rope_head_dim,
                            dtype=dtype),
        "kv_norm": rmsnorm_init(kv_lora_rank, dtype),
        "wkv_b": dense_init(k3, kv_lora_rank,
                            n_heads * (qk_nope_head_dim + v_head_dim),
                            dtype=dtype),
        "wo": dense_init(k4, n_heads * v_head_dim, d_model, dtype=dtype),
    }


def mla_compute(p, dt):
    """:func:`mla_apply`'s weights in the compute dtype ``dt``: the four
    projections (``wkv_b`` the absorbed decode also reads cast to the
    query's and the latent's dtype, both ``dt``); ``kv_norm`` is read in
    float32 and stays as stored."""
    return {k: v if k == "kv_norm" else dense_compute(v, dt)
            for k, v in p.items()}


def _mla_expanded(p, q, latent, *, n_heads, qk_nope_head_dim, v_head_dim,
                  q_offset, kv_len, chunk):
    """Causal attention with every head's K and V made from the latent
    ``[c, k_rope, 0...]`` (B, T, latent_width): ``[k_nope | v] = c W_kvb``
    per head, ``k = [k_nope | k_rope]``."""
    b, tk, _ = latent.shape
    r = p["kv_norm"]["scale"].shape[0]
    dr = q.shape[-1] - qk_nope_head_dim
    kv = dense_apply(p["wkv_b"], latent[..., :r]).reshape(
        b, tk, n_heads, qk_nope_head_dim + v_head_dim)
    k_rope = jnp.broadcast_to(latent[:, :, None, r:r + dr],
                              (b, tk, n_heads, dr))
    k = jnp.concatenate([kv[..., :qk_nope_head_dim],
                         k_rope.astype(kv.dtype)], axis=-1)
    return chunked_attention(q, k, kv[..., qk_nope_head_dim:], causal=True,
                             q_offset=q_offset, kv_len=kv_len, chunk=chunk)


def _mla_absorbed(p, q, latent, *, n_heads, qk_nope_head_dim, v_head_dim,
                  q_offset, kv_len):
    """Decode attention read straight from the latent cache (B, T,
    latent_width): ``W_uk`` is folded into the query (``q_nope W_ukᵀ`` is r
    wide, beside ``q_rope`` and zeros against the padding), so scores are
    one dot with the stored latent, and ``W_uv`` is applied to the attended
    latent.  The cache is never expanded."""
    b, tq, h, dq = q.shape
    tk, width = latent.shape[1:]
    r = p["kv_norm"]["scale"].shape[0]
    dt = latent.dtype
    w = p["wkv_b"]["w"].reshape(r, n_heads, qk_nope_head_dim + v_head_dim)
    q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :qk_nope_head_dim],
                       w[..., :qk_nope_head_dim].astype(q.dtype),
                       preferred_element_type=jnp.float32)
    qf = jnp.concatenate([q_lat.astype(dt),
                          q[..., qk_nope_head_dim:].astype(dt)], axis=-1)
    qf = jnp.pad(qf, ((0, 0),) * 3 + ((0, width - qf.shape[-1]),))
    # the stored latent is the dot's row operand with its minor axis
    # contracted, the query (B, t, r + dr, H) the column operand, so the
    # cache keeps one layout for the token write and both reads (as
    # _direct_attention's K and V do)
    s = jnp.einsum("bkf,btfh->bhtk", latent, jnp.swapaxes(qf, 2, 3),
                   preferred_element_type=jnp.float32) / np.sqrt(dq)
    mask = _decode_mask(b, tq, tk, q_offset=q_offset, kv_len=kv_len,
                        causal=True, window=None)
    s = jnp.where(mask[:, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    # attend over the whole stored row (the rope and padding columns'
    # products are dropped after) rather than slicing c out of the cache
    o = jnp.einsum("bhtk,bkf->bhtf", pr.astype(dt), latent,
                   preferred_element_type=jnp.float32)[..., :r]
    out = jnp.einsum("bhtr,rhv->bthv", o.astype(dt),
                     w[..., qk_nope_head_dim:].astype(dt),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def mla_apply(p, x, *, n_heads, qk_nope_head_dim, qk_rope_head_dim,
              v_head_dim, positions, rope_theta, norm_eps, cache=None,
              cache_layer=None, cache_pos=None, chunk=1024):
    """Causal multi-head latent attention.

    ``q = x W_q`` splits per head into ``q_nope`` and ``q_rope``;
    ``[c, k_r] = x W_kva``, ``c`` RMS-normalized; RoPE on ``q_rope`` and on
    ``k_r``, which all heads share; scores ``(q_nope·k_nope + q_rope·k_r) /
    √(nope + rope)``; ``out = concat_h(o_h) W_o``.

    ``cache``: dict(latent) of (L, B, T_max, latent_width) — one stream a
    token and layer.  The chunk's ``[c, RoPE(k_r), 0...]`` is written into
    layer
    ``cache_layer`` at ``cache_pos`` (a scalar or per-row ``(B,)``), then a
    decode step (t ≤ 8) attends in the absorbed form over that layer's
    stored latents and a prefill chunk expands them.  Returns (out,
    new_cache)."""
    b, t, _ = x.shape
    r = p["kv_norm"]["scale"].shape[0]
    q = dense_apply(p["wq"], x).reshape(
        b, t, n_heads, qk_nope_head_dim + qk_rope_head_dim)
    q = jnp.concatenate([q[..., :qk_nope_head_dim],
                         rope(q[..., qk_nope_head_dim:], positions,
                              rope_theta)], axis=-1)
    kv_a = dense_apply(p["wkv_a"], x)
    c = rmsnorm_apply(p["kv_norm"], kv_a[..., :r], norm_eps)
    k_r = rope(kv_a[:, :, None, r:], positions, rope_theta)[:, :, 0]
    latent = jnp.concatenate([c, k_r], axis=-1)
    latent = jnp.pad(latent, ((0, 0), (0, 0), (0, latent_width(
        r, qk_rope_head_dim) - latent.shape[-1])))
    dims = dict(n_heads=n_heads, qk_nope_head_dim=qk_nope_head_dim,
                v_head_dim=v_head_dim)
    new_cache = None
    if cache is None:
        out = _mla_expanded(p, q, latent, q_offset=0, kv_len=None,
                            chunk=chunk, **dims)
    else:
        new_cache = {"latent": kv_cache_write(cache["latent"], latent,
                                              cache_layer, cache_pos)}
        cur = jax.lax.dynamic_index_in_dim(new_cache["latent"], cache_layer,
                                           0, keepdims=False)
        if t <= 8:
            out = _mla_absorbed(p, q, cur, q_offset=cache_pos,
                                kv_len=cache_pos + t, **dims)
        else:
            out = _mla_expanded(p, q, cur, q_offset=cache_pos,
                                kv_len=cache_pos + t, chunk=chunk, **dims)
    out = out.reshape(b, t, n_heads * v_head_dim)
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


def swiglu_compute(p, dt):
    """:func:`swiglu_apply`'s weights in the compute dtype ``dt``: all
    three projections are dense."""
    return {k: dense_compute(v, dt) for k, v in p.items()}


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


def embedding_compute(p, dt):
    """The table in the compute dtype ``dt``: the embedding gathers rows
    and casts them to ``dt``, and :func:`lm_head_apply` casts the table to
    its input's dtype, so both read the same numbers from a cast table."""
    return {"table": held_in(p["table"], dt)}


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
