"""Recurrent blocks: RG-LRU (Griffin/RecurrentGemma) and RWKV-6 (Finch).

These are the attention-free layers of the hybrid/SSM architectures.  The
Segment dataflow does not apply to the recurrences themselves (DESIGN.md
§Arch-applicability); training uses jnp scans, serving can use the fused
Pallas kernel (:mod:`repro.kernels.rg_lru`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (Griffin)
# ---------------------------------------------------------------------------

_CONV_W = 4


def rglru_block_init(key, d_model, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    d = d_model
    return {
        "in_x": layers.dense_init(ks[0], d, d, dtype=dtype),
        "in_g": layers.dense_init(ks[1], d, d, dtype=dtype),
        "conv": jax.random.normal(ks[2], (_CONV_W, d), dtype) * 0.2,
        "a_gate": layers.dense_init(ks[3], d, d, dtype=dtype),
        "x_gate": layers.dense_init(ks[4], d, d, dtype=dtype),
        "a_param": jax.random.uniform(ks[5], (d,), dtype, 0.5, 2.0),
        "out": layers.dense_init(jax.random.fold_in(key, 7), d, d, dtype=dtype),
    }


def rglru_block_compute(p, dt):
    """:func:`rglru_block_apply`'s dense projections in the compute dtype
    ``dt``; the conv taps and ``a_param`` stay as stored."""
    return {k: layers.dense_compute(v, dt)
            if k in ("in_x", "in_g", "a_gate", "x_gate", "out") else v
            for k, v in p.items()}


def _causal_conv(x, w, state=None):
    """Depthwise causal conv, width 4. x: (B,T,D), w: (4,D).
    state: (B, 3, D) trailing context for decode. Returns (y, new_state)."""
    b, t, d = x.shape
    if state is None:
        state = jnp.zeros((b, _CONV_W - 1, d), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + t] * w[i].astype(x.dtype) for i in range(_CONV_W))
    return y, xp[:, -(_CONV_W - 1):]


def rglru_block_apply(p, x, state=None, c: float = 8.0):
    """x: (B,T,D). state: dict(conv, h) for decode. → (out, new_state)."""
    xb = layers.dense_apply(p["in_x"], x)
    gb = layers.dense_apply(p["in_g"], x)
    conv_state = state["conv"] if state is not None else None
    xb, new_conv = _causal_conv(xb, p["conv"], conv_state)
    ag = layers.dense_apply(p["a_gate"], xb)
    xg = layers.dense_apply(p["x_gate"], xb)
    log_a = (-c * jax.nn.softplus(p["a_param"].astype(jnp.float32))[None, None]
             * jax.nn.sigmoid(ag.astype(jnp.float32)))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    gated = beta * (jax.nn.sigmoid(xg.astype(jnp.float32)) * xb.astype(jnp.float32))
    h0 = (state["h"] if state is not None
          else jnp.zeros((x.shape[0], x.shape[2]), jnp.float32))

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    hT, hs = jax.lax.scan(step, h0, (a.transpose(1, 0, 2), gated.transpose(1, 0, 2)))
    hs = hs.transpose(1, 0, 2).astype(x.dtype)
    out = layers.dense_apply(p["out"], hs * jax.nn.gelu(gb))
    return out, {"conv": new_conv, "h": hT}


def rglru_block_state(b, d_model, dtype=jnp.float32):
    return {"conv": jnp.zeros((b, _CONV_W - 1, d_model), dtype),
            "h": jnp.zeros((b, d_model), jnp.float32)}


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) time-mix + channel-mix
# ---------------------------------------------------------------------------


def rwkv_block_init(key, d_model, n_heads, d_ff, dtype=jnp.float32):
    ks = jax.random.split(key, 12)
    d = d_model
    hd = d // n_heads
    lora = max(32, d // 16)
    return {
        "mix": jax.random.uniform(ks[0], (5, d), dtype, 0.0, 1.0),  # r,k,v,w,g
        "wr": layers.dense_init(ks[1], d, d, dtype=dtype),
        "wk": layers.dense_init(ks[2], d, d, dtype=dtype),
        "wv": layers.dense_init(ks[3], d, d, dtype=dtype),
        "wg": layers.dense_init(ks[4], d, d, dtype=dtype),
        "w_lora_a": jax.random.normal(ks[5], (d, lora), dtype) * 0.01,
        "w_lora_b": jax.random.normal(ks[6], (lora, d), dtype) * 0.01,
        "w_bias": jnp.zeros((d,), dtype) - 4.0,   # slow default decay
        "u": jax.random.normal(ks[7], (n_heads, hd), dtype) * 0.1,
        "wo": layers.dense_init(ks[8], d, d, dtype=dtype),
        "ln_x": layers.rmsnorm_init(d, dtype),
        # channel mix
        "cm_mix": jax.random.uniform(ks[9], (2, d), dtype, 0.0, 1.0),
        "cm_k": layers.dense_init(ks[10], d, d_ff, dtype=dtype),
        "cm_v": layers.dense_init(ks[11], d_ff, d, dtype=dtype),
    }


def rwkv_block_compute(p, dt):
    """The RWKV-6 block's dense projections in the compute dtype ``dt``;
    the token-shift mixes, the decay's bias and LoRA, ``u`` and the norm
    stay as stored."""
    return {k: layers.dense_compute(v, dt)
            if k in ("wr", "wk", "wv", "wg", "wo", "cm_k", "cm_v") else v
            for k, v in p.items()}


def _token_shift(x, prev):
    """shifted[t] = x[t-1]; prev fills t=0. x: (B,T,D), prev: (B,D)."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def rwkv_time_mix(p, x, n_heads, state):
    """x: (B,T,D); state: dict(shift (B,D), S (B,H,hd,hd)). → (out, state)."""
    b, t, d = x.shape
    hd = d // n_heads
    xs = _token_shift(x, state["shift"])
    mix = p["mix"].astype(x.dtype)
    def mixed(i):
        return x * mix[i][None, None] + xs * (1 - mix[i])[None, None]
    r = layers.dense_apply(p["wr"], mixed(0)).reshape(b, t, n_heads, hd)
    k = layers.dense_apply(p["wk"], mixed(1)).reshape(b, t, n_heads, hd)
    v = layers.dense_apply(p["wv"], mixed(2)).reshape(b, t, n_heads, hd)
    g = layers.dense_apply(p["wg"], mixed(4))
    # data-dependent decay (Finch): low-rank modulation of the decay bias
    w_raw = (p["w_bias"].astype(jnp.float32)[None, None]
             + jnp.tanh(mixed(3).astype(jnp.float32) @ p["w_lora_a"].astype(jnp.float32))
             @ p["w_lora_b"].astype(jnp.float32))
    # decay in (0,1): w = exp(-softplus(w_raw)) — bounded, data-dependent
    log_w = -jax.nn.softplus(w_raw)
    log_w = log_w.reshape(b, t, n_heads, hd)

    u = p["u"].astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, lw_t = inp                       # (B,H,hd)
        kv = jnp.einsum("bhi,bhj->bhij", k_t.astype(jnp.float32),
                        v_t.astype(jnp.float32))
        out = jnp.einsum("bhi,bhij->bhj", r_t.astype(jnp.float32),
                         S + u[None, :, :, None] * kv)
        S = jnp.exp(lw_t)[..., None] * S + kv
        return S, out

    S_T, outs = jax.lax.scan(
        step, state["S"],
        (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
         v.transpose(1, 0, 2, 3), log_w.transpose(1, 0, 2, 3)))
    outs = outs.transpose(1, 0, 2, 3).reshape(b, t, d).astype(x.dtype)
    outs = layers.rmsnorm_apply(p["ln_x"], outs) * jax.nn.silu(g)
    out = layers.dense_apply(p["wo"], outs)
    return out, {"shift": x[:, -1], "S": S_T}


def rwkv_channel_mix(p, x, state):
    xs = _token_shift(x, state)
    mix = p["cm_mix"].astype(x.dtype)
    xk = x * mix[0][None, None] + xs * (1 - mix[0])[None, None]
    h = jnp.square(jax.nn.relu(layers.dense_apply(p["cm_k"], xk)))
    return layers.dense_apply(p["cm_v"], h), x[:, -1]


def rwkv_block_state(b, d_model, n_heads, dtype=jnp.float32):
    hd = d_model // n_heads
    return {"shift": jnp.zeros((b, d_model), dtype),
            "S": jnp.zeros((b, n_heads, hd, hd), jnp.float32),
            "cm_shift": jnp.zeros((b, d_model), dtype)}
