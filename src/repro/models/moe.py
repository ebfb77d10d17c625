"""Mixture-of-Experts layer: the expert share, drop-free.

The router scores every routed expert of the layer (``n_experts``), but the
layer holds the weights of a share of them only: routed experts
``first_expert .. first_expert + n_held - 1``, as one rank of an
expert-parallel deployment holds its share (``ModelConfig.experts_held``
experts at rank ``ModelConfig.expert_rank``, so ``first_expert`` is their
product; a config that sets no share holds them all).  Each token's output is the part
its held chosen experts give, plus the shared experts, which every rank
computes alike; the parts the other ranks hold are theirs to add.

Routing (DeepSeek-V3 and the softmax top-k of Switch/Mixtral alike):
``s = score(x W_r)`` over all experts (``softmax`` or ``sigmoid``); each
token chooses the ``top_k`` experts of ``s + b`` (``b`` the correction
bias, in ``score_bias`` for sigmoid scoring — it changes the choice and
never the weights); the weights are the chosen scores normalized to sum 1,
times ``route_scale``.

Dispatch is the Segment grouped GEMM (:mod:`repro.kernels.moe_gemm`): the
routes to held experts are sorted by expert into chunks of ``chunk_rows``
rows (SELECTA's shared-operand grouping), each expert's last chunk padded
(folding), so every routed token gets its row — nothing is dropped, at
static shapes.  Each token's rows are its own, so each row of a decode
batch is computed as if alone.  The backend (:mod:`repro.api.backends`)
picks the compiled kernel, the interpreted kernel or the jnp oracle, over
one chunk layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api.backends import default_backend
from repro.kernels.moe_gemm import build_chunks, grouped_matmul
from . import layers

#: rows a grouped-GEMM chunk holds
CHUNK_ROWS = 128

#: the expert weights the grouped GEMM reads layer-stacked
EXPERT_WEIGHTS = ("gate", "up", "down")

#: what :func:`moe_apply` counts, in the order of its ``counts``: routes to
#: held experts (rows the kernels multiply for real), rows the kernels
#: compute (whole used chunks), and held experts with at least one route
#: (expert weight loads)
COUNTERS = ("moe_rows_routed", "moe_rows_computed", "moe_expert_loads")


def moe_init(key, d_model, d_ff, n_experts, *, n_held=None, shared_ff=0,
             score="softmax", dtype=jnp.float32):
    """Router over ``n_experts``, weights of ``n_held`` of them (all by
    default), and a shared SwiGLU of width ``shared_ff`` when non-zero."""
    n_held = n_experts if n_held is None else n_held
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s_in = 1.0 / jnp.sqrt(d_model)
    s_ff = 1.0 / jnp.sqrt(d_ff)
    p = {
        "router": layers.dense_init(k1, d_model, n_experts, dtype=dtype),
        "gate": jax.random.normal(k2, (n_held, d_model, d_ff), dtype) * s_in,
        "up": jax.random.normal(k3, (n_held, d_model, d_ff), dtype) * s_in,
        "down": jax.random.normal(k4, (n_held, d_ff, d_model), dtype) * s_ff,
    }
    if score == "sigmoid":
        p["score_bias"] = jnp.zeros((n_experts,), dtype)
    if shared_ff:
        p["shared"] = layers.swiglu_init(k5, d_model, shared_ff, dtype=dtype)
    return p


def moe_compute(p, dt):
    """:func:`moe_apply`'s weights in the compute dtype ``dt``: the shared
    experts (a SwiGLU on the layer's input).  The router and its bias are
    read in float32 and the held experts' stacks by the grouped GEMM as
    stored, so they stay."""
    if "shared" not in p:
        return p
    return {**p, "shared": layers.swiglu_compute(p["shared"], dt)}


def route(p, x, *, top_k: int, score: str = "softmax",
          route_scale: float = 1.0):
    """x: (N, D) → (chosen experts (N, top_k) int32, their weights
    (N, top_k) f32, scores (N, E) f32)."""
    # full float32 (one bf16 pass of the MXU would round the scores that
    # decide the top-k), as the published DeepSeek-V3 gate computes them
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown expert scoring {score!r}")
    sel = scores + p["score_bias"].astype(jnp.float32) \
        if "score_bias" in p else scores
    _, idx = jax.lax.top_k(sel, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * route_scale
    return idx, w, scores


def moe_apply(p, x, *, layer=0, top_k: int, score: str = "softmax",
              route_scale: float = 1.0, first_expert: int = 0,
              chunk_rows: int = CHUNK_ROWS):
    """x: (B, T, D) → (out (B, T, D), aux_loss scalar, counts (3,) int32 in
    the order of :data:`COUNTERS`).

    ``p`` is one layer's parameters, except the held experts' ``gate``,
    ``up`` and ``down``, which stay layer-stacked, ``(L, n_held, ...)``:
    the grouped GEMM reads layer ``layer`` of them where they lie."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    n_exp = p["router"]["w"].shape[1]
    n_held = p["up"].shape[1]
    idx, w, scores = route(p, xf, top_k=top_k, score=score,
                           route_scale=route_scale)

    # Switch-style load-balance auxiliary loss over all tokens and experts
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    ce = jnp.zeros(n_exp).at[idx[:, 0]].add(1.0) / (b * t)
    aux = n_exp * jnp.sum(probs.mean(axis=0) * ce)

    local = idx - first_expert
    held = (local >= 0) & (local < n_held)
    ch = build_chunks(jnp.where(held, local, n_held).reshape(-1), n_held,
                      chunk_rows)
    rows = jnp.zeros((ch.n_rows + 1, d), x.dtype).at[ch.dest].set(
        jnp.repeat(xf, top_k, axis=0))[:-1]
    backend = default_backend()
    at = jnp.asarray(layer, jnp.int32).reshape(1)

    def gemm(a, name):
        return grouped_matmul(a, p[name], ch.chunk_expert, ch.n_used, at,
                              chunk_rows, backend)

    h = (jax.nn.silu(gemm(rows, "gate")) * gemm(rows, "up")).astype(x.dtype)
    y = gemm(h, "down")
    dest = ch.dest.reshape(b * t, top_k)
    y_routes = jnp.where(held[..., None],
                         y[jnp.minimum(dest, ch.n_rows - 1)], 0.0)
    out = jnp.einsum("nkd,nk->nd", y_routes, w).reshape(b, t, d)
    if "shared" in p:
        out = out + layers.swiglu_apply(p["shared"], x).astype(jnp.float32)
    counts = jnp.stack([jnp.sum(ch.counts), ch.n_used[0] * chunk_rows,
                        jnp.sum(ch.counts > 0)]).astype(jnp.int32)
    return out.astype(x.dtype), aux, counts
