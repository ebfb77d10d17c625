"""Plain reference of Moonlight-16B-A3B on one expert-parallel rank's share,
and the comparison that decides a serving run's ``correct``.

The forward pass follows the published DeepSeek-V3 block as Moonlight's
``config.json`` sets it (Hugging Face ``DeepseekV3ForCausalLM``): token
embedding; per layer RMSNorm, multi-head latent attention (arXiv:2405.04434
§2.1, no query LoRA) in its expanded form — ``q = x W_q`` split per head
into 128 columns without and 64 with RoPE, ``[c, k_r] = x W_kva``, ``c``
RMS-normalized, ``[k_nope, v] = c W_kvb`` per head, RoPE (``rotate_half``,
base ``rope_theta``) on ``q_rope`` and on ``k_r``, which every head shares,
scores over √192 under a causal mask — residual, RMSNorm, then the FFN,
residual; final RMSNorm and an untied ``lm_head``.  The first
``first_k_dense_replace`` layers have a dense SwiGLU FFN; the others route
(arXiv:2412.19437 §2.1.2): sigmoid scores over all
``published["n_routed_experts"]`` experts, each token's top
``num_experts_per_tok`` by score plus the correction bias (``noaux_tc``
with one group), weights the chosen scores normalized to sum 1 times
``routed_scaling_factor``, and the shared experts (one SwiGLU of
``n_shared_experts × moe_intermediate_size``) added.  Only the
``n_routed_experts`` held here (experts ``expert_rank × n_routed_experts``
onwards: 0..7) contribute their routed part, as in the program.  Departure: the published code de-interleaves the RoPE
columns before ``rotate_half``; with random weights that is a permutation of
``W_q``'s and ``W_kva``'s RoPE columns, so plain ``rotate_half`` is used
here and in the program alike.

It runs in float32 at the highest matmul precision, one sequence at a
time, every held expert over every token (weighted by zero where not
chosen).  It imports nothing of the program; ``params`` is the weight tree
the benchmark made from the seed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec

#: the comparison is the serving configurations' one
_phi3 = spec.reference("phi3-mini-3.8b-bsffn")
served_positions = _phi3.served_positions
logit_gaps = _phi3.logit_gaps


def _dot(eq: str, a, b):
    """One contraction in float32 at the highest precision."""
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, H, D); ``rotate_half`` rotary embedding at positions 0..T-1."""
    t, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _swiglu(p, a):
    g = _dot("td,df->tf", a, p["gate"]["w"])
    u = _dot("td,df->tf", a, p["up"]["w"])
    return _dot("tf,fd->td", jax.nn.silu(g) * u, p["down"]["w"])


def _mla(cfg: Dict, p, a):
    t = a.shape[0]
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = _dot("td,de->te", a, p["wq"]["w"]).reshape(t, h, dn + dr)
    kv_a = _dot("td,de->te", a, p["wkv_a"]["w"])
    c = _rms(kv_a[:, :r], p["kv_norm"]["scale"], eps)
    k_r = _rope(kv_a[:, None, r:], cfg["rope_theta"])
    kv = _dot("tr,re->te", c, p["wkv_b"]["w"]).reshape(t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])],
                        -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, h, dr))],
                        -1)
    s = _dot("qhd,khd->hqk", q, k) / np.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = _dot("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:])
    return _dot("te,ed->td", o.reshape(t, h * dv), p["wo"]["w"])


def _held_margin(cfg: Dict, sel):
    """How far each token's choice of held experts lies from a tie: over
    the held experts, the least distance of ``score + bias`` from the
    other side of the top-k boundary (the k-th choice for an expert left
    out, the (k+1)-th for one chosen).  Near zero, a rounding of the scores
    can swap a held expert in or out of the choice."""
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first = cfg["expert_rank"] * held
    top = jax.lax.top_k(sel, k + 1)[0]
    kth, next_ = top[:, k - 1:k], top[:, k:k + 1]
    own = sel[:, first:first + held]
    return jnp.where(own >= kth, own - next_, kth - own).min(-1)


def _moe(cfg: Dict, p, a):
    """The held experts' routed part plus the shared experts, and each
    token's :func:`_held_margin`."""
    held = cfg["n_routed_experts"]
    first = cfg["expert_rank"] * held
    scores = jax.nn.sigmoid(_dot("td,de->te", a, p["router"]["w"]))
    sel = scores + p["score_bias"]
    _, idx = jax.lax.top_k(sel, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, -1)
    w = chosen / chosen.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]
    weight = jnp.zeros_like(scores).at[
        jnp.arange(a.shape[0])[:, None], idx].set(w)[:, first:first + held]
    g = _dot("td,edf->tef", a, p["gate"])
    u = _dot("td,edf->tef", a, p["up"])
    y = _dot("tef,efd->ted", jax.nn.silu(g) * u, p["down"])
    return (_dot("ted,te->td", y, weight) + _swiglu(p["shared"], a),
            _held_margin(cfg, sel))


def _forward(cfg: Dict, params, tokens):
    """Logits (T, vocab_size) of one token sequence, and each position's
    least :func:`_held_margin` over the MoE layers (T,)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(ffn):
        def body(x, p):
            x = x + _mla(cfg, p["attn"], _rms(x, p["norm1"]["scale"], eps))
            y, margin = ffn(p, _rms(x, p["norm2"]["scale"], eps))
            return x + y, margin
        return body

    x, _ = jax.lax.scan(
        layer(lambda p, a: (_swiglu(p["mlp"], a), None)), x, params["dense"])
    x, margin = jax.lax.scan(layer(lambda p, a: _moe(cfg, p["moe"], a)), x,
                             params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    head = params["lm_head"]["table"][:cfg["vocab_size"]]
    return _dot("td,vd->tv", x, head), margin.min(0)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: Tuple):
    return jax.jit(functools.partial(_forward, dict(cfg_items)))


def forward(cfg: Dict, params, seq: np.ndarray,
            bucket: int = 256) -> Tuple[np.ndarray, np.ndarray]:
    """Float32 logits of ``seq`` at every position, and each position's
    least held-expert margin (:func:`_held_margin`) over the MoE layers.
    The sequence is padded at its end to a multiple of ``bucket`` (causal
    attention keeps the padding out of every real position) so that few
    programs compile."""
    n = int(seq.size)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = seq
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str)) and v is not None))
    with jax.default_matmul_precision("highest"):
        out, margin = _compiled(key)(params, jnp.asarray(padded))
    return (np.asarray(out[:n], np.float64),
            np.asarray(margin[:n], np.float64))


def compare(cfg: Dict, params,
            samples: Sequence[Tuple[np.ndarray, np.ndarray]]
            ) -> Dict[str, float]:
    """The numbers compared for ``correct`` over served ``(prompt, tokens)``
    samples, from the gaps of the served tokens below the reference's best
    (``logit_gaps``).

    A position where some MoE layer's choice of held experts lies within
    ``cfg["near_tie"]`` of a tie (its :func:`_held_margin`, in the
    reference's own float32 scores) is a near-tie: there the program's
    bf16 activations may choose the other expert, which moves that
    position's logits as far as float8 experts do.  ``mean_logit_gap`` is
    the mean gap over the other positions, ``tokens_compared`` their
    count; ``near_ties`` counts the near-ties, and the widest gaps off and
    on them (``max_logit_gap``, ``max_logit_gap_near_ties``) are reported
    for reading only: a choice swapped at one position also reaches the
    later positions through the latent cache, so the widest gap off the
    near-ties does not part a sound run from a float8 one either."""
    gaps, margins = [], []
    for prompt, out in samples:
        seq, pos = served_positions(prompt, out)
        lg, margin = forward(cfg, params, seq)
        gaps.append(logit_gaps(lg, pos, out))
        margins.append(margin[pos])
    g, m = np.concatenate(gaps), np.concatenate(margins)
    far = m >= cfg["near_tie"]
    return {"mean_logit_gap": float(g[far].mean()) if far.any() else math.inf,
            "tokens_compared": int(far.sum()),
            "near_ties": int((~far).sum()),
            "max_logit_gap": float(g[far].max()) if far.any() else math.inf,
            "max_logit_gap_near_ties": float(g[~far].max()) if (~far).any()
            else 0.0}
