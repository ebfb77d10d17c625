"""Plain reference of phi3-mini with a block-sparse FFN, and the comparison
that decides a serving run's ``correct``.

The forward pass follows the published Phi-3 description (arXiv:2404.14219;
Hugging Face ``Phi3ForCausalLM``): token embedding; per layer RMSNorm,
multi-head attention with rotary embeddings (``rotate_half`` form, base
``rope_theta``) under a causal mask, residual, RMSNorm, SwiGLU FFN
``down(silu(gate(x)) * up(x))``, residual; final RMSNorm and an untied
``lm_head``.  It runs in float32 at the highest matmul precision, one
sequence at a time, scanned layer by layer.  The FFN weights are the
configuration's pruned ones: each projection is rebuilt dense from its
stored 128x128 blocks at the pattern the configuration states
(:func:`pattern`), so the reference never reads a table the program made.
The sliding window (2047) is wider than any sequence served here, so
attention is plainly causal.

It imports nothing of the program.  ``params`` is the weight tree the
benchmark made from the seed and handed to the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PROJECTIONS = ("up", "gate", "down")


def pattern(cfg: Dict) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Block coordinates ``(brow, bcol)`` of each FFN projection, stored
    ``(d_out, d_in)``, as the configuration's ``ffn_pattern`` states."""
    d, ff, b = cfg["hidden_size"], cfg["intermediate_size"], cfg["ffn_block"]
    shapes = {"up": (ff, d), "gate": (ff, d), "down": (d, ff)}
    keys = jax.random.split(jax.random.PRNGKey(cfg["ffn_pattern_key"]), 3)
    out = {}
    for proj, k in zip(PROJECTIONS, keys):
        rng = np.random.default_rng(np.asarray(jax.random.key_data(k))[-1])
        d_out, d_in = shapes[proj]
        mask = rng.random((d_out // b, d_in // b)) < cfg["ffn_density"]
        brow, bcol = np.nonzero(mask)
        out[proj] = (brow.astype(np.int32), bcol.astype(np.int32))
    return out


def _dot(eq: str, a, b):
    """One contraction in float32 at the highest precision."""
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, H, D); Hugging Face ``rotate_half`` rotary embedding."""
    t, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _dense(blocks, brow, bcol, shape, b):
    """(n, b, b) stored blocks -> the dense (d_out, d_in) weight."""
    gm, gk = shape[0] // b, shape[1] // b
    w = jnp.zeros((gm, gk, b, b), jnp.float32).at[brow, bcol].set(blocks)
    return w.transpose(0, 2, 1, 3).reshape(shape)


def _forward(cfg: Dict, pat, params, tokens):
    """Logits (T, vocab_size) of one token sequence."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h = cfg["num_attention_heads"]
    hd = d // h
    eps, b = cfg["rms_norm_eps"], cfg["ffn_block"]
    t = tokens.shape[0]
    shapes = {"up": (ff, d), "gate": (ff, d), "down": (d, ff)}
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = _rms(x, p["norm1"]["scale"], eps)
        q = _dot("td,de->te", a, p["attn"]["wq"]["w"]).reshape(t, h, hd)
        k = _dot("td,de->te", a, p["attn"]["wk"]["w"]).reshape(t, h, hd)
        v = _dot("td,de->te", a, p["attn"]["wv"]["w"]).reshape(t, h, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        s = _dot("qhd,khd->hqk", q, k) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = _dot("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + _dot("te,ed->td", o.reshape(t, d), p["attn"]["wo"]["w"])
        a = _rms(x, p["norm2"]["scale"], eps)
        w = {n: _dense(p["mlp"][n]["blocks"], *pat[n], shapes[n], b)
             for n in PROJECTIONS}
        g = _dot("td,fd->tf", a, w["gate"])
        u = _dot("td,fd->tf", a, w["up"])
        x = x + _dot("tf,df->td", jax.nn.silu(g) * u, w["down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    head = params["lm_head"]["table"][:cfg["vocab_size"]]
    return _dot("td,vd->tv", x, head)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: Tuple):
    cfg = dict(cfg_items)
    pat = {k: tuple(jnp.asarray(a) for a in v)
           for k, v in pattern(cfg).items()}
    return jax.jit(functools.partial(_forward, cfg, pat))


def logits(cfg: Dict, params, seq: np.ndarray,
           bucket: int = 256) -> np.ndarray:
    """Float32 logits of ``seq`` at every position.  The sequence is padded
    at its end to a multiple of ``bucket`` (causal attention keeps the
    padding out of every real position) so that few programs compile."""
    n = int(seq.size)
    padded = np.zeros(-(-n // bucket) * bucket, np.int32)
    padded[:n] = seq
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str)) and v is not None))
    with jax.default_matmul_precision("highest"):
        out = _compiled(key)(params, jnp.asarray(padded))
    return np.asarray(out[:n], np.float64)


def served_positions(prompt: np.ndarray, out: np.ndarray):
    """The sequence the served tokens were produced from, and the position
    whose logits chose each served token."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    return seq, np.arange(prompt.size - 1, prompt.size - 1 + out.size)


def logit_gaps(ref: np.ndarray, pos: np.ndarray,
               chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at its position, in standard deviations of the
    reference's logits there, so that the number does not depend on the
    logits' scale (inf for a token outside the vocabulary)."""
    rows = ref[pos]
    ok = (chosen >= 0) & (chosen < rows.shape[1])
    picked = np.where(ok, rows[np.arange(pos.size), np.where(ok, chosen, 0)],
                      -np.inf)
    return (rows.max(axis=1) - picked) / rows.std(axis=1)


def compare(cfg: Dict, params,
            samples: Sequence[Tuple[np.ndarray, np.ndarray]]
            ) -> Dict[str, float]:
    """The numbers compared for ``correct`` over served ``(prompt, tokens)``
    samples: ``max_logit_gap``, the widest gap of a served token below the
    reference's best (:func:`logit_gaps`)."""
    gaps: List[np.ndarray] = []
    for prompt, out in samples:
        seq, pos = served_positions(prompt, out)
        gaps.append(logit_gaps(logits(cfg, params, seq), pos, out))
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "tokens_compared": int(g.size)}
