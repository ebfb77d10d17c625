"""The expert-share MoE layer and latent attention against exact oracles
(CPU, small sizes): MLA's absorbed decode against its expanded form, the
shares of an expert-parallel layer adding up to the whole layer, no token
dropped, the bias choosing without weighting, the MoE counters, and
batched MoE serving equal to serving each request alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.backends import use_backend
from repro.configs import REGISTRY, reduced_config
from repro.models import build_model, layers, moe
from repro.runtime import Engine, Request

RNG = np.random.default_rng(0)
KEY = jax.random.PRNGKey(0)
SILU = lambda z: z / (1.0 + np.exp(-z))  # noqa: E731


def _moe_params(n_exp, d=32, ff=16, shared_ff=24, score="sigmoid", seed=0):
    """One MoE layer's parameters, the experts stacked as one layer."""
    p = moe.moe_init(jax.random.PRNGKey(seed), d, ff, n_exp,
                     shared_ff=shared_ff, score=score)
    if score == "sigmoid":
        p["score_bias"] = jnp.asarray(
            np.random.default_rng(seed).normal(0, 0.01, n_exp), jnp.float32)
    return {k: v[None] if k in moe.EXPERT_WEIGHTS else v
            for k, v in p.items()}


def _oracle(p, x, idx, w):
    """Dense per-token oracle: Σ_j w_j E_{idx_j}(x) + shared(x), float64,
    over the experts ``p`` holds (``idx`` already local; -1: not held)."""
    x = np.asarray(x, np.float64)
    g, u, dn = (np.asarray(p[k][0], np.float64)
                for k in ("gate", "up", "down"))
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, wt in zip(np.asarray(idx[n]), np.asarray(w[n])):
            if e >= 0:
                out[n] += wt * ((SILU(x[n] @ g[e]) * (x[n] @ u[e])) @ dn[e])
    if "shared" in p:
        s = {k: np.asarray(p["shared"][k]["w"], np.float64)
             for k in ("gate", "up", "down")}
        out += (SILU(x @ s["gate"]) * (x @ s["up"])) @ s["down"]
    return out


@pytest.mark.parametrize("t", [1, 5])
def test_mla_absorbed_decode_matches_expanded(t):
    """Reading the latent cache with W_uk folded into the query and W_uv
    after attention equals expanding every head's K and V from it."""
    h, r, dn, dr, dv, tk = 4, 32, 16, 8, 12, 40
    p = layers.mla_init(KEY, 64, h, kv_lora_rank=r, qk_nope_head_dim=dn,
                        qk_rope_head_dim=dr, v_head_dim=dv)
    q = jnp.asarray(RNG.standard_normal((2, t, h, dn + dr)), jnp.float32)
    latent = jnp.asarray(RNG.standard_normal((2, tk, r + dr)), jnp.float32)
    pos = jnp.asarray([7, 30], jnp.int32)       # per-row positions
    dims = dict(n_heads=h, qk_nope_head_dim=dn, v_head_dim=dv)
    with jax.default_matmul_precision("highest"):
        absorbed = layers._mla_absorbed(p, q, latent, q_offset=pos,
                                        kv_len=pos + t, **dims)
        expanded = layers._mla_expanded(p, q, latent, q_offset=pos,
                                        kv_len=pos + t, chunk=16, **dims)
    assert absorbed.shape == (2, t, h, dv)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=1e-4, atol=1e-5)


def test_expert_share_parts_add_up_to_the_whole_layer():
    """Eight ranks of two experts each: their routed parts, plus the shared
    experts counted once, are the uncut 16-expert layer."""
    n_exp, held, top_k = 16, 2, 4
    p = _moe_params(n_exp)
    x = jnp.asarray(RNG.standard_normal((2, 12, 32)) * 0.5, jnp.float32)
    kw = dict(top_k=top_k, score="sigmoid", route_scale=2.446, chunk_rows=8)
    with use_backend("interpret"):
        whole, _, _ = moe.moe_apply(p, x, **kw)
        shared = layers.swiglu_apply(p["shared"], x)
        parts = []
        for rank in range(n_exp // held):
            share = {k: v for k, v in p.items() if k != "shared"}
            for k in ("gate", "up", "down"):
                share[k] = p[k][:, rank * held:(rank + 1) * held]
            # the rank's first expert as the model path places it
            first = dataclasses.replace(
                REGISTRY["moonlight-16b-a3b"], n_experts=n_exp,
                experts_held=held, expert_rank=rank).first_expert
            parts.append(moe.moe_apply(share, x, first_expert=first,
                                       **kw)[0])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_no_token_is_dropped(backend):
    """Every token chooses expert 0 (its bias dwarfs every score): 64 rows
    over 8-row chunks fill 8 chunks of one expert, and the layer equals
    the dense oracle — where a capacity would have dropped all but a few."""
    n_exp, top_k = 4, 2
    p = _moe_params(n_exp)
    p["score_bias"] = p["score_bias"].at[0].set(100.0)
    x = jnp.asarray(RNG.standard_normal((4, 16, 32)) * 0.5, jnp.float32)
    with use_backend(backend):
        out, _, counts = moe.moe_apply(p, x, top_k=top_k, score="sigmoid",
                                       route_scale=2.446, chunk_rows=8)
    xf = x.reshape(-1, 32)
    idx, w, _ = moe.route(p, xf, top_k=top_k, score="sigmoid",
                          route_scale=2.446)
    assert (np.asarray(idx)[:, 0] == 0).all()
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32),
                               _oracle(p, xf, idx, w), rtol=1e-4, atol=1e-5)
    assert np.asarray(counts).tolist()[0] == 64 * top_k


def test_score_bias_chooses_but_does_not_weight():
    p = _moe_params(8)
    x = jnp.asarray(RNG.standard_normal((6, 32)), jnp.float32)
    idx0, _, s = moe.route(p, x, top_k=2, score="sigmoid", route_scale=1.0)
    # lift the lowest-scored expert of every token above all others
    worst = np.asarray(jnp.argmin(s, axis=-1))
    bias = np.zeros((6, 8), np.float32)
    bias[np.arange(6), worst] = 10.0
    p2 = dict(p, score_bias=jnp.asarray(bias.max(axis=0)))
    idx, w, s2 = moe.route(p2, x, top_k=2, score="sigmoid", route_scale=2.0)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s))
    assert (np.asarray(idx) != np.asarray(idx0)).any()
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), 2.0 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)


def _moonlight(**over):
    cfg = dataclasses.replace(reduced_config(REGISTRY["moonlight-16b-a3b"]),
                              dtype="float32", **over)
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def test_moe_counters_on_a_known_routing():
    """The bias sends every token to experts 0 and 3, and this model holds
    experts 0 and 1: each program run routes one row a token (to expert
    0), computes one 128-row chunk and loads one expert."""
    cfg, model, params = _moonlight(experts_held=2)
    bias = params["layers"]["moe"]["score_bias"]
    params["layers"]["moe"]["score_bias"] = bias.at[:, jnp.asarray(
        [0, 3])].set(100.0)
    x = jnp.asarray(RNG.standard_normal((1, 10, cfg.d_model)), jnp.float32)
    p1 = {k: v if k in moe.EXPERT_WEIGHTS else jax.tree.map(
        lambda a: a[0], v) for k, v in params["layers"]["moe"].items()}
    _, _, counts = moe.moe_apply(p1, x, top_k=cfg.top_k, score="sigmoid",
                                 chunk_rows=4)
    assert np.asarray(counts).tolist() == [10, 12, 1]

    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8))
    rng = np.random.default_rng(1)
    eng.generate([Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                          max_new_tokens=4) for n in (5, 19, 9)])
    c = eng.counters()
    runs = c["decode_steps"] + c["prefill_chunks"]
    assert c["moe_rows_routed"] == (c["prefill_padded_tokens"]
                                    + eng.slots * c["decode_steps"])
    assert c["moe_rows_computed"] == moe.CHUNK_ROWS * runs
    assert c["moe_expert_loads"] == runs


def test_batched_moe_serving_matches_each_request_alone():
    """Decode rows are independent: a batched mixed-length run through the
    expert share emits, token for token, what each request gets alone."""
    cfg, model, params = _moonlight(experts_held=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (4, 17, 31)]
    alone = []
    for pr in prompts:
        r = Request(prompt=pr.copy(), max_new_tokens=6)
        Engine(model, params, slots=1, max_len=64,
               prefill_buckets=(16, 8)).generate([r])
        alone.append(r.out_tokens.tolist())
    reqs = [Request(prompt=pr.copy(), max_new_tokens=6) for pr in prompts]
    Engine(model, params, slots=2, max_len=64,
           prefill_buckets=(16, 8)).generate(reqs)
    assert [r.out_tokens.tolist() for r in reqs] == alone
