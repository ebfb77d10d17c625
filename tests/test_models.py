"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + no NaNs (the full configs are exercised only via the
dry-run)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, REGISTRY, reduced_config
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, t=64):
    batch = {"tokens": jnp.arange(b * t).reshape(b, t) % cfg.vocab}
    batch["targets"] = jnp.roll(batch["tokens"], -1, axis=1)
    if cfg.family == "vlm":
        batch["vis_embeds"] = jnp.ones(
            (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "enc_dec":
        batch["enc_embeds"] = jnp.ones(
            (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_loss(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    logits, aux = jax.jit(model.forward)(
        params, batch["tokens"], vis_embeds=batch.get("vis_embeds"),
        enc_embeds=batch.get("enc_embeds"))
    t_total = batch["tokens"].shape[1] + (
        cfg.n_frontend_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (2, t_total, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    loss, _ = jax.jit(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    # random-init loss ≈ ln(padded_vocab) sanity band
    assert 0.5 * np.log(cfg.padded_vocab) < float(loss) < 2.5 * np.log(cfg.padded_vocab)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_decode(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    params = model.init(KEY)
    cache = model.init_cache(2, 128)
    tok = jnp.zeros((2, 1), jnp.int32)
    step = jax.jit(model.decode_step)
    logits, cache = step(params, cache, tok, jnp.int32(0))
    logits2, cache = step(params, cache, tok, jnp.int32(1))
    assert logits.shape == (2, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


@pytest.mark.parametrize("arch", ["granite-3-8b", "llama4-maverick-400b-a17b",
                                  "recurrentgemma-9b", "rwkv6-1.6b",
                                  "whisper-tiny"])
def test_arch_gradients(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    grads = jax.jit(jax.grad(lambda p, b: model.loss_fn(p, b)[0]))(params, batch)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                               for g in jax.tree.leaves(grads))))
    assert np.isfinite(gnorm) and gnorm > 0


#: one decoder per kind of decode cache: full bf16 K/V, the int8 K/V cache
#: with its scales, MoE, MLA's latent cache beside a leading dense layer and
#: the expert share with shared experts, the hybrid family's recurrent state
#: beside its ``local`` ring buffer, and rwkv's recurrent state
DECODE_CASES = {
    "granite-3-8b": ("granite-3-8b", {}),
    "int8-kv": ("granite-3-8b", {"kv_cache_dtype": "int8"}),
    "moe": ("phi3.5-moe-42b-a6.6b", {}),
    "moonlight": ("moonlight-16b-a3b", {"experts_held": 2}),
    "hybrid-ring": ("recurrentgemma-9b", {}),
    "rwkv": ("rwkv6-1.6b", {}),
}


def _decode_tokens(model, params, cache, toks, start):
    """Feed ``toks`` (B, n) one token a step; row ``r`` sits at position
    ``start[r]`` before the first (a shared scalar when ``start`` is an
    int).  Returns the last step's logits and the cache."""
    step = jax.jit(model.decode_step)
    for i in range(toks.shape[1]):
        logits, cache = step(params, cache, toks[:, i:i + 1],
                             jnp.asarray(start, jnp.int32) + i)
    return logits, cache


@pytest.mark.parametrize("per_row", [False, True],
                         ids=["shared_pos", "per_row_pos"])
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_matches_forward(case, per_row):
    """Greedy decode over a prompt must produce the same last-token logits
    as a full forward pass (cache correctness).  ``per_row``: the rows sit
    at different positions, as ``Engine`` sends them — row 1 decodes its
    first ``off`` tokens alone, then both rows decode together at per-row
    ``(B,)`` positions."""
    arch, over = DECODE_CASES[case]
    cfg = dataclasses.replace(reduced_config(REGISTRY[arch]), attn_chunk=32,
                              **over)
    model = build_model(cfg)
    params = model.init(KEY)
    b, t, off = 2, 24, (11 if per_row else 0)
    # the ring of the hybrid case (local_window 32) wraps at 24 + 11
    toks = (jnp.arange(b * (t + off)).reshape(b, t + off) * 7) % cfg.vocab
    seqs = [toks[0, :t], toks[1]]
    if per_row:
        _, lead = _decode_tokens(model, params, model.init_cache(1, 64),
                                 toks[1:, :off], 0)
        cache = jax.tree.map(lambda a, r: jnp.concatenate([a, r], axis=1),
                             model.init_cache(1, 64), lead)
        logits_dec, _ = _decode_tokens(
            model, params, cache, jnp.stack([seqs[0], seqs[1][off:]]),
            np.array([0, off]))
    else:
        logits_dec, _ = _decode_tokens(model, params, model.init_cache(b, 64),
                                       toks[:, :t], 0)
    for r, seq in enumerate(seqs):
        logits_full, _ = model.forward(params, seq[None])
        np.testing.assert_allclose(
            np.asarray(logits_dec[r], np.float32),
            np.asarray(logits_full[0, -1], np.float32), rtol=2e-2, atol=2e-2)


def test_chunked_prefill_matches_stepwise():
    cfg = reduced_config(REGISTRY["qwen1.5-4b"])
    model = build_model(cfg)
    params = model.init(KEY)
    b, t = 1, 24
    toks = (jnp.arange(b * t).reshape(b, t) * 11) % cfg.vocab
    cache = model.init_cache(b, 64)
    logits_chunk, _ = model.decode_step(params, cache, toks, jnp.int32(0))
    cache2 = model.init_cache(b, 64)
    for i in range(t):
        logits_step, cache2 = model.decode_step(
            params, cache2, toks[:, i:i + 1], jnp.int32(i))
    np.testing.assert_allclose(np.asarray(logits_chunk, np.float32),
                               np.asarray(logits_step, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_sparse_ffn_variant_trains():
    cfg = dataclasses.replace(reduced_config(REGISTRY["phi3-mini-3.8b"]),
                              ffn_block_sparse=True, ffn_block=32,
                              ffn_density=0.5)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    loss, _ = jax.jit(model.loss_fn)(params, batch)
    grads = jax.jit(jax.grad(lambda p, b: model.loss_fn(p, b)[0]))(params, batch)
    gn = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                            for g in jax.tree.leaves(grads))))
    assert np.isfinite(float(loss)) and np.isfinite(gn) and gn > 0


def test_int8_kv_cache_close_to_bf16():
    """Beyond-paper int8 KV cache: greedy-decode logits stay within 5% of
    the bf16 cache path (see EXPERIMENTS.md §Perf cell C4)."""
    base = reduced_config(REGISTRY["granite-3-8b"])
    q8 = dataclasses.replace(base, kv_cache_dtype="int8")
    m_bf, m_q8 = build_model(base), build_model(q8)
    params = m_bf.init(KEY)
    b, t = 2, 16
    toks = (jnp.arange(b * t).reshape(b, t) * 7) % base.vocab
    c_bf = m_bf.init_cache(b, 64)
    c_q8 = m_q8.init_cache(b, 64)
    for i in range(t):
        lo_bf, c_bf = m_bf.decode_step(params, c_bf, toks[:, i:i + 1],
                                       jnp.int32(i))
        lo_q8, c_q8 = m_q8.decode_step(params, c_q8, toks[:, i:i + 1],
                                       jnp.int32(i))
    a = np.asarray(lo_bf, np.float32)
    b_ = np.asarray(lo_q8, np.float32)
    assert np.abs(a - b_).max() / (np.abs(a).max() + 1e-9) < 0.05
