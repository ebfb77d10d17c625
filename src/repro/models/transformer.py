"""Unified transformer family: one implementation, ten architectures.

Families (``ModelConfig.family``):
* ``dense`` / ``vlm`` / ``audio-as-decoder`` — GQA attention + SwiGLU (or
  block-sparse Segment) FFN, scanned over layers;
* ``moe``    — GQA or latent (MLA) attention + the expert-share MoE FFN
  on the Segment grouped GEMM, after ``first_k_dense`` dense layers;
* ``hybrid`` — RecurrentGemma: repeating (rec, rec, local-attention) units;
* ``ssm``    — RWKV-6 time-mix/channel-mix;
* ``enc_dec``— Whisper backbone: bidirectional encoder over frame embeddings
  (frontend stubbed per spec) + causal decoder with cross-attention.

Params are pytrees with layer-stacked leaves; layer iteration is
``lax.scan`` (+ optional remat) so the HLO stays compact for the 512-chip
dry-run even at 64 layers.
"""
from __future__ import annotations

import copy
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.formats import QUANT_DTYPES, quantize_blocks
from repro.sharding import act_constrain
from . import layers, moe, recurrent
from .sparse_ffn import SparseMLP


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _sparse_mlp_params(key, sm: SparseMLP, dtype):
    """Fresh trainable blocks for the *shared* sparse schedule (all layers
    prune to the same block pattern; only values differ)."""
    def pb(k, lin):
        n = lin.plan.n_blocks
        bm, bk = lin.plan.block_shape
        return {"blocks": jax.random.normal(k, (n, bm, bk), dtype)
                / np.sqrt(lin.d_in)}
    k1, k2, k3 = jax.random.split(key, 3)
    return {"up": pb(k1, sm.up), "gate": pb(k2, sm.gate),
            "down": pb(k3, sm.down)}


def _is_sparse_mlp_params(p) -> bool:
    """True for a block dict whose ``mlp`` subtree holds SparseMLP leaves
    (``up``/``gate``/``down`` each carrying ``blocks``) rather than dense
    SwiGLU weights."""
    mlp = p.get("mlp") if isinstance(p, dict) else None
    return (isinstance(mlp, dict)
            and all(isinstance(mlp.get(k), dict) and "blocks" in mlp[k]
                    for k in ("up", "gate", "down")))


def _quantize_mlp_params(mlp, dtype: str):
    """Quantize one (layer-stacked) SparseMLP param subtree: each
    projection's fp32 ``blocks`` leaf — any leading stack axes, then
    ``(n_blocks, bm, bk)`` — becomes a payload + per-block (or per-block-row
    for ``*.rowwise`` modes) fp32 ``scales`` leaf with the same stacking."""
    out = {}
    for proj in ("up", "gate", "down"):
        leaf = mlp[proj]
        blocks = np.asarray(leaf["blocks"])
        if ("scales" in leaf
                or np.dtype(blocks.dtype) in QUANT_DTYPES.values()):
            raise ValueError(
                f"params['...']['mlp']['{proj}'] is already quantized "
                f"({blocks.dtype}) — quantize from the fp32 model+params")
        *stack, n, bm, bk = blocks.shape
        q = quantize_blocks(blocks.reshape(-1, bm, bk).astype(np.float32),
                            dtype)
        out[proj] = {
            "blocks": jnp.asarray(q.payload.reshape(blocks.shape)),
            "scales": jnp.asarray(q.scales.reshape(
                tuple(stack) + (n,) + q.scales.shape[1:])),
        }
    return out


# ---------------------------------------------------------------------------
# per-kind block init / apply
# ---------------------------------------------------------------------------


def _attn_init(cfg: ModelConfig, key, dt):
    if cfg.kv_lora_rank:
        return layers.mla_init(
            key, cfg.d_model, cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, dtype=dt)
    return layers.attention_init(key, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                 cfg.hd, qkv_bias=cfg.qkv_bias, dtype=dt)


def _block_init(cfg: ModelConfig, key, kind: str, sparse_mlp: Optional[SparseMLP]):
    dt = jnp.float32
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": layers.rmsnorm_init(d), "norm2": layers.rmsnorm_init(d)}
    k1, k2 = jax.random.split(key)
    if kind in ("attn", "attn_bidir", "local", "cross"):
        p["attn"] = _attn_init(cfg, k1, dt)
        if kind == "cross":
            p["norm_x"] = layers.rmsnorm_init(d)
            p["xattn"] = layers.attention_init(
                jax.random.fold_in(k1, 1), d, cfg.n_heads, cfg.n_kv, cfg.hd,
                qkv_bias=cfg.qkv_bias, dtype=dt)
        if sparse_mlp is not None:
            p["mlp"] = _sparse_mlp_params(k2, sparse_mlp, dt)
        else:
            p["mlp"] = layers.swiglu_init(k2, d, cfg.d_ff, dtype=dt)
    elif kind == "moe":
        p["attn"] = _attn_init(cfg, k1, dt)
        p["moe"] = moe.moe_init(
            k2, d, cfg.expert_d_ff, cfg.n_experts, n_held=cfg.n_held,
            shared_ff=cfg.n_shared_experts * cfg.expert_d_ff,
            score=cfg.moe_score, dtype=dt)
    elif kind == "rec":
        p["rec"] = recurrent.rglru_block_init(k1, d, dtype=dt)
        p["mlp"] = layers.swiglu_init(k2, d, cfg.d_ff, dtype=dt)
    elif kind == "rwkv":
        p = {"norm1": layers.rmsnorm_init(d), "norm2": layers.rmsnorm_init(d),
             "rwkv": recurrent.rwkv_block_init(k1, d, cfg.n_heads or 32,
                                               cfg.d_ff, dtype=dt)}
    else:
        raise ValueError(kind)
    return p


def _block_compute(cfg: ModelConfig, p, kind: str,
                   sparse_mlp: Optional[SparseMLP], dt):
    """One block's parameters as :meth:`Transformer.compute_params` holds
    them: each sublayer's own declaration; norms stay as stored, and so
    do the Segment FFN's blocks, which its kernels read."""
    p = dict(p)
    if "attn" in p:
        p["attn"] = (layers.mla_compute if cfg.kv_lora_rank
                     else layers.attention_compute)(p["attn"], dt)
    if "xattn" in p:
        p["xattn"] = layers.attention_compute(p["xattn"], dt)
    if "mlp" in p and (kind == "rec" or sparse_mlp is None):
        p["mlp"] = layers.swiglu_compute(p["mlp"], dt)
    if "moe" in p:
        p["moe"] = moe.moe_compute(p["moe"], dt)
    if "rec" in p:
        p["rec"] = recurrent.rglru_block_compute(p["rec"], dt)
    if "rwkv" in p:
        p["rwkv"] = recurrent.rwkv_block_compute(p["rwkv"], dt)
    return p


def _self_attention(cfg: ModelConfig, p, x, kind: str, *, positions,
                    cache, layer, cache_pos):
    """The block's self-attention sublayer: (h, new kv cache or None)."""
    kv_cache = cache.get("kv") if cache else None
    with jax.named_scope("attn"):
        a = layers.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if cfg.kv_lora_rank:
            return layers.mla_apply(
                p["attn"], a, n_heads=cfg.n_heads,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, positions=positions,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                cache=kv_cache, cache_layer=layer, cache_pos=cache_pos,
                chunk=cfg.attn_chunk)
        return layers.attention_apply(
            p["attn"], a, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.hd, positions=positions,
            causal=(kind != "attn_bidir"),
            window=cfg.local_window if kind == "local" else None,
            rope_theta=cfg.rope_theta, cache=kv_cache, cache_layer=layer,
            cache_pos=cache_pos, chunk=cfg.attn_chunk,
            ring=(kind == "local" and cache is not None))


def _block_apply(cfg: ModelConfig, p, x, kind: str, *, positions,
                 sparse_mlp: Optional[SparseMLP], enc_out=None,
                 cache=None, layer=None, cache_pos=None):
    """Returns (x, aux_loss, new_cache, counts).

    ``cache`` is the block's layer-stacked decode cache and ``layer`` the
    block's index in it; ``new_cache`` is the stack with this layer's
    tokens (KV caches) or state (recurrent blocks) written in.  ``counts``
    is the MoE layer's work (:data:`repro.models.moe.COUNTERS`), zero for
    other blocks."""
    if cfg.seq_shard and cache is None:
        x = act_constrain(x, "seq")
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
    new_cache: Dict[str, Any] = {}
    # the named scopes ("attn", "ffn", "lm_head" in layers.py) are op
    # metadata only: the profiler's op-profile view groups device ops by them
    if kind in ("attn", "attn_bidir", "local", "cross"):
        h, kv = _self_attention(cfg, p, x, kind, positions=positions,
                                cache=cache, layer=layer, cache_pos=cache_pos)
        x = x + h
        if kv is not None:
            new_cache["kv"] = kv
        if kind == "cross":
            with jax.named_scope("attn"):
                hx, xkv = layers.attention_apply(
                    p["xattn"],
                    layers.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps),
                    n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
                    positions=positions, causal=False, rope_theta=0.0,
                    kv_ctx=enc_out, chunk=cfg.attn_chunk)
            x = x + hx
        n2 = layers.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        with jax.named_scope("ffn"):
            if sparse_mlp is not None:
                x = x + sparse_mlp.apply(p["mlp"], n2)
            else:
                x = x + layers.swiglu_apply(p["mlp"], n2)
    elif kind == "moe":
        h, kv = _self_attention(cfg, p, x, kind, positions=positions,
                                cache=cache, layer=layer, cache_pos=cache_pos)
        x = x + h
        if kv is not None:
            new_cache["kv"] = kv
        with jax.named_scope("ffn"):
            h, aux, counts = moe.moe_apply(
                p["moe"], layers.rmsnorm_apply(p["norm2"], x, cfg.norm_eps),
                layer=layer, top_k=cfg.top_k, score=cfg.moe_score,
                route_scale=cfg.moe_route_scale,
                first_expert=cfg.first_expert)
        x = x + h
    elif kind == "rec":
        h, st = recurrent.rglru_block_apply(
            p["rec"], layers.rmsnorm_apply(p["norm1"], x, cfg.norm_eps),
            state=_state_read(cache["rec"], layer) if cache else None)
        x = x + h
        new_cache["rec"] = (_state_write(cache["rec"], layer, st) if cache
                            else st)
        with jax.named_scope("ffn"):
            x = x + layers.swiglu_apply(
                p["mlp"], layers.rmsnorm_apply(p["norm2"], x, cfg.norm_eps))
    elif kind == "rwkv":
        st = (_state_read(cache["rwkv"], layer) if cache
              else recurrent.rwkv_block_state(x.shape[0], cfg.d_model,
                                              cfg.n_heads or 32, x.dtype))
        h, st_tm = recurrent.rwkv_time_mix(
            p["rwkv"], layers.rmsnorm_apply(p["norm1"], x, cfg.norm_eps),
            cfg.n_heads or 32, {"shift": st["shift"], "S": st["S"]})
        x = x + h
        h, cm_shift = recurrent.rwkv_channel_mix(
            p["rwkv"], layers.rmsnorm_apply(p["norm2"], x, cfg.norm_eps),
            st["cm_shift"])
        x = x + h
        st = {"shift": st_tm["shift"], "S": st_tm["S"], "cm_shift": cm_shift}
        new_cache["rwkv"] = (_state_write(cache["rwkv"], layer, st) if cache
                             else st)
    else:
        raise ValueError(kind)
    return x, aux, new_cache, counts


def _split_experts(tree):
    """(the tree without the MoE expert weights, those weights): the
    grouped GEMM reads the layer-stacked experts whole, by layer, so the
    layer scan does not slice them."""
    if not isinstance(tree, dict):
        return tree, {}
    scanned, whole = {}, {}
    for k, v in tree.items():
        if k == "moe":
            scanned[k] = {n: a for n, a in v.items()
                          if n not in moe.EXPERT_WEIGHTS}
            whole[k] = {n: v[n] for n in moe.EXPERT_WEIGHTS}
        else:
            scanned[k], w = _split_experts(v)
            if w:
                whole[k] = w
    return scanned, whole


def _merge(tree, extra):
    if not extra:
        return tree
    return {k: _merge(v, extra.get(k)) if isinstance(v, dict) else v
            for k, v in tree.items()} | {k: v for k, v in extra.items()
                                         if k not in tree}


def _state_read(stack, layer):
    """One layer's recurrent state out of its layer-stacked cache."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        stack)


def _state_write(stack, layer, state):
    """The stack with layer ``layer``'s recurrent state replaced whole
    (O(d) a row: no token granularity to exploit)."""
    return jax.tree.map(
        lambda a, s: jax.lax.dynamic_update_index_in_dim(
            a, s.astype(a.dtype), layer, 0), stack, state)


def _block_cache_init(cfg: ModelConfig, kind: str, b: int, t_max: int, dt):
    """One layer's decode cache.  K and V are (B, T, n_kv·hd): heads and
    head dim merged into one minor axis, so the token write and the
    attention read share a layout with no padding of hd to the lane
    width.  Latent attention caches one stream instead, shared by every
    head: ``[RMSNorm(c_kv), RoPE(k_rope)]`` zero-padded to whole lanes
    (:func:`layers.latent_width`)."""
    def kv(t_len):
        if cfg.kv_lora_rank:
            return {"latent": jnp.zeros((b, t_len, layers.latent_width(
                cfg.kv_lora_rank, cfg.qk_rope_head_dim)), dt)}
        f = cfg.n_kv * cfg.hd
        if cfg.kv_cache_dtype == "int8":
            return {"k": jnp.zeros((b, t_len, f), jnp.int8),
                    "v": jnp.zeros((b, t_len, f), jnp.int8),
                    "k_s": jnp.zeros((b, t_len, cfg.n_kv), jnp.float32),
                    "v_s": jnp.zeros((b, t_len, cfg.n_kv), jnp.float32)}
        return {"k": jnp.zeros((b, t_len, f), dt),
                "v": jnp.zeros((b, t_len, f), dt)}
    if kind in ("attn", "cross", "moe"):
        return {"kv": kv(t_max)}
    if kind == "local":
        return {"kv": kv(min(t_max, cfg.local_window))}
    if kind == "rec":
        return {"rec": recurrent.rglru_block_state(b, cfg.d_model, dt)}
    if kind == "rwkv":
        return {"rwkv": recurrent.rwkv_block_state(b, cfg.d_model,
                                                   cfg.n_heads or 32, dt)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Transformer:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.sparse_mlp: Optional[SparseMLP] = None
        if cfg.ffn_block_sparse:
            # one shared schedule (same pruning pattern every layer)
            self.sparse_mlp, self._sparse_proto = SparseMLP.create(
                jax.random.PRNGKey(17), cfg.d_model, cfg.d_ff,
                block=cfg.ffn_block, density=cfg.ffn_density)
        # layer grouping for scans
        if cfg.family == "enc_dec":
            self.groups = [("enc", "attn_bidir", cfg.enc_layers),
                           ("dec", "cross", cfg.dec_layers)]
        elif cfg.layer_pattern:
            n_units = cfg.n_layers // len(cfg.layer_pattern)
            rem = cfg.n_layers - n_units * len(cfg.layer_pattern)
            self.groups = [("units", tuple(cfg.layer_pattern), n_units)]
            if rem:
                self.groups.append(("tail", tuple(cfg.layer_pattern[:rem]), 1))
        elif cfg.first_k_dense:
            # leading dense layers, then the rest (DeepSeek-V3's
            # first_k_dense_replace): one scan each
            self.groups = [("dense", "attn", cfg.first_k_dense),
                           ("layers", cfg.layer_kind(cfg.first_k_dense),
                            cfg.n_layers - cfg.first_k_dense)]
        else:
            kind = cfg.layer_kind(0)
            self.groups = [("layers", kind, cfg.n_layers)]

    # -- init ---------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": layers.embedding_init(keys[0], cfg.padded_vocab, cfg.d_model),
            "final_norm": layers.rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.embedding_init(keys[1], cfg.padded_vocab,
                                                      cfg.d_model)
        if cfg.frontend != "none":
            params["frontend"] = layers.dense_init(
                keys[2], cfg.d_model, cfg.d_model)
        kidx = 3
        for gi, (name, kinds, n) in enumerate(self.groups):
            gkey = keys[min(kidx + gi, 7)]

            def one(k):
                if isinstance(kinds, tuple):       # hybrid unit
                    sub = {}
                    for j, kd in enumerate(kinds):
                        sub[f"b{j}"] = _block_init(cfg, jax.random.fold_in(k, j),
                                                   kd, self.sparse_mlp)
                    return sub
                return _block_init(cfg, k, kinds, self.sparse_mlp)

            lkeys = jax.random.split(gkey, n)
            params[name] = jax.vmap(one)(lkeys)
        return params

    # -- quantized serving ----------------------------------------------------
    def quantize(self, params, dtype: str = "int8"):
        """Freeze a trained block-sparse-FFN model for quantized serving.

        Returns ``(model, params)``: a copy of this model whose shared
        :class:`SparseMLP` plans store ``dtype`` payloads (``"int8"``,
        ``"fp8"``, or the per-block-row ``"int8.rowwise"``/
        ``"fp8.rowwise"`` modes), and the matching param tree with every
        layer's fp32 FFN ``blocks`` leaves replaced by quantized payload +
        fp32 ``scales`` leaves in the same layer stacking.  Attention,
        norm, and embedding params pass through unchanged.  The Segment
        kernels dequantize at the fp32 accumulator, so decode runs on the
        low-precision weight fetch the traffic model prices (~4× fewer A
        bytes) without a dequantized weight copy ever materializing.
        """
        if self.sparse_mlp is None:
            raise ValueError(
                "Transformer.quantize requires a block-sparse FFN model "
                "(ModelConfig.ffn_block_sparse=True); dense SwiGLU weights "
                "have no Segment plan to quantize")
        model = copy.copy(self)
        model.sparse_mlp, model._sparse_proto = self.sparse_mlp.quantize(
            self._sparse_proto, dtype)

        new_params = dict(params)
        for (name, kinds, _) in self.groups:
            g = params[name]
            if isinstance(kinds, tuple):
                new_g = {}
                for j in range(len(kinds)):
                    sub = g[f"b{j}"]
                    if _is_sparse_mlp_params(sub):
                        sub = dict(sub)
                        sub["mlp"] = _quantize_mlp_params(sub["mlp"], dtype)
                    new_g[f"b{j}"] = sub
                new_params[name] = new_g
            elif _is_sparse_mlp_params(g):
                new_g = dict(g)
                new_g["mlp"] = _quantize_mlp_params(g["mlp"], dtype)
                new_params[name] = new_g
        return model, new_params

    def compute_params(self, params):
        """The parameters as the serving programs hold them: each leaf that
        :meth:`decode_step_counted` reads only cast to the compute dtype
        (``ModelConfig.dtype``) is held in that dtype, so no program casts
        it again; the rounding is the same, once.  That is the dense
        projections of attention, MLA, SwiGLU (the shared experts too) and
        the recurrent blocks, and the embedding and head tables.  Norm
        scales, the router and its bias, the recurrent gates' own
        parameters, the Segment FFN blocks, the held experts' stacks and
        every quantized payload and scale stay as stored.  Each layer type
        declares its own leaves beside its apply.  Leaves may be arrays or
        ``jax.ShapeDtypeStruct``; with float32 compute the tree is returned
        as it is.  Training reads the float32 tree."""
        cfg = self.cfg
        dt = _dtype(cfg)
        if dt == jnp.float32:
            return params
        out = dict(params)
        for name in ("embed", "lm_head"):
            if name in params:
                out[name] = layers.embedding_compute(params[name], dt)
        if "frontend" in params:
            out["frontend"] = layers.dense_compute(params["frontend"], dt)
        for name, kinds, _ in self.groups:
            g = params[name]
            if isinstance(kinds, tuple):
                out[name] = {f"b{j}": _block_compute(
                    cfg, g[f"b{j}"], kd, self.sparse_mlp, dt)
                    for j, kd in enumerate(kinds)}
            else:
                out[name] = _block_compute(cfg, g, kinds, self.sparse_mlp, dt)
        return out

    # -- scanned stacks -------------------------------------------------------
    def _run_group(self, params_g, x, kinds, *, positions, enc_out=None,
                   caches=None, cache_pos=None):
        """Scan the group's layers over ``x``.  With ``caches`` (decode and
        prefill) the layer-stacked cache rides in the scan's carry: each
        layer writes its new tokens (or its recurrent state) into the
        stack and reads its own slice back, so a donated cache is updated
        in place and no second stack is built.  Returns (x, aux, caches,
        counts), ``counts`` the MoE work summed over the layers.
        """
        cfg = self.cfg

        params_g, experts = _split_experts(params_g)

        def body(carry, inp):
            x, aux, counts, cache = carry
            p_l, layer = inp
            p_l = _merge(p_l, experts)
            if isinstance(kinds, tuple):
                blocks = [(p_l[f"b{j}"], kd, f"b{j}")
                          for j, kd in enumerate(kinds)]
            else:
                blocks = [(p_l, kinds, None)]
            for p_b, kd, sub in blocks:
                c_b = cache if cache is None or sub is None else cache[sub]
                x, a, c_b, n = _block_apply(
                    cfg, p_b, x, kd, positions=positions,
                    sparse_mlp=self.sparse_mlp, enc_out=enc_out,
                    cache=c_b, layer=layer, cache_pos=cache_pos)
                if cache is not None:
                    cache = c_b if sub is None else {**cache, sub: c_b}
                aux = aux + a
                counts = counts + n
            return (x, aux, counts, cache), None

        body_fn = body
        if cfg.remat and caches is None:
            body_fn = jax.checkpoint(body, prevent_cse=False)
        n = jax.tree.leaves(params_g)[0].shape[0]
        xs = (params_g, jnp.arange(n))
        # NOTE (decode on CPU backend): XLA's bf16-dot emulation hoists f32
        # converts of the per-layer KV-cache slices out of this scan and
        # carries full f32 cache copies in the while tuple. This is a
        # CPU-only artifact (TPU bf16 dots are native); the dry-run measures
        # and subtracts it — see launch/dryrun.py `cpu_artifact_bytes`.
        # the body is traced once and runs once per layer
        counts = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
        with obs.repeated(n):
            (x, aux, counts, caches), _ = jax.lax.scan(
                body_fn, (x, jnp.zeros((), jnp.float32), counts, caches), xs)
        return x, aux, caches, counts

    # -- forward (train / prefill logits) -------------------------------------
    def forward(self, params, tokens, vis_embeds=None, enc_embeds=None):
        """tokens: (B, T_text). vis_embeds: (B, Nv, D) for vlm/audio decoder
        prefixes; enc_embeds: (B, T_enc, D) for enc_dec."""
        cfg = self.cfg
        dt = _dtype(cfg)
        x = layers.embedding_apply(params["embed"], tokens).astype(dt)
        if vis_embeds is not None:
            v = layers.dense_apply(params["frontend"], vis_embeds.astype(dt))
            x = jnp.concatenate([v, x], axis=1)
        x = act_constrain(x, "hidden")
        b, t, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        aux_total = jnp.zeros((), jnp.float32)

        enc_out = None
        if cfg.family == "enc_dec":
            e = layers.dense_apply(params["frontend"], enc_embeds.astype(dt))
            ep = jnp.broadcast_to(jnp.arange(e.shape[1]), (b, e.shape[1]))
            enc_out, aux, _, _ = self._run_group(
                params["enc"], e, "attn_bidir", positions=ep)
            aux_total += aux
            x, aux, _, _ = self._run_group(params["dec"], x, "cross",
                                           positions=positions,
                                           enc_out=enc_out)
            aux_total += aux
        else:
            for (name, kinds, n) in self.groups:
                x, aux, _, _ = self._run_group(params[name], x, kinds,
                                               positions=positions)
                aux_total += aux
        x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        head = params.get("lm_head", params["embed"])
        logits = act_constrain(layers.lm_head_apply(head, x), "logits")
        return logits, aux_total

    def loss_fn(self, params, batch):
        """batch: dict(tokens, targets[, vis_embeds, enc_embeds, mask])."""
        logits, aux = self.forward(
            params, batch["tokens"], vis_embeds=batch.get("vis_embeds"),
            enc_embeds=batch.get("enc_embeds"))
        targets = batch["targets"]
        n_prefix = logits.shape[1] - targets.shape[1]
        if n_prefix > 0:
            logits = logits[:, n_prefix:]
        loss = layers.cross_entropy(logits, targets, batch.get("mask"))
        return loss + 0.01 * aux, {"loss": loss, "aux": aux}

    # -- serving ---------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)

        def stack(kinds, n):
            if isinstance(kinds, tuple):
                one = {f"b{j}": _block_cache_init(cfg, kd, batch_size, max_len, dt)
                       for j, kd in enumerate(kinds)}
            else:
                one = _block_cache_init(cfg, kinds, batch_size, max_len, dt)
            return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype),
                                one)

        return {name: stack(kinds, n) for (name, kinds, n) in self.groups
                if name != "enc"}

    def has_moe(self) -> bool:
        return any(k == "moe" for (_, kinds, _) in self.groups
                   for k in (kinds if isinstance(kinds, tuple) else (kinds,)))

    def decode_step(self, params, cache, token, pos, enc_out=None, *,
                    logit_idx=None):
        """:meth:`decode_step_counted` without its counts."""
        logits, cache, _ = self.decode_step_counted(
            params, cache, token, pos, enc_out, logit_idx=logit_idx)
        return logits, cache

    def decode_step_counted(self, params, cache, token, pos, enc_out=None, *,
                            logit_idx=None):
        """token: (B, T) int32 (T=1 decode, T>1 chunked prefill); pos:
        absolute position of token[:, 0] — a shared scalar int32 (lockstep
        decode) or a per-row (B,) int32 vector (continuous batching: every
        slot sits at its own position).

        ``logit_idx``: optional per-row (B,) int32 index into the T axis —
        the logits are gathered at each row's *last valid* token instead of
        ``T-1`` (mixed-length chunked prefill: a row whose prompt ends
        mid-chunk must not sample its first token from padding).

        Returns (logits (B, vocab), new_cache, counts): ``counts`` maps
        each of :data:`repro.models.moe.COUNTERS` to its int32 total over
        the MoE layers (empty for a model without them)."""
        cfg = self.cfg
        dt = _dtype(cfg)
        x = layers.embedding_apply(params["embed"], token).astype(dt)
        x = act_constrain(x, "hidden")
        b, t, _ = x.shape
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            positions = jnp.broadcast_to((pos + jnp.arange(t))[None, :], (b, t))
        else:
            positions = pos[:, None] + jnp.arange(t)[None, :]
        positions = positions.astype(jnp.int32)
        new_cache = {}
        counts = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
        for (name, kinds, n) in self.groups:
            if name == "enc":
                continue
            x, _, nc, c = self._run_group(
                params[name], x, kinds, positions=positions, enc_out=enc_out,
                caches=cache[name], cache_pos=pos)
            new_cache[name] = nc
            counts = counts + c
        x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        # gather each row's output position *before* the lm_head so the
        # (B, T, vocab) prefill logits never materialize
        if logit_idx is None:
            x = x[:, -1:]
        else:
            idx = jnp.broadcast_to(jnp.asarray(logit_idx, jnp.int32), (b,))
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        head = params.get("lm_head", params["embed"])
        logits = layers.lm_head_apply(head, x)
        named = dict(zip(moe.COUNTERS, counts)) if self.has_moe() else {}
        return logits[:, 0], new_cache, named
