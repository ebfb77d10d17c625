"""Logical sharding rules: parameter/batch PartitionSpecs for any mesh.

Strategy (DESIGN.md §5):

* **TP** on ``model``: attention heads, FFN hidden, vocab, experts;
* **FSDP** on ``data``: the *other* dimension of every large matrix is
  sharded too, so params + optimizer state scale down with the full slice
  count (104B × 12 B/param ÷ 256 ≈ 4.9 GB/chip);
* **DP** on ``pod`` (multi-pod): pure replication — gradients all-reduce
  across the DCN; FSDP stays *within* a pod so param all-gathers ride ICI.

Rules are name/shape heuristics over the parameter pytree — the same table
MaxText-style frameworks encode, kept in one place.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# parameter-name classes
_COL_PARALLEL = {"up", "gate", "wq", "wk", "wv", "wg", "wr", "in_x", "in_g",
                 "a_gate", "x_gate", "cm_k", "w_lora_a", "router",
                 "wkv_a", "wkv_b"}
_ROW_PARALLEL = {"down", "wo", "out", "cm_v", "w_lora_b"}
_REPLICATED = {"scale", "b", "a_param", "mix", "cm_mix", "u", "conv",
               "w_bias", "score_bias"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return tuple(names)


def param_pspec(path, leaf, *, dp: str = "data", tp: str = "model") -> P:
    names = _path_names(path)
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    ndim = np.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim
    # stacked-layer leading dim (scan stacks) is never sharded; detect via
    # ndim relative to the logical rank below.

    if name == "blocks":
        # Segment-kernel BSR blocks: schedule indexes the full block list —
        # replicate (sparse layers are small; device-level sharding goes
        # through core.schedule.shard_schedule instead)
        return P()
    if name == "table":                      # (vocab, d) embedding
        return P(tp, dp)
    if name in _REPLICATED:
        return P()
    if name == "w" and parent in _COL_PARALLEL:
        return _last2(ndim, dp, tp)
    if name == "w" and parent in _ROW_PARALLEL:
        return _last2(ndim, tp, dp)
    if name in ("gate", "up") and ndim >= 3:   # (E, d, ff) expert weights
        return _expert(ndim, tp, dp)
    if name == "down" and ndim >= 3:           # (E, ff, d)
        return _expert(ndim, tp, dp, swap=True)
    if ndim >= 2:
        return _last2(ndim, dp, tp)
    return P()


def _last2(ndim, a, b) -> P:
    """Shard the last two dims as (a, b); leading (stack) dims unsharded."""
    pad = [None] * (ndim - 2)
    return P(*pad, a, b)


def _expert(ndim, tp, dp, swap=False) -> P:
    pad = [None] * (ndim - 3)
    if swap:
        return P(*pad, tp, None, dp)
    return P(*pad, tp, dp, None)


def params_pspecs(params, fsdp="data"):
    """Pytree of PartitionSpecs matching a parameter pytree.

    ``fsdp`` may be ``("data", "pod")`` for cross-pod ZeRO-3 (giants whose
    state exceeds one pod's HBM); sanitize drops absent axes."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, leaf, dp=fsdp), params)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch-sharding axes: ('pod','data') multi-pod, ('data',) single."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_pspec(mesh: Mesh, ndim: int) -> P:
    dp = dp_axes(mesh)
    axes = [dp] + [None] * (ndim - 1)
    return P(*axes)


def batch_pspecs(mesh: Mesh, batch):
    return jax.tree.map(
        lambda leaf: batch_pspec(mesh, np.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim),
        batch)


def cache_pspec(mesh: Mesh, path, leaf) -> P:
    """Decode-state sharding: batch on dp, axis-2 on model.

    KV caches ``k``/``v`` are (layers, B, T, n_kv·hd) → **sequence-parallel
    decode**: the 32k KV timeline shards over the model axis (1.1 TB of
    command-r cache → 2.1 GB/chip); attention reductions over T psum across
    shards.  The int8 cache's scales ``k_s``/``v_s`` (layers, B, T, n_kv)
    shard T with it (otherwise every layer reshards them — §Perf C4).
    Latent attention's ``latent`` (layers, B, T, r + dr) shards its
    timeline alike.  RWKV state (layers, B, H, hd, hd) shards heads on the
    same rule.
    ``path`` is the leaf's key path in the cache tree, as
    ``jax.tree_util.tree_map_with_path`` gives it.
    """
    dp = dp_axes(mesh)
    ndim = leaf.ndim
    names = _path_names(path)
    timeline = bool(names) and names[-1] in ("k", "v", "k_s", "v_s",
                                             "latent")
    if timeline or ndim >= 5:
        tp = "model" if (leaf.shape[2] % mesh.shape["model"] == 0) else None
        return P(None, dp, tp, *([None] * (ndim - 3)))
    if ndim >= 2:
        return P(None, dp, *([None] * (ndim - 2)))
    return P()


def sanitize_pspec(mesh: Mesh, spec: P, shape) -> P:
    """Drop sharding on dims the mesh doesn't divide (Megatron pads vocab;
    everything else falls back to replication on that dim)."""
    dims = tuple(shape)
    new = []
    for i, axes in enumerate(spec):
        if axes is None or i >= len(dims):
            new.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        keep = []
        size = 1
        for a in ax_tuple:
            if a in mesh.axis_names and dims[i] % (size * mesh.shape[a]) == 0:
                keep.append(a)
                size *= mesh.shape[a]
        new.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*new)


def make_shardings(mesh: Mesh, pspecs, leaves=None):
    """NamedShardings from specs; with ``leaves`` given, specs are sanitized
    against the actual shapes first."""
    if leaves is None:
        return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(
        lambda s, l: NamedSharding(mesh, sanitize_pspec(mesh, s, l.shape)),
        pspecs, leaves, is_leaf=lambda x: isinstance(x, P))


def constrain(x, mesh: Mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_like_params(grads, fsdp="data"):
    """Pin gradient shardings to the parameter rules (inside an abstract
    mesh context).  Forces XLA to reduce-scatter per-layer weight grads into
    the FSDP layout instead of materializing them replicated."""
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:
        return grads
    if m is None or not m.axis_names or "model" not in m.axis_names:
        return grads
    def fix(path, g):
        spec = sanitize_pspec(m, param_pspec(path, g, dp=fsdp), g.shape)
        return jax.lax.with_sharding_constraint(g, spec)
    return jax.tree_util.tree_map_with_path(fix, grads)


def act_constrain(x, kind: str):
    """Mesh-aware activation constraint; no-op outside a mesh context.

    kinds: ``hidden`` (B, T, D) batch-sharded; ``logits`` (B, T, V) batch +
    vocab(model)-sharded (padded vocab is always divisible).
    """
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if m is None or not m.axis_names or "model" not in m.axis_names:
        return x
    dp = tuple(a for a in ("pod", "data") if a in m.axis_names)
    if not dp:
        return x
    if x.shape[0] % int(np.prod([m.shape[a] for a in dp])) != 0:
        return x
    tp_ok = lambda dim: dim % m.shape["model"] == 0
    if kind == "logits":
        spec = P(dp, *([None] * (x.ndim - 2)), "model")
    elif kind == "seq" and x.ndim >= 2 and tp_ok(x.shape[1]):
        # sequence parallelism: residuals shard T over the model axis —
        # saved-activation memory drops by the TP degree
        spec = P(dp, "model", *([None] * (x.ndim - 2)))
    elif kind == "ffn" and tp_ok(x.shape[-1]):
        # FFN hidden sharded on model — keeps the bwd dW contraction
        # partial-per-shard (reduce-scatter, not replicate)
        spec = P(dp, *([None] * (x.ndim - 2)), "model")
    elif kind == "heads" and x.ndim == 4 and tp_ok(x.shape[2]):
        spec = P(dp, None, "model", None)
    elif kind == "scores_t" and x.ndim == 4 and tp_ok(x.shape[-1]):
        # decode attention scores (B, H, Tq, Tk): keep the KV timeline
        # sharded on model — softmax/PV reduce via psum instead of
        # resharding the whole cache slice every layer
        spec = P(dp, None, None, "model")
    else:
        spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)
