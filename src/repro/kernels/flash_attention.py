"""Fused attention (flash-style online softmax) — Pallas TPU.

Standard IO-aware attention with GQA, causal and local-window masking.  The
local-window variant shares the block-schedule machinery philosophy of the
Segment dataflow: fully-masked KV blocks are *skipped structurally* (the
banded block pattern is static given window size), so compute scales with
the band, not the full T² — which is what makes ``long_500k`` decoding
feasible for the hybrid architectures.

Layout: q (BH, Tq, D), k/v (BH, Tk, D) — GQA head replication is resolved in
``ops.flash_mha``.  Grid ``(BH, n_q, n_kv)`` with KV innermost; running max /
denominator / accumulator live in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, offset, kv_len, bq, bkv, n_kv, q_period):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: with causal/window masking many KV blocks are fully
    # masked — do no work for them (structural block sparsity)
    q_row = qi * bq
    if q_period is not None:
        # GQA grouping: the q axis stacks `rep` query copies of length
        # q_period; positions repeat per copy (q blocks never straddle a
        # copy — q_period % bq == 0 is asserted at call time).
        q_row = jax.lax.rem(q_row, q_period)
    q_lo = offset + q_row                     # first absolute q position
    q_hi = q_lo + bq - 1
    k_lo = ki * bkv
    k_hi = k_lo + bkv - 1
    live = k_lo < kv_len                      # padded KV tail is dead
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _body():
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bkv)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "offset", "kv_len", "bq", "bkv", "q_period",
    "interpret", "out_dtype"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    offset=None, kv_len=None, bq: int = 128, bkv: int = 128,
                    q_period=None, interpret: bool = False, out_dtype=None):
    """q: (BH, Tq, D); k/v: (BH, Tk, D). Returns (BH, Tq, D).

    ``offset``: absolute position of q[0] (default Tk - Tq: queries are the
    final positions of the context).  ``kv_len``: number of live keys
    (positions ≥ kv_len are padding and masked out).  ``q_period``: the q
    axis holds several stacked query groups of this length sharing the K/V
    rows (GQA grouping — positions repeat every ``q_period`` rows; must be
    a multiple of ``bq``).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    bq = min(bq, tq)
    bkv = min(bkv, tk)
    if tq % bq or tk % bkv:
        raise ValueError(f"Tq={tq}/Tk={tk} must be multiples of the tile "
                         f"sizes bq={bq}/bkv={bkv}")
    if q_period is not None and (q_period % bq or tq % q_period):
        raise ValueError(f"q_period={q_period} must be a multiple of bq={bq} "
                         f"and divide Tq={tq}")
    n_q, n_kv = tq // bq, tk // bkv
    offset = (tk - (tq if q_period is None else q_period)) \
        if offset is None else offset
    kv_len = tk if kv_len is None else kv_len
    scale = 1.0 / np.sqrt(d)
    out_dtype = out_dtype or q.dtype

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, offset=offset,
        kv_len=kv_len, bq=bq, bkv=bkv, n_kv=n_kv, q_period=q_period)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
