"""Public jit'd wrappers around the Pallas kernels.

Plan construction moved to :mod:`repro.api` — :func:`plan_spmm` /
:func:`plan_spgemm` remain as thin deprecation shims that delegate to
``repro.api.plan_matmul`` and return the unified :class:`SegmentPlan`
(call-compatible with the old ``SpmmPlan``/``SpgemmPlan``).

Backend selection (compiled / interpret / reference) lives in
:mod:`repro.api.backends`; the wrappers below resolve their ``interpret``
flag from it at call time, so importing this module touches no device.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.backends import default_backend
from repro.api.plan import SegmentPlan
from repro.api.planner import plan_matmul
from repro.core.formats import BSR
from . import ref
from .flash_attention import flash_attention
from .moe_gemm import build_moe_chunks, moe_gemm
from .rg_lru import rg_lru


def _interpret(flag: Optional[bool]) -> bool:
    """An explicit ``interpret`` flag, else compiled only when the default
    backend is ``pallas``."""
    return default_backend() != "pallas" if flag is None else flag

# Deprecated aliases — both old plan classes are now the one SegmentPlan.
SpmmPlan = SegmentPlan
SpgemmPlan = SegmentPlan


def _deprecated(old: str) -> None:
    warnings.warn(f"repro.kernels.ops.{old} is deprecated; use "
                  f"repro.api.plan_matmul", DeprecationWarning, stacklevel=3)


def plan_spmm(a: BSR, policy: str = "segment", n_cols_hint: int = 1024,
              fold_len: Optional[int] = None) -> SegmentPlan:
    """Deprecated shim for :func:`repro.api.plan_matmul` (SpMM)."""
    _deprecated("plan_spmm")
    return plan_matmul(a, policy=policy, n_cols_hint=n_cols_hint,
                       fold_len=fold_len)


def plan_spgemm(a: BSR, b: BSR, policy: str = "segment",
                fold_len: Optional[int] = None) -> SegmentPlan:
    """Deprecated shim for :func:`repro.api.plan_matmul` (SpGEMM)."""
    _deprecated("plan_spgemm")
    return plan_matmul(a, b, policy=policy, fold_len=fold_len)


# ---------------------------------------------------------------------------
# Attention / recurrences / MoE
# ---------------------------------------------------------------------------


def flash_mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              bq: int = 128, bkv: int = 128, interpret: Optional[bool] = None):
    """GQA flash attention. q: (B, Tq, H, D), k/v: (B, Tk, Hkv, D).

    Grouped queries are folded into the q axis — the ``rep = H/Hkv`` query
    heads of one KV head run as ``rep`` stacked ``Tq``-long groups against a
    single K/V copy (``q_period`` position wrap in the kernel), so each K/V
    head is read from HBM once instead of ``rep`` times (the old path
    materialized ``jnp.repeat`` copies of K and V).
    """
    interpret = _interpret(interpret)
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    # pad Tq/Tk (at the end) to block multiples; real queries keep their
    # absolute positions via the explicit offset, padded keys are masked by
    # kv_len, padded query rows are sliced off.
    bq_eff = min(bq, max(8, 1 << max(tq - 1, 0).bit_length()))
    bkv_eff = min(bkv, max(128, 1 << max(tk - 1, 0).bit_length()))
    pad_q = (-tq) % bq_eff
    pad_k = (-tk) % bkv_eff
    tq_pad = tq + pad_q
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, tk, d)
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))
    # (B, Tq, H, D) → (B, Hkv, rep, Tq_pad, D) → (B·Hkv, rep·Tq_pad, D):
    # query heads of one KV head stack along the q axis (head h maps to KV
    # head h // rep, matching jnp.repeat(..., axis=2) semantics).
    qh = q.transpose(0, 2, 1, 3).reshape(b, hkv, rep, tq, d)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    qh = qh.reshape(b * hkv, rep * tq_pad, d)
    out = flash_attention(qh, kh, vh, causal=causal, window=window,
                          offset=tk - tq, kv_len=tk,
                          bq=bq_eff, bkv=bkv_eff,
                          q_period=tq_pad if rep > 1 else None,
                          interpret=interpret)
    out = out.reshape(b, hkv, rep, tq_pad, d)[:, :, :, :tq]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, d)


def rg_lru_scan(x, a_gate, x_gate, a_param, h0=None, *, ct: int = 128,
                interpret: Optional[bool] = None):
    interpret = _interpret(interpret)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), jnp.float32)
    return rg_lru(x, a_gate, x_gate, a_param, h0, ct=min(ct, x.shape[1]),
                  interpret=interpret)


def moe_apply(x, w_up, w_down, router_logits, *, top_k: int = 1,
              chunk_rows: int = 128, capacity_factor: float = 1.25,
              activation=jax.nn.silu, interpret: Optional[bool] = None):
    """Full MoE FFN: route → Segment-sort → grouped GEMMs → unsort-combine.

    x: (T, d_model); w_up: (E, d_model, d_ff); w_down: (E, d_ff, d_model).
    Returns (T, d_model).
    """
    interpret = _interpret(interpret)
    t, d_model = x.shape
    n_exp = w_up.shape[0]
    top_vals, top_idx = jax.lax.top_k(router_logits, top_k)      # (T, top_k)
    gates = jax.nn.softmax(top_vals, axis=-1)
    out = jnp.zeros((t, d_model), jnp.float32)
    for j in range(top_k):
        expert = top_idx[:, j]
        order, slot, chunk_expert, keep, n_chunks, cap_rows = build_moe_chunks(
            expert, n_exp, chunk_rows, capacity_factor)
        cap_total = n_exp * cap_rows
        # scatter tokens (sorted by expert) into the padded chunk buffer;
        # dropped tokens land on the trash row which is cut before the GEMM
        buf = jnp.zeros((cap_total + 1, d_model), x.dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], x[order], 0))
        buf = buf[:-1]
        h = moe_gemm(buf, w_up, chunk_expert, chunk_rows=chunk_rows,
                     interpret=interpret)
        h = activation(h).astype(x.dtype)
        y = moe_gemm(h, w_down, chunk_expert, chunk_rows=chunk_rows,
                     interpret=interpret)
        # gather back: sorted position s ↔ original token order[s]
        vals = jnp.where(keep[:, None],
                         y[jnp.minimum(slot, cap_total - 1)], 0.0)
        y_tok = jnp.zeros((t, d_model), jnp.float32).at[order].set(vals)
        out = out + y_tok * gates[:, j][:, None]
    return out


__all__ = [
    "SpmmPlan", "SpgemmPlan", "plan_spmm", "plan_spgemm",
    "flash_mha", "rg_lru_scan", "moe_apply", "ref",
]
