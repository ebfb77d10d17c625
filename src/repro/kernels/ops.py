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

import jax.numpy as jnp

from repro.api.backends import default_backend
from repro.api.plan import SegmentPlan
from repro.api.planner import plan_matmul
from repro.core.formats import BSR
from . import ref
from .flash_attention import flash_attention
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
# Attention / recurrences
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


__all__ = [
    "SpmmPlan", "SpgemmPlan", "plan_spmm", "plan_spgemm",
    "flash_mha", "rg_lru_scan", "ref",
]
