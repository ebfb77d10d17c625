"""RG-LRU linear recurrence (Griffin / RecurrentGemma) — Pallas TPU.

The recurrence ``h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)`` is the
memory-bound hot loop of the hybrid architecture's recurrent blocks.  The
kernel fuses gate math + scan per (batch row × time chunk), carrying the
hidden state in VMEM scratch across sequential time-chunk grid steps — one
HBM read per input element, one write per output element.

Note: the Segment dataflow is *inapplicable* here (attention-free dense
recurrence — see DESIGN.md §Arch-applicability); this kernel exists because
the architecture pool requires the layer to be fast, not because the paper's
technique maps onto it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, ag_ref, xg_ref, ap_ref, h0_ref, o_ref, hT_ref, h_ref, *,
            ct, n_chunks, c):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    # fused gate math for the whole chunk (VPU elementwise)
    log_a = (-c * jax.nn.softplus(ap_ref[...].astype(jnp.float32))
             * jax.nn.sigmoid(ag_ref[0].astype(jnp.float32)))
    a = jnp.exp(log_a)                                   # (ct, D)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    xb = beta * (jax.nn.sigmoid(xg_ref[0].astype(jnp.float32))
                 * x_ref[0].astype(jnp.float32))

    def step(t, h):
        h = a[t] * h + xb[t]
        o_ref[0, t] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, ct, step, h_ref[0])
    h_ref[...] = h[None]

    @pl.when(ti == n_chunks - 1)
    def _finish():
        hT_ref[...] = h_ref[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "c", "interpret"))
def rg_lru(x, a_gate, x_gate, a_param, h0, *, ct: int = 128, c: float = 8.0,
           interpret: bool = False):
    """x/a_gate/x_gate: (B, T, D); a_param: (D,); h0: (B, D).

    Returns (out (B, T, D), h_T (B, D)).
    """
    b, t, d = x.shape
    ct = min(ct, t)
    if t % ct:
        raise ValueError(f"sequence length T={t} must be a multiple of the "
                         f"chunk length ct={ct}")
    n_chunks = t // ct

    kernel = functools.partial(_kernel, ct=ct, n_chunks=n_chunks, c=c)
    out, h_t = pl.pallas_call(
        kernel,
        grid=(b, n_chunks),
        in_specs=[
            pl.BlockSpec((1, ct, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ct, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ct, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((d,), lambda i, j: (0,)),
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ct, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, a_gate, x_gate, a_param, h0)
    return out, h_t
