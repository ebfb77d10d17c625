"""Sparse-weight linear layers backed by the Segment SpMM kernel.

Weights are stored block-sparse (BSR) and driven entirely through
:mod:`repro.api`: the layer holds a :class:`~repro.api.SegmentPlan` built
with ``with_grad=True`` (so the plan carries the transposed schedule for the
backward pass) and the trainable parameters are the plan's block values in
original BSR storage order.  Forward and backward both run through
:func:`repro.api.apply_plan` — the one ``custom_vjp`` shared with serving:

* ``dx = Wᵀ @ dy``  — another Segment SpMM under the transposed schedule
  (built once, static);
* ``dW_blocks[i] = dy[m_i] @ x[k_i]ᵀ`` — a block-sampled dense-dense product
  (SDDMM at block granularity), pure jnp gather + matmul.

This is the paper's technique as a *first-class trainable layer*: prune a
dense weight to blocks, keep the schedule fixed (static sparsity amortizes
the scheduling cost, DESIGN.md §2), train the surviving blocks.  The plan is
a registered pytree, so layers jit/vmap/shard without the identity-hash
``_Static`` wrapper this module used to define.

For serving, :meth:`SparseLinear.quantize` / :meth:`SparseMLP.quantize`
freeze trained blocks into int8/fp8 payloads with per-block fp32 scales —
the kernels dequantize at the fp32 accumulator, cutting the weight-fetch
bytes the Segment schedule's traffic model counts by ~4×.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import SegmentPlan, apply_plan, plan_matmul
from repro.core.formats import BSR, QUANT_DTYPES


@dataclasses.dataclass
class SparseLinear:
    """W (d_out × d_in) block-sparse; apply computes x @ Wᵀ via W @ xᵀ."""

    plan: SegmentPlan        # with_grad plan; lhs_blocks = init values
    d_out: int
    d_in: int

    @staticmethod
    def create(key, d_in, d_out, *, block=128, density=0.25,
               policy: str = "segment", dtype=jnp.float32):
        if d_in % block or d_out % block:
            raise ValueError(f"d_in={d_in} and d_out={d_out} must be "
                             f"multiples of block={block}: the Segment grid "
                             f"is exact and would pad the output otherwise")
        rng = np.random.default_rng(np.asarray(jax.random.key_data(key))[-1])
        w = BSR.random(rng, (d_out, d_in), (block, block), density,
                       dtype=np.float32)
        plan = plan_matmul(w, policy=policy, with_grad=True)
        layer = SparseLinear(plan=plan, d_out=d_out, d_in=d_in)
        # trainable values live in the params dict, in original BSR block
        # order (the plan's storage layout — ``plan.a_brow``/``a_bcol`` give
        # each block's coordinates); the plan copy keeps the init values
        # only as a template.
        params = {"blocks": plan.lhs_blocks.astype(dtype)}
        return layer, params

    def quantize(self, params, dtype: str = "int8"):
        """Freeze trained fp32 blocks into a quantized inference layer.

        Rebuilds the plan with ``quantize=dtype`` over the same pattern —
        the payload + per-block scales become the new param leaves (in the
        same BSR storage order), the kernels dequantize at the fp32
        accumulator, and gradients to the weights stop (x-gradients still
        flow, so the layer composes under ``jax.grad`` of downstream
        losses).  The source plan's full planner configuration — lanes,
        unroll, backend, ``pipeline`` and the tuned ``bn_hint`` — is
        carried over.  ``fold_len`` is the one knob a plan does not record;
        a fold-built plan (any ``accum_prev`` item set) raises rather than
        silently re-planning without the fold.  Returns ``(layer, params)``
        like :meth:`create`.
        """
        blocks = np.asarray(params["blocks"])
        if (self.plan.quantized or "scales" in params
                or np.dtype(blocks.dtype) in QUANT_DTYPES.values()):
            raise ValueError(
                "layer is already quantized — re-quantizing would treat the "
                f"{blocks.dtype} payload as fp32 weights and silently drop "
                "the per-block scales; quantize from the fp32 layer+params")
        if self.plan.accum_prev is not None and np.any(
                np.asarray(self.plan.accum_prev)):
            raise ValueError(
                "cannot quantize a layer built from a fold_len plan: the "
                "fold length is not recorded on the plan, so re-planning "
                "would silently drop the fold schedule — build the fp32 "
                "layer without fold_len, or re-plan manually with "
                "plan_matmul(..., fold_len=..., quantize=...)")
        w = BSR(shape=(self.d_out, self.d_in),
                block_shape=self.plan.block_shape,
                brow=np.asarray(self.plan.a_brow),
                bcol=np.asarray(self.plan.a_bcol),
                blocks=blocks.astype(np.float32))
        plan = plan_matmul(w, policy=self.plan.policy, with_grad=True,
                           quantize=dtype, n_lanes=self.plan.n_lanes,
                           unroll=self.plan.unroll, backend=self.plan.backend,
                           pipeline=self.plan.pipeline,
                           bn_hint=self.plan.bn_hint)
        layer = SparseLinear(plan=plan, d_out=self.d_out, d_in=self.d_in)
        return layer, {"blocks": plan.lhs_blocks, "scales": plan.lhs_scales}

    def apply(self, params, x2d):
        """x2d: (T, d_in) → (T, d_out)."""
        plan = self.plan.with_values(params["blocks"],
                                     lhs_scales=params.get("scales"))
        yT = apply_plan(plan, x2d.T)
        return yT.T


@dataclasses.dataclass
class SparseMLP:
    """SwiGLU MLP with block-sparse up/gate/down projections."""

    up: SparseLinear
    gate: SparseLinear
    down: SparseLinear

    @staticmethod
    def create(key, d_model, d_ff, *, block=128, density=0.25,
               dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        up, p_up = SparseLinear.create(k1, d_model, d_ff, block=block,
                                       density=density, dtype=dtype)
        gate, p_gate = SparseLinear.create(k2, d_model, d_ff, block=block,
                                           density=density, dtype=dtype)
        down, p_down = SparseLinear.create(k3, d_ff, d_model, block=block,
                                           density=density, dtype=dtype)
        layer = SparseMLP(up=up, gate=gate, down=down)
        return layer, {"up": p_up, "gate": p_gate, "down": p_down}

    def quantize(self, params, dtype: str = "int8"):
        """Quantized inference copy of the MLP (all three projections)."""
        up, p_up = self.up.quantize(params["up"], dtype)
        gate, p_gate = self.gate.quantize(params["gate"], dtype)
        down, p_down = self.down.quantize(params["down"], dtype)
        layer = SparseMLP(up=up, gate=gate, down=down)
        return layer, {"up": p_up, "gate": p_gate, "down": p_down}

    def apply(self, params, x):
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        h = (jax.nn.silu(self.gate.apply(params["gate"], x2))
             * self.up.apply(params["up"], x2))
        y = self.down.apply(params["down"], h.astype(x.dtype))
        return y.reshape(*shape[:-1], -1).astype(x.dtype)
