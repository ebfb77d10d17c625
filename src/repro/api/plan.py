"""``SegmentPlan`` — the one plan abstraction, registered as a JAX pytree.

A plan freezes everything a Segment-dataflow matmul needs at run time:

* **leaves** (device arrays): the block values (fp32, or a quantized
  payload plus per-block fp32 ``lhs_scales``/``rhs_scales``), the
  scalar-prefetch schedule arrays (``seg_start``/``seg_write``/
  ``accum_prev`` plus the DMA-pipeline ``a_fetch``/``b_fetch``/``a_slot``/
  ``b_slot`` fetch schedule), per-item block coordinates, the row liveness
  mask, and — when the plan was built with ``with_grad=True`` — a nested
  backward plan for the transposed schedule;
* **static aux data** (hashable python values): grid sizes, block shape,
  policy name, kind, the traffic estimate, and the pattern fingerprint.

Because the plan is a pytree, it passes through ``jax.jit`` (as a traced
argument), donation, and sharding like any other array container — this
replaces the identity-hash ``_Static`` workaround the trainable layers used
to need.  Aux data is hashable, so jit caches correctly key on the static
schedule structure while the arrays stay dynamic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import QUANT_DTYPES, quant_base_dtype

SPMM = "spmm"
SPGEMM = "spgemm"

# Leaf fields, flattening order.  ``grad_plan`` is itself a SegmentPlan (a
# child pytree); None fields flatten to zero leaves.
_LEAF_FIELDS = (
    "lhs_blocks", "rhs_blocks",
    "lhs_scales", "rhs_scales",
    "m_idx", "k_idx",
    "a_idx", "b_idx", "c_idx",
    "slot_idx", "valid",
    "seg_start", "seg_write", "accum_prev",
    "a_fetch", "b_fetch", "a_slot", "b_slot",
    "row_mask",
    "a_brow", "a_bcol", "b_brow", "b_bcol", "c_brow_arr", "c_bcol_arr",
    "grad_plan",
)
_AUX_FIELDS = ("kind", "policy", "block_shape", "grid", "rhs_grid",
               "n_out_blocks", "traffic_items", "fingerprint", "backend",
               "n_lanes", "unroll", "transpose_lhs", "block_dtype",
               "out_dtype", "has_pads", "bn_hint", "prefetch")


@dataclasses.dataclass(eq=False)   # array fields make generated __eq__ ambiguous
class SegmentPlan:
    """Frozen Segment schedule + block values for one sparse matmul.

    ``kind == "spmm"``: ``lhs_blocks`` are the A tiles in **original BSR
    storage order** (``a_brow``/``a_bcol`` give each stored block's
    coordinates); the lane-major schedule addresses them through
    ``slot_idx``, so realizing a plan never gathers block values.  Calling
    the plan with a dense ``(K, N)`` right-hand side returns the dense
    ``(M, N)`` product.

    ``kind == "spgemm"``: ``lhs_blocks``/``rhs_blocks`` are the A/B tiles in
    original BSR order, ``a_idx``/``b_idx``/``c_idx`` map schedule items to
    block slots, and calling the plan returns the ``(n_out_blocks, bm, bn)``
    C blocks at the symbolic pattern positions (``c_brow``/``c_bcol``).
    """

    # --- static aux data (hashable; part of the jit cache key) ---
    kind: str
    policy: str
    block_shape: Tuple[int, int]                  # (bm, bk) of A tiles
    grid: Tuple[int, int]                         # A's (grid_m, grid_k)
    rhs_grid: Optional[Tuple[int, int]]           # B's (grid_k, grid_n) | None
    n_out_blocks: int                             # spgemm: |C blocks|; spmm: grid_m
    traffic_items: Tuple[Tuple[str, float], ...]  # frozen traffic estimate
    fingerprint: str                              # pattern+policy hash
    backend: Optional[str] = None                 # preferred backend | None=default
    n_lanes: int = 1                              # parallel lanes in the grid
    unroll: int = 1                               # items per grid step
    transpose_lhs: bool = False                   # kernel contracts Aᵀ (bwd)
    block_dtype: str = "fp32"                     # "fp32" | "int8" | "fp8"
    out_dtype: Optional[str] = None               # dtype name | None=float32
    # True when the lane-major schedule carries any valid=0 padding item —
    # the executor masks pad contributions exactly when this is set (the
    # conservative default keeps hand-built plans safe)
    has_pads: bool = True
    # preferred executor N-tile width (set by the repro.tune search; the
    # executor uses it when the caller passes no explicit bn)
    bn_hint: Optional[int] = None
    # DMA schedule mode (see core.schedule.PREFETCH_MODES): "cross_pass"
    # makes the kernels issue the next (lane, N-tile) pass's first copies
    # during the current pass's tail step instead of draining the pipeline
    # at the boundary; None keeps the drained schedule.  Certified
    # hazard-free per kernel variant by repro.analysis.order.
    prefetch: Optional[str] = None

    # --- pytree leaves (device arrays; None where not applicable) ---
    lhs_blocks: Optional[jax.Array] = None
    rhs_blocks: Optional[jax.Array] = None
    lhs_scales: Optional[jax.Array] = None        # (n_blocks,) fp32 | None
    rhs_scales: Optional[jax.Array] = None
    m_idx: Optional[jax.Array] = None
    k_idx: Optional[jax.Array] = None
    a_idx: Optional[jax.Array] = None
    b_idx: Optional[jax.Array] = None
    c_idx: Optional[jax.Array] = None
    slot_idx: Optional[jax.Array] = None
    valid: Optional[jax.Array] = None
    seg_start: Optional[jax.Array] = None
    seg_write: Optional[jax.Array] = None
    accum_prev: Optional[jax.Array] = None
    # DMA pipeline schedule: per-item fetch flags + resident ring-buffer
    # slots for the A and B operand streams (see core.schedule.fetch_flags)
    a_fetch: Optional[jax.Array] = None
    b_fetch: Optional[jax.Array] = None
    a_slot: Optional[jax.Array] = None
    b_slot: Optional[jax.Array] = None
    row_mask: Optional[jax.Array] = None
    a_brow: Optional[jax.Array] = None
    a_bcol: Optional[jax.Array] = None
    b_brow: Optional[jax.Array] = None
    b_bcol: Optional[jax.Array] = None
    c_brow_arr: Optional[jax.Array] = None
    c_bcol_arr: Optional[jax.Array] = None
    grad_plan: Optional["SegmentPlan"] = None

    # ------------------------------------------------------------------
    # pytree protocol
    # ------------------------------------------------------------------

    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in _LEAF_FIELDS)
        aux = tuple(getattr(self, f) for f in _AUX_FIELDS)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        kw: Dict[str, Any] = dict(zip(_AUX_FIELDS, aux))
        kw.update(zip(_LEAF_FIELDS, children))
        return cls(**kw)

    # ------------------------------------------------------------------
    # convenience surface
    # ------------------------------------------------------------------

    @property
    def n_items(self) -> int:
        """Padded schedule length (``n_lanes * lane_len``, pads included)."""
        return int(self.seg_start.shape[0])

    @property
    def lane_len(self) -> int:
        return self.n_items // self.n_lanes

    @property
    def n_blocks(self) -> int:
        """Number of *stored* lhs blocks (original BSR order)."""
        src = self.lhs_blocks if self.lhs_blocks is not None else self.a_brow
        return int(src.shape[0])

    @property
    def traffic(self) -> Dict[str, float]:
        """Revisiting-model HBM traffic estimate (see ``schedule_traffic``)."""
        return dict(self.traffic_items)

    @property
    def grid_m(self) -> int:
        return self.grid[0]

    @property
    def grid_k(self) -> int:
        return self.grid[1]

    @property
    def n_c_blocks(self) -> int:
        if self.kind != SPGEMM:
            raise AttributeError("n_c_blocks is only defined for spgemm plans")
        return self.n_out_blocks

    @property
    def c_brow(self) -> np.ndarray:
        """Symbolic C pattern rows (spgemm), as host numpy."""
        return np.asarray(self.c_brow_arr)

    @property
    def c_bcol(self) -> np.ndarray:
        return np.asarray(self.c_bcol_arr)

    def replace(self, **kw) -> "SegmentPlan":
        return dataclasses.replace(self, **kw)

    def verify(self, level: str = "fast", **kw):
        """Run the static schedule verifier over this plan.

        Delegates to :func:`repro.analysis.verify_plan` (``level`` is
        ``"fast"`` or ``"full"``; keyword args — ``invariants``, ``bn``,
        ``n_cols`` — pass through) and returns its
        :class:`~repro.analysis.VerifyResult`; call
        ``.raise_if_findings()`` on it to turn findings into a
        :class:`~repro.analysis.PlanVerificationError`.
        """
        from repro.analysis.invariants import verify_plan
        return verify_plan(self, level=level, **kw)

    @property
    def quantized(self) -> bool:
        """True when block values are stored quantized (+ per-block scales)."""
        return self.block_dtype != "fp32"

    def with_values(self, lhs_blocks, rhs_blocks=None, *, lhs_scales=None,
                    rhs_scales=None) -> "SegmentPlan":
        """Same schedule, new block values (e.g. the current train params).

        ``lhs_blocks`` must match the plan's storage layout: original BSR
        (row-major) block order for both plan kinds.  Quantized plans take
        the low-precision payload plus the matching per-block ``*_scales``
        (``None`` keeps the plan's current scales).
        """
        self._check_value_dtype("lhs_blocks", lhs_blocks)
        kw: Dict[str, Any] = {"lhs_blocks": lhs_blocks}
        if rhs_blocks is not None:
            self._check_value_dtype("rhs_blocks", rhs_blocks)
            kw["rhs_blocks"] = rhs_blocks
        if lhs_scales is not None:
            kw["lhs_scales"] = lhs_scales
        if rhs_scales is not None:
            kw["rhs_scales"] = rhs_scales
        return dataclasses.replace(self, **kw)

    def _check_value_dtype(self, name: str, blocks) -> None:
        """New block values must match the plan's storage format: a
        quantized plan silently applying its per-block scales to fp32
        values (or an fp32 plan fed a raw payload) is numerically wrong in
        a way no shape check catches."""
        got = np.dtype(jnp.result_type(blocks))
        if self.quantized:
            expect = QUANT_DTYPES[quant_base_dtype(self.block_dtype)]
            if got != expect:
                raise ValueError(
                    f"{name} has dtype {got}, but this plan stores "
                    f"{self.block_dtype} payloads ({expect}) — quantize the "
                    f"values (repro.core.formats.quantize_blocks) or use the "
                    f"fp32 plan of this pattern")
        elif got in QUANT_DTYPES.values():
            raise ValueError(
                f"{name} has quantized payload dtype {got}, but this plan "
                f"stores fp32 blocks — build it with plan_matmul(..., "
                f"quantize=...) to carry the matching scales")

    def __call__(self, rhs=None, *, bn: Optional[int] = None,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None, out_dtype=None):
        """Execute the plan.

        spmm: ``plan(b_dense)`` → dense ``(M, N)``.
        spgemm: ``plan()`` → ``(n_out_blocks, bm, bn)`` C blocks.

        ``bn=None`` defers to the plan's tuned ``bn_hint`` (when the plan
        came out of the :mod:`repro.tune` search) and otherwise to the
        executor default (512).  ``interpret`` is a deprecated alias for
        ``backend`` kept for the old ``ops.SpmmPlan``/``ops.SpgemmPlan``
        call signature.
        """
        from . import executor  # local import: executor imports this module
        if interpret is not None:
            backend = "interpret" if interpret else "pallas"
        return executor.execute_plan(self, rhs, bn=bn, backend=backend,
                                     out_dtype=out_dtype)


jax.tree_util.register_pytree_node(
    SegmentPlan, SegmentPlan.tree_flatten, SegmentPlan.tree_unflatten)
