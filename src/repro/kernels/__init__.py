"""Pallas TPU kernels for the Segment dataflow + architecture hot spots.

Each kernel module pairs with a pure-jnp oracle in :mod:`repro.kernels.ref`;
:mod:`repro.kernels.ops` exposes the jit'd public wrappers (interpret mode
unless the default backend is ``pallas``).
"""
from . import ops, ref
from .ops import (SpgemmPlan, SpmmPlan, flash_mha, plan_spgemm, plan_spmm,
                  rg_lru_scan)

__all__ = [
    "ops", "ref", "SpgemmPlan", "SpmmPlan", "flash_mha",
    "plan_spgemm", "plan_spmm", "rg_lru_scan",
]
