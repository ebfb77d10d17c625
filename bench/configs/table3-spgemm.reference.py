"""Plain reference of ``C = A @ A.T`` and the comparison that decides an
SpGEMM run's ``correct``.

Each ``A`` is rebuilt dense on the device from the benchmark's own block
arrays and multiplied in float32 at the highest matmul precision.  The
program's answer, its output blocks at the coordinates it reports, is
scattered into a dense matrix of the same shape, so a block in the wrong
place, a missing block and a wrong value all show.  It imports nothing of
the program.

The number compared is the componentwise error: each element's
``|C - C_ref|`` over ``(|A| @ |A.T|)`` at that element, the sum of the
magnitudes of the products it adds up.  A float32 product run as one
bfloat16 pass (both operands rounded to bfloat16, float32 accumulation)
stays under ``2 * 2**-8 + 2**-16`` of that sum in every element (bfloat16
rounds to nearest at ``2**-8``), plus the accumulation's float32
rounding.  An operand quantized against a whole block's largest value
loses its small elements: under int8 those elements read near 1, under
float8 (three mantissa bits) near ``2 * 2**-4``.  An element no product
reaches has to come back 0.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def dense(shape, block: int, brow, bcol, blocks) -> jax.Array:
    """Dense (padded to whole blocks) matrix from stored blocks."""
    gm, gk = -(-shape[0] // block), -(-shape[1] // block)
    w = jnp.zeros((gm, gk, block, block), jnp.float32)
    w = w.at[jnp.asarray(brow), jnp.asarray(bcol)].set(jnp.asarray(blocks))
    return w.transpose(0, 2, 1, 3).reshape(gm * block, gk * block)


@jax.jit
def _componentwise_err(a, at, c):
    hi = jax.lax.Precision.HIGHEST
    ref = jnp.dot(a, at, precision=hi, preferred_element_type=jnp.float32)
    mag = jnp.dot(jnp.abs(a), jnp.abs(at), precision=hi,
                  preferred_element_type=jnp.float32)
    tiny = jnp.finfo(jnp.float32).tiny
    return jnp.max(jnp.abs(c - ref) / jnp.maximum(mag, tiny))


def compare(answers: Sequence) -> Dict[str, float]:
    """``answers``: ``(name, A, A.T, c_brow, c_bcol, c_blocks)`` per matrix,
    ``A``/``A.T`` as the benchmark's block arrays.  Returns the worst
    componentwise error over the pass as ``spgemm_cw_err``, and each
    matrix's under its name."""
    out: Dict[str, float] = {}
    for name, a, at, c_brow, c_bcol, c_blocks in answers:
        b = a.block
        c = dense((a.shape[0], at.shape[1]), b, np.asarray(c_brow),
                  np.asarray(c_bcol), c_blocks)
        err = float(_componentwise_err(
            dense(a.shape, b, a.brow, a.bcol, a.blocks),
            dense(at.shape, b, at.brow, at.bcol, at.blocks), c))
        out["cw_err." + name] = err if np.isfinite(err) else float("inf")
        del c
    out["spgemm_cw_err"] = max(out.values()) if out else float("inf")
    return out
