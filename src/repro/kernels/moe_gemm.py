"""Segment-scheduled grouped expert GEMM (MoE) — Pallas TPU.

MoE expert compute is the *data-dependent* instance of the paper's dynamic
dataflow: routing produces a (token-group × expert) block-sparse structure
known only at runtime.  The Segment treatment, inside jit:

* **SELECTA** ≙ sort routes by expert (:func:`build_chunks`): consecutive
  chunks share the expert weight block, which then stays resident in VMEM
  across grid steps (row-wise reuse of the stationary operand);
* **folding** ≙ an expert's rows are split into fixed-size chunks, the
  last one padded — load is balanced at chunk, not expert, granularity.

Nothing is dropped: every route to a held expert gets a row.  The chunk
count is static, ``routes // chunk_rows + n_experts`` (each expert pads at
most one chunk), and the chunks past the ``n_used`` that the routing needs
are skipped: they fetch nothing new, compute nothing and write zeros.

Grid: ``(n_tiles_n, n_chunks)``; the chunk→expert map, ``n_used`` and the
layer are scalar-prefetched.  The kernel reads the weights from the whole
layer-stacked array as stored (float32 on the model path), so the tile is
fetched from HBM by the kernel itself and no per-layer slice or cast is
made outside it; the weight tile for expert e, N-tile j is re-fetched only
when (e, j) changes — with chunks sorted by expert this is once per expert
per N tile.  The tile is cast to the rows' dtype (bf16 on the model path)
in VMEM and multiplied with float32 accumulation.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.api.backends import LANE
from . import ref


def _kernel(chunk_expert, n_used, layer, x, w, out, *, upcast: bool):
    used = pl.program_id(1) < n_used[0]

    @pl.when(used)
    def _():
        a, b = x[...], w[0].astype(x.dtype)
        if upcast:
            # the CPU has no bf16 x bf16 -> f32 dot: the same exact products
            # and f32 sums in f32 operands
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        out[...] = jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out.dtype)

    @pl.when(jnp.logical_not(used))
    def _():
        out[...] = jnp.zeros(out.shape, out.dtype)


def pick_bn(d_out: int, bn: int) -> int:
    """The N tile: all of ``d_out`` when it fits ``bn``, else the widest
    lane-aligned divisor of ``d_out`` under ``bn``."""
    if d_out <= bn:
        return d_out
    for k in range(bn // LANE, 0, -1):
        if d_out % (k * LANE) == 0:
            return k * LANE
    raise ValueError(f"d_out={d_out} has no N tile of at most {bn} columns "
                     f"that is a multiple of {LANE}")


def kernel_name(dtype) -> str:
    """The ``pallas_call`` name of the variant for operands of ``dtype``
    (``moe_gemm_bfloat16`` on the model path)."""
    return f"moe_gemm_{jnp.dtype(dtype).name}"


@functools.partial(jax.jit, static_argnames=("chunk_rows", "bn", "interpret",
                                             "out_dtype"))
def moe_gemm(x_sorted, w, chunk_expert, n_used, layer, *,
             chunk_rows: int = 128, bn: int = 2048, interpret: bool = False,
             out_dtype=jnp.float32):
    """Grouped GEMM over expert-sorted rows.

    Args:
      x_sorted: (n_chunks * chunk_rows, d_in) rows sorted by expert, each
        expert's group padded to whole chunks (:func:`build_chunks`).
      w: (L, E, d_in, d_out) layer-stacked expert weights, any float dtype;
        layer ``layer`` is used, cast to ``x_sorted``'s dtype.
      chunk_expert: (n_chunks,) int32 expert id per chunk, ascending; the
        chunks past ``n_used`` repeat the last used chunk's expert.
      n_used: (1,) int32, the chunks that hold rows.
      layer: (1,) int32.
    Returns:
      (n_chunks * chunk_rows, d_out) in the sorted order; the rows of the
      chunks past ``n_used`` are zero.
    """
    t, d_in = x_sorted.shape
    n_chunks = chunk_expert.shape[0]
    if t != n_chunks * chunk_rows:
        raise ValueError(
            f"x_sorted has {t} rows but chunk_expert describes "
            f"{n_chunks} chunks of {chunk_rows} rows — pad the sorted "
            f"rows to whole chunks")
    _, e, d_in_w, d_out = w.shape
    if d_in_w != d_in:
        raise ValueError(f"expert weights contract over d_in={d_in_w} but "
                         f"rows have d_in={d_in}")
    bn = pick_bn(d_out, bn)
    n_tiles_n = d_out // bn

    def chunk(c, nu):
        # a skipped chunk reads the last used one's block: no new fetch
        return jnp.maximum(jnp.minimum(c, nu[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles_n, n_chunks),
        in_specs=[
            pl.BlockSpec((chunk_rows, d_in),
                         lambda j, c, ce, nu, ly: (chunk(c, nu), 0)),
            pl.BlockSpec((None, 1, d_in, bn),
                         lambda j, c, ce, nu, ly: (ly[0], ce[c], 0, j)),
        ],
        out_specs=pl.BlockSpec((chunk_rows, bn),
                               lambda j, c, ce, nu, ly: (c, j)),
    )
    vmem = 2 * (chunk_rows * d_in * jnp.dtype(x_sorted.dtype).itemsize
                + d_in * bn * jnp.dtype(w.dtype).itemsize
                + chunk_rows * bn * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, upcast=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d_out), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        name=kernel_name(x_sorted.dtype),
    )(chunk_expert, n_used, layer, x_sorted, w)


@dataclasses.dataclass(frozen=True)
class Chunks:
    """A drop-free chunk layout of routes over held experts."""
    dest: jax.Array          # (R,) int32 row of each route; n_rows if unheld
    chunk_expert: jax.Array  # (n_chunks,) int32
    n_used: jax.Array        # (1,) int32
    counts: jax.Array        # (n_experts,) int32 routes per held expert
    chunk_rows: int

    @property
    def n_rows(self) -> int:
        return self.chunk_expert.shape[0] * self.chunk_rows


def build_chunks(expert, n_experts: int, chunk_rows: int) -> Chunks:
    """In-jit SELECTA for MoE, drop-free.

    ``expert``: (R,) int32, the held expert of each route, or ``n_experts``
    for a route to an expert held elsewhere.  Each held expert's routes
    fill ``ceil(count / chunk_rows)`` consecutive chunks, experts in
    ascending order; ``dest`` gives each route's row.  All shapes are
    static: ``n_chunks = R // chunk_rows + n_experts``, enough for any
    routing since each expert pads at most one chunk."""
    r = expert.shape[0]
    n_chunks = r // chunk_rows + n_experts
    counts = jnp.zeros((n_experts + 1,), jnp.int32).at[expert].add(1)
    counts = counts[:n_experts]
    ends = jnp.cumsum((counts + chunk_rows - 1) // chunk_rows)
    n_used = ends[-1]
    starts = ends - (counts + chunk_rows - 1) // chunk_rows
    order = jnp.argsort(expert, stable=True)
    sorted_e = expert[order]
    rank = jnp.arange(r) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    held = sorted_e < n_experts
    row = starts[jnp.minimum(sorted_e, n_experts - 1)] * chunk_rows + rank
    dest = jnp.zeros((r,), jnp.int32).at[order].set(
        jnp.where(held, row, n_chunks * chunk_rows).astype(jnp.int32))
    last = jnp.minimum(jnp.arange(n_chunks), n_used - 1)
    chunk_expert = jnp.minimum(
        jnp.searchsorted(ends, last, side="right"), n_experts - 1)
    return Chunks(dest=dest, chunk_expert=chunk_expert.astype(jnp.int32),
                  n_used=n_used.reshape(1).astype(jnp.int32), counts=counts,
                  chunk_rows=chunk_rows)


def _oracle(x, w, chunk_expert, layer, chunk_rows):
    """The kernel's arithmetic in jnp: layer ``layer``'s weights rounded to
    the rows' dtype, products summed in float32."""
    w_l = jax.lax.dynamic_index_in_dim(w, layer[0], 0, keepdims=False)
    return ref.moe_gemm_ref(x, w_l.astype(x.dtype), chunk_expert, chunk_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def grouped_matmul(x, w, chunk_expert, n_used, layer, chunk_rows: int,
                   backend: str):
    """:func:`moe_gemm` on ``backend`` — the compiled kernel (``pallas``),
    the same kernel interpreted (``interpret``) or the pure-jnp oracle
    :func:`repro.kernels.ref.moe_gemm_ref` (``reference``), all over one
    chunk layout.  Gradients run through the oracle."""
    if backend == "reference":
        return _oracle(x, w, chunk_expert, layer, chunk_rows)
    return moe_gemm(x, w, chunk_expert, n_used, layer, chunk_rows=chunk_rows,
                    interpret=backend == "interpret")


def _grouped_fwd(x, w, chunk_expert, n_used, layer, chunk_rows, backend):
    out = grouped_matmul(x, w, chunk_expert, n_used, layer, chunk_rows,
                         backend)
    return out, (x, w, chunk_expert, layer)


def _grouped_bwd(chunk_rows, backend, res, g):
    x, w, chunk_expert, layer = res
    _, vjp = jax.vjp(
        lambda x, w: _oracle(x, w, chunk_expert, layer, chunk_rows), x, w)
    dx, dw = vjp(g.astype(jnp.float32))
    return dx.astype(x.dtype), dw.astype(w.dtype), None, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
