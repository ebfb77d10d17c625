"""Quantized BSR block storage: round-trip bounds, three-way backend
parity, quantized vjp, plan-cache dtype keying, and zero-block safety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional-dep guard

from repro import api
from repro.core.formats import (BSR, QUANT_DTYPES, QuantizedBlocks,
                                dequantize_blocks, quant_error_bound,
                                quantize_blocks)

RNG = np.random.default_rng(0)

#: Normalized (max |got - want| / max |want|) tolerance vs the dense *fp32*
#: oracle on the small test cases here — the documented CI bounds (int8
#: 5e-2, fp8 1e-1) apply to the larger bench case; these are tighter.
REL_TOL = {"int8": 5e-2, "fp8": 1e-1}


def _random_bsr(seed=1, shape=(128, 160), block=(32, 32), density=0.35):
    return BSR.random(np.random.default_rng(seed), shape, block, density)


def _dequant_dense(a: BSR, dtype: str) -> np.ndarray:
    """Dense matrix of ``a`` after a quantize→dequantize round trip — the
    exact value a quantized plan computes (up to fp32 matmul rounding)."""
    q = quantize_blocks(a.blocks, dtype)
    deq = BSR(a.shape, a.block_shape, a.brow, a.bcol, dequantize_blocks(q))
    return deq.to_dense()


# ---------------------------------------------------------------------------
# round-trip helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantize_roundtrip_error_bound(dtype):
    blocks = RNG.standard_normal((9, 16, 16)).astype(np.float32)
    blocks[3] = 0.0                      # an exactly-zero block
    blocks[5] *= 100.0                   # large-magnitude block
    q = quantize_blocks(blocks, dtype)
    assert q.payload.dtype == QUANT_DTYPES[dtype]
    assert q.scales.dtype == np.float32
    assert (q.scales > 0).all()          # zero block must not zero the scale
    deq = dequantize_blocks(q)
    assert np.isfinite(deq).all()
    amax = np.abs(blocks).max(axis=(1, 2))
    bound = np.maximum(amax, 0.0) * quant_error_bound(dtype) + 1e-7
    assert (np.abs(blocks - deq) <= bound[:, None, None]).all()
    # the zero block round-trips to exactly zero
    np.testing.assert_array_equal(deq[3], 0.0)


def test_quantize_rejects_unknown_dtype():
    blocks = np.zeros((1, 4, 4), np.float32)
    with pytest.raises(ValueError, match="unknown quantized block dtype"):
        quantize_blocks(blocks, "int4")
    with pytest.raises(ValueError, match="unknown quantize dtype"):
        api.plan_matmul(_random_bsr(), quantize="int4")
    with pytest.raises(ValueError, match="blocks must be"):
        quantize_blocks(np.zeros((4, 4), np.float32), "int8")


# ---------------------------------------------------------------------------
# three-way backend parity (pallas-interpret / reference / dense oracle)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
@pytest.mark.parametrize("n_lanes", [1, 2])
def test_spmm_three_way_parity(dtype, n_lanes):
    a = _random_bsr()
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 48)).astype(np.float32))
    plan = api.plan_matmul(a, x.shape, quantize=dtype, n_lanes=n_lanes)
    assert plan.quantized and plan.block_dtype == dtype
    got_i = np.asarray(plan(x, bn=16, backend="interpret"))
    got_r = np.asarray(plan(x, backend="reference"))
    # interpret and reference compute the *same* dequantized product
    np.testing.assert_allclose(got_i, got_r, rtol=1e-4, atol=1e-4)
    # both match the dequantized dense matmul tightly
    want_q = _dequant_dense(a, dtype) @ np.asarray(x)
    np.testing.assert_allclose(got_i, want_q, rtol=1e-3, atol=1e-3)
    # and the original fp32 oracle within the dtype's normalized bound
    want = a.to_dense() @ np.asarray(x)
    rel = np.abs(got_i - want).max() / np.abs(want).max()
    assert rel < REL_TOL[dtype], (dtype, rel)


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_spgemm_three_way_parity(dtype):
    a = _random_bsr(6, (128, 160), (32, 32), 0.3)
    b = _random_bsr(7, (160, 96), (32, 32), 0.3)
    plan = api.plan_matmul(a, b, quantize=dtype, n_lanes=2)
    got_i = np.asarray(plan(backend="interpret"))
    got_r = np.asarray(plan(backend="reference"))
    np.testing.assert_allclose(got_i, got_r, rtol=1e-4, atol=1e-4)
    want_q = _dequant_dense(a, dtype) @ _dequant_dense(b, dtype)
    want = a.to_dense() @ b.to_dense()
    norm = np.abs(want).max()
    for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
        tile_q = want_q[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32]
        np.testing.assert_allclose(got_i[i], tile_q, rtol=1e-3, atol=1e-3)
        tile = want[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32]
        assert np.abs(got_i[i] - tile).max() / norm < REL_TOL[dtype]


def test_quantized_zero_block_produces_finite_output():
    """A block that is exactly zero must not poison the plan with NaN/inf
    (its scale is clamped to 1.0; payload stays zero)."""
    blocks = np.stack([np.zeros((32, 32), np.float32),
                       RNG.standard_normal((32, 32)).astype(np.float32)])
    a = BSR(shape=(64, 32), block_shape=(32, 32),
            brow=np.array([0, 1], np.int32), bcol=np.array([0, 0], np.int32),
            blocks=blocks)
    x = jnp.asarray(RNG.standard_normal((32, 16)).astype(np.float32))
    for dtype in ("int8", "fp8"):
        plan = api.plan_matmul(a, x.shape, quantize=dtype)
        got = np.asarray(plan(x, bn=16, backend="interpret"))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[:32], 0.0)  # zero block row
        want = a.to_dense() @ np.asarray(x)
        assert np.abs(got - want).max() / np.abs(want).max() < REL_TOL[dtype]


# ---------------------------------------------------------------------------
# quantized vjp (transpose_lhs backward against the quantized storage)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_quantized_vjp_dx_matches_dequantized_dense(backend):
    a = _random_bsr(8, (96, 128), (32, 32), 0.4)
    plan = api.plan_matmul(a, with_grad=True, quantize="int8", n_lanes=2)
    assert plan.grad_plan.transpose_lhs
    assert plan.grad_plan.block_dtype == "int8"
    x = jnp.asarray(RNG.standard_normal((128, 48)).astype(np.float32))

    def loss(xx):
        return jnp.sum(api.apply_plan(plan, xx, backend=backend) ** 2)

    gx = jax.grad(loss)(x)
    w_deq = jnp.asarray(_dequant_dense(a, "int8"))
    gx_d = jax.grad(lambda xx: jnp.sum((w_deq @ xx) ** 2))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-3, atol=1e-3)


def test_quantized_payload_cotangent_is_symbolic_zero():
    """int8 payloads are frozen inference storage: the weight leaf gets a
    float0 cotangent while x-gradients flow normally."""
    a = _random_bsr(9, (64, 64), (32, 32), 0.5)
    plan = api.plan_matmul(a, with_grad=True, quantize="int8")
    x = jnp.asarray(RNG.standard_normal((64, 8)).astype(np.float32))
    out, vjp = jax.vjp(
        lambda p, xx: api.apply_plan(p, xx, backend="interpret"), plan, x)
    dplan, dx = vjp(jnp.ones_like(out))
    assert dplan.lhs_blocks.dtype == jax.dtypes.float0
    assert np.isfinite(np.asarray(dx)).all()


# ---------------------------------------------------------------------------
# plan cache: dtype keying + per-dtype stats
# ---------------------------------------------------------------------------


def test_fp32_and_quantized_plans_never_collide():
    api.clear_plan_cache()
    a = _random_bsr(10)
    p32 = api.plan_matmul(a, n_cols_hint=64)
    p8 = api.plan_matmul(a, n_cols_hint=64, quantize="int8")
    pf8 = api.plan_matmul(a, n_cols_hint=64, quantize="fp8")
    assert len({p32.fingerprint, p8.fingerprint, pf8.fingerprint}) == 3
    stats = api.plan_cache_stats()
    assert stats["size"] == 3 and stats["misses"] == 3
    assert stats["by_dtype"] == {"fp32": 1, "int8": 1, "fp8": 1}
    # same pattern + dtype is a hit (and realizes fresh quantized values)
    p8b = api.plan_matmul(a, n_cols_hint=64, quantize="int8")
    assert p8b.fingerprint == p8.fingerprint
    assert api.plan_cache_stats()["hits"] == 1
    api.clear_plan_cache()
    assert api.plan_cache_stats()["by_dtype"] == {}


def test_quantized_traffic_reprices_a_bytes():
    a = _random_bsr(11, (256, 256), (64, 64), 0.25)
    t32 = api.plan_matmul(a, n_cols_hint=64).traffic
    t8 = api.plan_matmul(a, n_cols_hint=64, quantize="int8").traffic
    # payload byte + 4 scale bytes per 64x64 tile vs 4 bytes/elem
    expect = t32["a_bytes"] / (64 * 64 * 4) * (64 * 64 + 4)
    assert t8["a_bytes"] == pytest.approx(expect)
    assert t8["b_bytes"] == t32["b_bytes"] and t8["c_bytes"] == t32["c_bytes"]
    assert t8["total"] < t32["total"]


# ---------------------------------------------------------------------------
# zero-copy realize of pre-quantized payloads + out_dtype plumbing
# ---------------------------------------------------------------------------


def test_prequantized_payload_uploads_verbatim():
    a = _random_bsr(12)
    q = quantize_blocks(a.blocks, "int8")
    qdev = QuantizedBlocks(payload=jnp.asarray(q.payload),
                           scales=jnp.asarray(q.scales), dtype="int8")
    a_q = BSR(a.shape, a.block_shape, a.brow, a.bcol, qdev)
    plan = api.plan_matmul(a_q, quantize="int8", cache=False)
    assert plan.lhs_blocks is qdev.payload      # same device buffers
    assert plan.lhs_scales is qdev.scales
    with pytest.raises(ValueError, match="pre-quantized"):
        api.plan_matmul(a_q, quantize="fp8", cache=False)


def test_out_dtype_plumbed_through_plan_and_overridable():
    a = _random_bsr(13)
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 32)).astype(np.float32))
    plan = api.plan_matmul(a, x.shape, quantize="int8", out_dtype=jnp.bfloat16)
    assert plan.out_dtype == "bfloat16"
    for backend in ("interpret", "reference"):
        assert plan(x, bn=16, backend=backend).dtype == jnp.bfloat16
    # per-call override beats the plan default
    assert plan(x, bn=16, backend="interpret",
                out_dtype=jnp.float32).dtype == jnp.float32
    # default stays float32 when unset
    p2 = api.plan_matmul(a, x.shape)
    assert p2.out_dtype is None
    assert p2(x, bn=16, backend="interpret").dtype == jnp.float32


# ---------------------------------------------------------------------------
# quantized inference layers
# ---------------------------------------------------------------------------


def test_sparse_linear_quantize_matches_and_keeps_config():
    from repro.models.sparse_ffn import SparseLinear
    layer, params = SparseLinear.create(jax.random.PRNGKey(0), 128, 64,
                                        block=32, density=0.4)
    x = jnp.asarray(RNG.standard_normal((8, 128)).astype(np.float32))
    with api.use_backend("interpret"):
        y = layer.apply(params, x)
        qlayer, qparams = layer.quantize(params, "int8")
        yq = qlayer.apply(qparams, x)
    assert qlayer.plan.block_dtype == "int8"
    # lane/unroll config survives the rebuild
    assert qlayer.plan.n_lanes == layer.plan.n_lanes
    assert qlayer.plan.unroll == layer.plan.unroll
    rel = float(jnp.abs(y - yq).max() / jnp.abs(y).max())
    assert rel < REL_TOL["int8"], rel


def test_with_values_rejects_mismatched_storage_dtype():
    """A quantized plan fed fp32 values would apply its stale per-block
    scales to them (silently ~wrong output); the reverse feeds a raw
    payload into an fp32 plan with no scales.  Both must raise."""
    a = _random_bsr(14)
    p32 = api.plan_matmul(a)
    p8 = api.plan_matmul(a, quantize="int8")
    with pytest.raises(ValueError, match="stores int8 payloads"):
        p8.with_values(jnp.asarray(a.blocks))
    with pytest.raises(ValueError, match="stores fp32 blocks"):
        p32.with_values(p8.lhs_blocks)
    # matching dtypes pass through
    assert p8.with_values(p8.lhs_blocks).lhs_blocks is p8.lhs_blocks
    # ...and the layer-level misuse the guard is for:
    from repro.models.sparse_ffn import SparseLinear
    layer, params = SparseLinear.create(jax.random.PRNGKey(2), 64, 64,
                                        block=32, density=0.5)
    qlayer, _qparams = layer.quantize(params, "int8")
    x = jnp.asarray(RNG.standard_normal((4, 64)).astype(np.float32))
    with pytest.raises(ValueError, match="stores int8 payloads"):
        qlayer.apply(params, x)   # stale fp32 params into quantized layer


def test_sparse_linear_rejects_double_quantization():
    """Re-quantizing a quantized layer would read the int8 payload as fp32
    weights and drop the scales — must raise, not corrupt silently."""
    from repro.models.sparse_ffn import SparseLinear
    layer, params = SparseLinear.create(jax.random.PRNGKey(1), 64, 64,
                                        block=32, density=0.5)
    qlayer, qparams = layer.quantize(params, "int8")
    with pytest.raises(ValueError, match="already quantized"):
        qlayer.quantize(qparams, "int8")
    with pytest.raises(ValueError, match="already quantized"):
        layer.quantize(qparams, "fp8")   # quantized params, fp32 layer


# ---------------------------------------------------------------------------
# property sweep: pattern × dtype ≡ dequantized oracle, bounded vs fp32
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 10_000), gm=st.integers(1, 5),
       gk=st.integers(1, 5), density=st.floats(0.15, 1.0),
       dtype=st.sampled_from(["int8", "fp8"]))
def test_quant_property_roundtrip_and_parity(seed, gm, gk, density, dtype):
    rng = np.random.default_rng(seed)
    a = BSR.random(rng, (gm * 16, gk * 16), (16, 16), density)
    # round trip obeys the per-block bound
    q = quantize_blocks(a.blocks, dtype)
    deq = dequantize_blocks(q)
    amax = np.abs(a.blocks).max(axis=(1, 2))
    bound = amax * quant_error_bound(dtype) + 1e-7
    assert (np.abs(a.blocks - deq) <= bound[:, None, None]).all()
    # backend parity on the quantized plan
    x = rng.standard_normal((gk * 16, 32)).astype(np.float32)
    plan = api.plan_matmul(a, x.shape, quantize=dtype)
    got = np.asarray(plan(jnp.asarray(x), bn=16, backend="interpret"))
    got_r = np.asarray(plan(jnp.asarray(x), backend="reference"))
    np.testing.assert_allclose(got, got_r, rtol=1e-4, atol=1e-4)
    deq_bsr = BSR(a.shape, a.block_shape, a.brow, a.bcol, deq)
    want_q = deq_bsr.to_dense() @ x
    np.testing.assert_allclose(got, want_q, rtol=1e-3, atol=1e-3)
    want = a.to_dense() @ x
    norm = max(float(np.abs(want).max()), 1e-3)
    assert np.abs(got - want).max() / norm < REL_TOL[dtype]


# ---------------------------------------------------------------------------
# sub-block (per-row-of-block) scales: "*.rowwise" modes
# ---------------------------------------------------------------------------


def _outlier_bsr(seed=21, shape=(128, 160), block=(32, 32), density=0.35):
    """BSR whose blocks each carry one large-magnitude row — the case
    per-block scales handle worst and per-row scales are built for."""
    a = BSR.random(np.random.default_rng(seed), shape, block, density)
    a.blocks[:, 3, :] *= 50.0
    return a


@pytest.mark.parametrize("mode", ["int8.rowwise", "fp8.rowwise"])
def test_rowwise_roundtrip_error_bound(mode):
    base = mode.split(".", 1)[0]
    blocks = RNG.standard_normal((9, 16, 16)).astype(np.float32)
    blocks[3] = 0.0
    blocks[5, 7] *= 100.0                # one outlier row
    q = quantize_blocks(blocks, mode)
    assert q.dtype == mode
    assert q.payload.dtype == QUANT_DTYPES[base]
    assert q.scales.shape == (9, 16)     # one fp32 scale per block row
    assert (q.scales > 0).all()
    deq = dequantize_blocks(q)
    assert np.isfinite(deq).all()
    # the bound is the *per-row* absmax fraction — strictly tighter than
    # the per-block bound wherever rows differ in magnitude
    amax_row = np.abs(blocks).max(axis=2)
    bound = amax_row * quant_error_bound(mode) + 1e-7
    assert (np.abs(blocks - deq) <= bound[:, :, None]).all()
    np.testing.assert_array_equal(deq[3], 0.0)


@pytest.mark.parametrize("base", ["int8", "fp8"])
def test_rowwise_tightens_outlier_rows(base):
    """On blocks with a magnitude-outlier row, per-row scales beat
    per-block scales: the non-outlier rows keep their own resolution."""
    a = _outlier_bsr()
    err = {m: np.linalg.norm(a.blocks - dequantize_blocks(
        quantize_blocks(a.blocks, m)))
        for m in (base, base + ".rowwise")}
    assert err[base + ".rowwise"] < err[base]


@pytest.mark.parametrize("mode", ["int8.rowwise", "fp8.rowwise"])
@pytest.mark.parametrize("prefetch", [None, "cross_pass"])
def test_rowwise_spmm_three_way_parity(mode, prefetch):
    a = _outlier_bsr()
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 48)).astype(np.float32))
    plan = api.plan_matmul(a, x.shape, quantize=mode, n_lanes=2,
                           prefetch=prefetch, verify="full")
    assert plan.quantized and plan.block_dtype == mode
    assert plan.lhs_scales.shape == (a.nblocks, a.block_shape[0])
    got_i = np.asarray(plan(x, bn=16, backend="interpret"))
    got_r = np.asarray(plan(x, backend="reference"))
    np.testing.assert_allclose(got_i, got_r, rtol=1e-4, atol=1e-4)
    want_q = _dequant_dense(a, mode) @ np.asarray(x)
    np.testing.assert_allclose(got_i, want_q, rtol=1e-3, atol=1e-3)
    want = a.to_dense() @ np.asarray(x)
    rel = np.abs(got_i - want).max() / np.abs(want).max()
    assert rel < REL_TOL[mode.split(".", 1)[0]], (mode, rel)


@pytest.mark.parametrize("mode", ["int8.rowwise", "fp8.rowwise"])
def test_rowwise_spgemm_parity(mode):
    a = _outlier_bsr(22, (128, 160), (32, 32), 0.3)
    b = _outlier_bsr(23, (160, 96), (32, 32), 0.3)
    plan = api.plan_matmul(a, b, quantize=mode, n_lanes=2, verify="full")
    # B-side rowwise scales run over the contraction rows (bk)
    assert plan.rhs_scales.shape == (b.nblocks, 32)
    got_i = np.asarray(plan(backend="interpret"))
    got_r = np.asarray(plan(backend="reference"))
    np.testing.assert_allclose(got_i, got_r, rtol=1e-4, atol=1e-4)
    want_q = _dequant_dense(a, mode) @ _dequant_dense(b, mode)
    for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
        tile_q = want_q[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32]
        np.testing.assert_allclose(got_i[i], tile_q, rtol=1e-3, atol=1e-3)


def test_rowwise_vjp_dx_matches_dequantized_dense():
    """transpose_lhs rowwise kernels dequantize pre-dot, so the backward
    x-gradient matches the dequantized dense oracle."""
    a = _outlier_bsr(24)
    plan = api.plan_matmul(a, with_grad=True, quantize="int8.rowwise",
                           n_lanes=2)
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 24)).astype(np.float32))
    gx = jax.grad(lambda xx: jnp.sum(
        api.apply_plan(plan, xx, backend="interpret") ** 2))(x)
    w_deq = jnp.asarray(_dequant_dense(a, "int8.rowwise"))
    gx_d = jax.grad(lambda xx: jnp.sum((w_deq @ xx) ** 2))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-3, atol=1e-3)


def test_rowwise_and_per_block_plans_never_collide():
    """The full mode string is the plan's block_dtype: "int8" and
    "int8.rowwise" plans of the same pattern get distinct fingerprints and
    distinct cache entries."""
    a = _random_bsr(25)
    api.clear_plan_cache()
    p_blk = api.plan_matmul(a, quantize="int8")
    p_row = api.plan_matmul(a, quantize="int8.rowwise")
    assert p_blk.block_dtype == "int8" and p_row.block_dtype == "int8.rowwise"
    assert p_blk.fingerprint != p_row.fingerprint
    assert p_blk.lhs_scales.ndim == 1 and p_row.lhs_scales.ndim == 2
    stats = api.plan_cache_stats()
    assert stats["by_dtype"].get("int8") == 1
    assert stats["by_dtype"].get("int8.rowwise") == 1


def test_rowwise_traffic_prices_scale_rows():
    """Rowwise A-fetch traffic carries bm fp32 scales per block fetch where
    per-block carries one; payload bytes are identical."""
    a = _random_bsr(26)
    t_blk = api.plan_matmul(a, quantize="int8", cache=False).traffic
    t_row = api.plan_matmul(a, quantize="int8.rowwise", cache=False).traffic
    bm = 32
    n_fetch = t_blk["a_bytes"] / (bm * bm * 1 + 4)
    assert t_row["a_bytes"] == pytest.approx(n_fetch * (bm * bm * 1 + bm * 4))


def test_rowwise_scale_agreement_verifier():
    """verify_plan(level="full") passes a healthy rowwise plan and flags a
    scale array of the wrong granularity."""
    a = _random_bsr(27)
    plan = api.plan_matmul(a, quantize="int8.rowwise", cache=False)
    plan.verify(level="full").raise_if_findings()
    bad = plan.replace(lhs_scales=plan.lhs_scales[:, :1])
    findings = bad.verify(level="fast").findings
    assert any(f.invariant == "scale-agreement" and "per block row"
               in f.message for f in findings)


def test_sparse_linear_quantize_carries_full_planner_config():
    """Regression: quantize() used to rebuild the plan with only
    lanes/unroll/backend, silently dropping the tuned bn_hint."""
    from repro.models.sparse_ffn import SparseLinear
    layer, params = SparseLinear.create(jax.random.PRNGKey(3), 128, 64,
                                        block=32, density=0.4)
    tuned = SparseLinear(plan=layer.plan.replace(bn_hint=128),
                         d_out=64, d_in=128)
    qlayer, _ = tuned.quantize(params, "int8")
    assert qlayer.plan.bn_hint == 128
    assert qlayer.plan.n_lanes == tuned.plan.n_lanes
    assert qlayer.plan.unroll == tuned.plan.unroll


def test_sparse_linear_quantize_fold_plan_raises():
    """fold_len is not recorded on a plan, so quantize() on a fold-built
    layer must raise instead of silently re-planning without the fold."""
    from repro.models.sparse_ffn import SparseLinear
    a = BSR.random(np.random.default_rng(28), (128, 256), (32, 32), 0.8)
    plan = api.plan_matmul(a, policy="segment", fold_len=2, with_grad=True,
                           cache=False)
    assert np.any(np.asarray(plan.accum_prev))   # the fold actually folded
    layer = SparseLinear(plan=plan, d_out=128, d_in=256)
    with pytest.raises(ValueError, match="fold_len"):
        layer.quantize({"blocks": np.asarray(plan.lhs_blocks)}, "int8")


# ---------------------------------------------------------------------------
# whole-model quantization (Transformer.quantize)
# ---------------------------------------------------------------------------


def _sparse_model():
    import dataclasses
    from repro.configs import REGISTRY, reduced_config
    from repro.models import build_model
    cfg = dataclasses.replace(reduced_config(REGISTRY["phi3-mini-3.8b"]),
                              dtype="float32", ffn_block_sparse=True,
                              ffn_block=32, ffn_density=0.5)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(4))


@pytest.mark.parametrize("mode", ["int8", "int8.rowwise"])
def test_transformer_quantize_param_tree_and_logits(mode):
    cfg, model, params = _sparse_model()
    qmodel, qparams = model.quantize(params, mode)
    assert qmodel.sparse_mlp.up.plan.block_dtype == mode
    # FFN leaves became payload + scales with the layer stacking intact
    for proj in ("up", "gate", "down"):
        leaf32 = params["layers"]["mlp"][proj]
        leaf = qparams["layers"]["mlp"][proj]
        assert leaf["blocks"].dtype == QUANT_DTYPES["int8"]
        assert leaf["blocks"].shape == leaf32["blocks"].shape
        n_layers, n_blocks = leaf32["blocks"].shape[:2]
        want_scales = ((n_layers, n_blocks, 32) if mode.endswith("rowwise")
                       else (n_layers, n_blocks))
        assert leaf["scales"].shape == want_scales
    # non-FFN params pass through untouched
    assert qparams["embed"] is params["embed"]
    assert qparams["layers"]["attn"] is params["layers"]["attn"]
    # forward logits stay close to fp32
    toks = (jnp.arange(2 * 8).reshape(2, 8) * 13) % cfg.vocab
    with api.use_backend("interpret"):
        lo32, _ = model.forward(params, toks)
        loq, _ = qmodel.forward(qparams, toks)
    rel = float(jnp.abs(loq - lo32).max() / jnp.abs(lo32).max())
    assert rel < REL_TOL["int8"], rel
    # the original model+params still serve fp32 (no in-place mutation)
    assert model.sparse_mlp.up.plan.block_dtype == "fp32"


def test_transformer_quantize_rejects_double_and_dense():
    import dataclasses
    from repro.configs import REGISTRY, reduced_config
    from repro.models import build_model
    _, model, params = _sparse_model()
    qmodel, qparams = model.quantize(params, "int8")
    with pytest.raises(ValueError, match="already quantized"):
        qmodel.quantize(qparams, "int8")
    with pytest.raises(ValueError, match="already quantized"):
        model.quantize(qparams, "int8")
    dense_cfg = dataclasses.replace(
        reduced_config(REGISTRY["phi3-mini-3.8b"]), dtype="float32")
    dense = build_model(dense_cfg)
    with pytest.raises(ValueError, match="block-sparse"):
        dense.quantize(dense.init(jax.random.PRNGKey(5)), "int8")
