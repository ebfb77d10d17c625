"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st  # optional-dep guard

from repro.core.formats import BSR
from repro.kernels import ops, ref


RNG = np.random.default_rng(0)


@pytest.mark.parametrize("policy", ["segment", "gustavson"])
@pytest.mark.parametrize("m,k,bm,bk,density", [
    (256, 384, 64, 64, 0.3),
    (128, 256, 32, 64, 0.15),
    (512, 512, 128, 128, 0.2),
    (64, 64, 8, 8, 0.5),
])
def test_spmm_vs_oracle(policy, m, k, bm, bk, density):
    a = BSR.random(RNG, (m, k), (bm, bk), density)
    bd = RNG.standard_normal((k, 256)).astype(np.float32)
    out = np.asarray(ops.plan_spmm(a, policy=policy)(jnp.asarray(bd), bn=128))
    want = a.to_dense() @ bd
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype):
    a = BSR.random(RNG, (128, 128), (32, 32), 0.4)
    a.blocks = a.blocks.astype(dtype)
    bd = RNG.standard_normal((128, 64)).astype(np.float32)
    out = np.asarray(ops.plan_spmm(a)(jnp.asarray(bd).astype(dtype), bn=64),
                     dtype=np.float32)
    want = np.asarray(a.blocks, np.float32)
    dense = BSR(a.shape, a.block_shape, a.brow, a.bcol, want).to_dense() @ bd
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(out, dense, rtol=tol, atol=tol)


@pytest.mark.parametrize("policy", ["segment", "gustavson"])
def test_spgemm_vs_oracle(policy):
    a = BSR.random(RNG, (256, 320), (64, 64), 0.3)
    b = BSR.random(RNG, (320, 192), (64, 64), 0.3)
    plan = ops.plan_spgemm(a, b, policy=policy)
    blocks = np.asarray(plan())
    want = a.to_dense() @ b.to_dense()
    for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
        np.testing.assert_allclose(
            blocks[i], want[r * 64:(r + 1) * 64, c * 64:(c + 1) * 64],
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,tq,tk,h,hkv,d,causal,window", [
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 64, 256, 4, 1, 64, True, None),
    (2, 128, 128, 4, 4, 64, True, 64),
    (2, 128, 128, 4, 2, 64, True, 64),      # GQA × window (q_period wrap)
    (1, 1, 96, 8, 2, 64, True, None),       # decode shape
    (2, 48, 48, 2, 2, 32, False, None),     # bidirectional, ragged sizes
    (1, 32, 512, 2, 2, 128, True, 128),     # long kv + window
])
def test_flash_attention_vs_oracle(b, tq, tk, h, hkv, d, causal, window):
    q = RNG.standard_normal((b, tq, h, d)).astype(np.float32) * 0.5
    k = RNG.standard_normal((b, tk, hkv, d)).astype(np.float32) * 0.5
    v = RNG.standard_normal((b, tk, hkv, d)).astype(np.float32) * 0.5
    out = ops.flash_mha(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal, window=window)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("b,t,d,ct", [(2, 128, 64, 32), (1, 64, 128, 64),
                                      (3, 96, 32, 16)])
def test_rg_lru_vs_oracle(b, t, d, ct):
    x = RNG.standard_normal((b, t, d)).astype(np.float32)
    ag = RNG.standard_normal((b, t, d)).astype(np.float32)
    xg = RNG.standard_normal((b, t, d)).astype(np.float32)
    ap = RNG.standard_normal(d).astype(np.float32)
    out, hT = ops.rg_lru_scan(*map(jnp.asarray, (x, ag, xg, ap)), ct=ct)
    want, wT = ref.rg_lru_ref(x, ag, xg, ap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(wT), atol=1e-5)


def test_rwkv_ref_state_continuity():
    """Chunked evaluation with carried state equals one-shot evaluation."""
    b, t, h, d = 1, 32, 2, 16
    r, k, v = (RNG.standard_normal((b, t, h, d)).astype(np.float32) * 0.3
               for _ in range(3))
    w = -np.abs(RNG.standard_normal((b, t, h, d))).astype(np.float32) - 0.1
    u = RNG.standard_normal((h, d)).astype(np.float32) * 0.1
    full, _ = ref.rwkv6_ref(*map(jnp.asarray, (r, k, v, w, u)))
    half1, s = ref.rwkv6_ref(*map(jnp.asarray,
                                  (r[:, :16], k[:, :16], v[:, :16], w[:, :16], u)))
    half2, _ = ref.rwkv6_ref(jnp.asarray(r[:, 16:]), jnp.asarray(k[:, 16:]),
                             jnp.asarray(v[:, 16:]), jnp.asarray(w[:, 16:]),
                             jnp.asarray(u), state0=s)
    np.testing.assert_allclose(np.asarray(full[:, 16:]), np.asarray(half2),
                               rtol=1e-4, atol=1e-5)


def test_moe_gemm_padded_and_skipped_chunks_vs_ref():
    """The drop-free layout: expert 1 overflows one chunk (3 chunks, the
    last padded), expert 2 has no route, expert 3 one route, and two spare
    chunks are skipped — against :func:`ref.moe_gemm_ref` over the same
    layout, the skipped chunks' rows zero."""
    from repro.kernels.moe_gemm import build_chunks, moe_gemm
    rows, dm, dff, e = 8, 32, 64, 4
    expert = jnp.asarray([1] * 19 + [0] * 5 + [3] + [4] * 3, jnp.int32)
    ch = build_chunks(expert, e, rows)
    assert ch.chunk_expert.shape == (28 // rows + e,)
    assert int(ch.n_used[0]) == 1 + 3 + 1
    assert np.asarray(ch.chunk_expert).tolist() == [0, 1, 1, 1, 3, 3, 3]
    assert np.asarray(ch.counts).tolist() == [5, 19, 0, 1]
    x = np.zeros((ch.n_rows, dm), np.float32)
    d = np.asarray(ch.dest)
    held = d < ch.n_rows
    x[d[held]] = RNG.standard_normal((int(held.sum()), dm)) * 0.3
    # two layers of experts, the second one used
    w = (RNG.standard_normal((2, e, dm, dff)) * 0.1).astype(np.float32)
    got = np.asarray(moe_gemm(jnp.asarray(x), jnp.asarray(w),
                              ch.chunk_expert, ch.n_used,
                              jnp.asarray([1], jnp.int32), chunk_rows=rows,
                              interpret=True))
    want = np.asarray(ref.moe_gemm_ref(jnp.asarray(x), jnp.asarray(w[1]),
                                       ch.chunk_expert, rows))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not got[5 * rows:].any()
    # every route to a held expert has its own row, with its expert's block
    assert len(set(d[held].tolist())) == int(held.sum())
    assert (np.asarray(ch.chunk_expert)[d[held] // rows]
            == np.asarray(expert)[held]).all()


@settings(deadline=None, max_examples=15)
@given(seed=st.integers(0, 10_000), gm=st.integers(1, 6), gk=st.integers(1, 6),
       density=st.floats(0.1, 1.0),
       policy=st.sampled_from(["segment", "gustavson"]))
def test_spmm_property(seed, gm, gk, density, policy):
    rng = np.random.default_rng(seed)
    a = BSR.random(rng, (gm * 16, gk * 16), (16, 16), density)
    bd = rng.standard_normal((gk * 16, 32)).astype(np.float32)
    out = np.asarray(ops.plan_spmm(a, policy=policy)(jnp.asarray(bd), bn=32))
    np.testing.assert_allclose(out, a.to_dense() @ bd, rtol=1e-4, atol=1e-4)
