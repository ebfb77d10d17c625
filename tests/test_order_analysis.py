"""Mutation-kill battery for the inter-pass ordering analyzer.

The first half drives the four ``ORDER_RULES`` with toy Pallas kernels
built around a two-pass, two-step grid (both axes ``"arbitrary"``, so the
outer axis is a pass axis) and an end-of-body cross-pass tail prefetch —
the toy analog of the SpMM kernels' ``prefetch="cross_pass"`` schedule:

* wrong-slot first wait of pass 1        -> ``cross-pass-war``
* re-issued prologue start over the
  still-outstanding prefetch (with a
  paired extra wait, so whole-chain
  semaphore totals stay balanced)       -> ``sem-carryover``
* pass-1 waits with swapped semaphores  -> ``prefetch-raw``
* small copy issued before a bulky one  -> ``dma-priority``

Each mutation must be caught by *exactly* its targeted rule — the
set-equality assertions double as a no-collateral proof against the
whole merged rule set (syntactic linter + symbolic analyzer + ordering
rules), and the unmutated toys must prove clean, which exercises the
non-trivial paths (a wait legitimately discharging a copy issued in the
previous pass's tail).

The second half certifies the shipped ``prefetch="cross_pass"`` mode:
bit-exact numerical parity against the drained schedule across lanes ×
unroll × quantization × the transposed backward pass, a clean ordering
proof over the traced kernels with a non-vacuous (two-pass) model, the
``prefetch_fetches`` traffic accounting and its verifier agreement
check, and the knob's plumbing through plan aux / planner validation /
cost model / autotuner.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import (
    ORDER_RULES,
    analyze_callable,
    build_order,
    pass_local_chains,
    trace_kernel_irs,
    verify_plan,
)
from repro.api import apply_plan, plan_matmul
from repro.core.formats import BSR
from repro.core.schedule import (PREFETCH_MODES, fetch_flags,
                                 lane_traffic_spgemm, lane_traffic_spmm)
from repro.tune import Candidate, autotune_matmul
from repro.tune.cost import DEFAULT_INTERPRET, DEFAULT_TPU, CostModel


def _rules(findings):
    return set(f.rule for f in findings)


_N_PASS, _N_STEP = 2, 2


# ---------------------------------------------------------------------------
# toy kernels: a two-pass ring with an end-of-body cross-pass tail
# ---------------------------------------------------------------------------


def _xpass_toy(x, *, mutate=None):
    """Two passes x two steps over a depth-2 DMA ring; the last step of
    pass ``j`` issues pass ``j+1``'s first copy (slot 0, sem 0) after its
    own read — exactly the kernels' cross-pass prefetch contract.

    ``mutate="clobber"``: pass 1's first wait discharges ring slot 1
    instead of slot 0 (sem slot kept correct), leaving the prefetched
    copy in flight over the slot-0 read.
    ``mutate="carryover"``: pass 1 re-issues the prologue start while the
    prefetch is still outstanding on the same (sem, slot); a paired extra
    wait keeps whole-chain start/wait totals balanced, so only the
    boundary-granular rule can see it.
    """

    def kernel(hbm_ref, out_ref, buf_ref, sem_ref):
        j = pl.program_id(0)            # pass axis (the N-tile analog)
        s = pl.program_id(1)            # step axis
        slot = s % 2
        nxt = (s + 1) % 2

        def start(step, sl, sem_sl):
            pltpu.make_async_copy(hbm_ref.at[pl.ds(step * 8, 8)],
                                  buf_ref.at[sl], sem_ref.at[sem_sl]).start()

        def wait(sl, sem_sl):
            pltpu.make_async_copy(hbm_ref.at[pl.ds((j * _N_STEP + s) * 8, 8)],
                                  buf_ref.at[sl], sem_ref.at[sem_sl]).wait()

        @pl.when((j == 0) & (s == 0))
        def _prologue():
            start(0, 0, 0)

        @pl.when(s + 1 < _N_STEP)
        def _ahead():
            start(j * _N_STEP + s + 1, nxt, nxt)

        if mutate == "carryover":
            @pl.when((j == 1) & (s == 0))
            def _double_start():
                start(j * _N_STEP + s, 0, 0)

        if mutate == "clobber":
            @pl.when((j == 1) & (s == 0))
            def _wrong_slot():
                wait(1, 0)

            @pl.when((j == 0) | (s == 1))
            def _right_slot():
                wait(slot, slot)
        else:
            wait(slot, slot)

        if mutate == "carryover":
            @pl.when((j == 1) & (s == 0))
            def _double_wait():
                wait(0, 0)

        out_ref[...] = buf_ref[slot]

        # the cross-pass tail: issued after this pass's last read, waited
        # by the next pass's first step
        @pl.when((s + 1 == _N_STEP) & (j + 1 < _N_PASS))
        def _tail():
            start((j + 1) * _N_STEP, 0, 0)

    return pl.pallas_call(
        kernel, grid=(_N_PASS, _N_STEP),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8,), lambda j, s: (j * _N_STEP + s,)),
        scratch_shapes=[pltpu.VMEM((2, 8), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((_N_PASS * _N_STEP * 8,), jnp.float32),
        interpret=True,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(x)


def _twin_ring_toy(xa, xb, *, swap_pass1_sems=False):
    """Two equal-size depth-2 rings (equal so ``dma-priority`` stays
    vacuous), each with its own semaphore pair and a cross-pass tail.
    ``swap_pass1_sems`` makes pass 1's first waits discharge each buffer
    with the *other* buffer's semaphore — every (sem, slot) FIFO stays
    balanced at the boundary, but neither first consumption waits on its
    filler."""

    def kernel(ha_ref, hb_ref, out_ref, bufa_ref, bufb_ref,
               sema_ref, semb_ref):
        j = pl.program_id(0)
        s = pl.program_id(1)
        slot = s % 2
        nxt = (s + 1) % 2

        def start(hbm, buf, sem, step, sl):
            pltpu.make_async_copy(hbm.at[pl.ds(step * 8, 8)],
                                  buf.at[sl], sem.at[sl]).start()

        def wait(hbm, buf, sem, sl, sem_sl):
            pltpu.make_async_copy(hbm.at[pl.ds((j * _N_STEP + s) * 8, 8)],
                                  buf.at[sl], sem.at[sem_sl]).wait()

        @pl.when((j == 0) & (s == 0))
        def _prologue():
            start(ha_ref, bufa_ref, sema_ref, 0, 0)
            start(hb_ref, bufb_ref, semb_ref, 0, 0)

        @pl.when(s + 1 < _N_STEP)
        def _ahead():
            start(ha_ref, bufa_ref, sema_ref, j * _N_STEP + s + 1, nxt)
            start(hb_ref, bufb_ref, semb_ref, j * _N_STEP + s + 1, nxt)

        if swap_pass1_sems:
            @pl.when((j == 1) & (s == 0))
            def _swapped():
                wait(ha_ref, bufa_ref, semb_ref, 0, 0)
                wait(hb_ref, bufb_ref, sema_ref, 0, 0)

            @pl.when((j == 0) | (s == 1))
            def _straight():
                wait(ha_ref, bufa_ref, sema_ref, slot, slot)
                wait(hb_ref, bufb_ref, semb_ref, slot, slot)
        else:
            wait(ha_ref, bufa_ref, sema_ref, slot, slot)
            wait(hb_ref, bufb_ref, semb_ref, slot, slot)

        out_ref[...] = bufa_ref[slot] + bufb_ref[slot]

        @pl.when((s + 1 == _N_STEP) & (j + 1 < _N_PASS))
        def _tail():
            start(ha_ref, bufa_ref, sema_ref, (j + 1) * _N_STEP, 0)
            start(hb_ref, bufb_ref, semb_ref, (j + 1) * _N_STEP, 0)

    return pl.pallas_call(
        kernel, grid=(_N_PASS, _N_STEP),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8,), lambda j, s: (j * _N_STEP + s,)),
        scratch_shapes=[pltpu.VMEM((2, 8), jnp.float32),
                        pltpu.VMEM((2, 8), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((_N_PASS * _N_STEP * 8,), jnp.float32),
        interpret=True,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(xa, xb)


def _priority_toy(x_small, x_big, *, small_first):
    """A 4096-byte and an 8192-byte copy at every grid step.  The clean
    variant issues the bulky one first (the kernels' convention); the
    mutation swaps the issue order."""
    n = 2

    def kernel(hs_ref, hb_ref, out_ref, small_ref, big_ref,
               s_sem, b_sem):
        s = pl.program_id(0)

        def start_small():
            pltpu.make_async_copy(hs_ref.at[pl.ds(s * 8, 8)],
                                  small_ref.at[0], s_sem.at[0]).start()

        def start_big():
            pltpu.make_async_copy(hb_ref.at[pl.ds(s * 8, 8)],
                                  big_ref.at[0], b_sem.at[0]).start()

        if small_first:
            start_small()
            start_big()
        else:
            start_big()
            start_small()

        pltpu.make_async_copy(hb_ref.at[pl.ds(s * 8, 8)],
                              big_ref.at[0], b_sem.at[0]).wait()
        pltpu.make_async_copy(hs_ref.at[pl.ds(s * 8, 8)],
                              small_ref.at[0], s_sem.at[0]).wait()
        out_ref[...] = small_ref[0] + big_ref[0][:, :128]

    return pl.pallas_call(
        kernel, grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda s: (s, 0)),
        scratch_shapes=[pltpu.VMEM((1, 8, 128), jnp.float32),
                        pltpu.VMEM((1, 8, 256), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,)),
                        pltpu.SemaphoreType.DMA((1,))],
        out_shape=jax.ShapeDtypeStruct((n * 8, 128), jnp.float32),
        interpret=True,
    )(x_small, x_big)


# ---------------------------------------------------------------------------
# mutation-kill assertions: exactly one rule each
# ---------------------------------------------------------------------------


def test_clean_cross_pass_prefetch_proves_clean_and_runs():
    x = jnp.arange(_N_PASS * _N_STEP * 8, dtype=jnp.float32)
    good = lambda xx: _xpass_toy(xx, mutate=None)
    assert analyze_callable(good, x, label="toy-xpass-good") == []
    # and the schedule it certifies is actually correct
    np.testing.assert_array_equal(np.asarray(good(x)), np.asarray(x))


def test_toy_happens_before_model_is_two_passes():
    x = jnp.zeros((_N_PASS * _N_STEP * 8,), jnp.float32)
    irs = trace_kernel_irs(lambda xx: _xpass_toy(xx), x, label="toy-hb")
    assert len(irs) == 1
    hb = build_order(irs[0])
    assert hb.n_passes == _N_PASS
    # no parallel axis: one chain of 4 points, split at the pass boundary
    assert len(hb.chains) == 1 and len(hb.chains[0]) == _N_PASS * _N_STEP
    locals_ = pass_local_chains(irs[0])
    assert [len(c) for c in locals_] == [_N_STEP, _N_STEP]
    # program edges: ordered within the chain, never across equal points
    assert hb.ordered(0, 3) and not hb.ordered(3, 0) and not hb.ordered(1, 1)


def test_wrong_slot_wait_is_killed_by_cross_pass_war_only():
    x = jnp.zeros((_N_PASS * _N_STEP * 8,), jnp.float32)
    findings = analyze_callable(lambda xx: _xpass_toy(xx, mutate="clobber"),
                                x, label="toy-xpass-clobber")
    assert _rules(findings) == {"cross-pass-war"}, findings
    assert any("still in flight" in f.message for f in findings)


def test_boundary_double_start_is_killed_by_sem_carryover_only():
    x = jnp.zeros((_N_PASS * _N_STEP * 8,), jnp.float32)
    findings = analyze_callable(lambda xx: _xpass_toy(xx, mutate="carryover"),
                                x, label="toy-xpass-carryover")
    assert _rules(findings) == {"sem-carryover"}, findings
    assert any("pass boundary" in f.message for f in findings)


def test_clean_twin_ring_proves_clean():
    xa = jnp.arange(32, dtype=jnp.float32)
    xb = jnp.arange(32, dtype=jnp.float32) * 2
    good = lambda a, b: _twin_ring_toy(a, b, swap_pass1_sems=False)
    assert analyze_callable(good, xa, xb, label="toy-twin-good") == []
    np.testing.assert_array_equal(np.asarray(good(xa, xb)),
                                  np.asarray(xa + xb))


def test_swapped_sems_are_killed_by_prefetch_raw_only():
    xa = jnp.zeros((32,), jnp.float32)
    xb = jnp.zeros((32,), jnp.float32)
    findings = analyze_callable(
        lambda a, b: _twin_ring_toy(a, b, swap_pass1_sems=True),
        xa, xb, label="toy-twin-swapped")
    assert _rules(findings) == {"prefetch-raw"}, findings
    # both buffers' first consumptions wait on the wrong filler
    assert len(findings) == 2
    assert any("does not wait on its filler" in f.message for f in findings)


def test_big_copy_first_proves_clean():
    xs = jnp.ones((16, 128), jnp.float32)
    xb = jnp.ones((16, 256), jnp.float32)
    good = lambda a, b: _priority_toy(a, b, small_first=False)
    assert analyze_callable(good, xs, xb, label="toy-prio-good") == []
    np.testing.assert_array_equal(np.asarray(good(xs, xb)),
                                  np.full((16, 128), 2.0, np.float32))


def test_small_copy_first_is_killed_by_dma_priority_only():
    xs = jnp.zeros((16, 128), jnp.float32)
    xb = jnp.zeros((16, 256), jnp.float32)
    findings = analyze_callable(
        lambda a, b: _priority_toy(a, b, small_first=True),
        xs, xb, label="toy-prio-bad")
    assert _rules(findings) == {"dma-priority"}, findings
    assert any("8192" in f.message and "4096" in f.message for f in findings)


def test_order_rule_catalog():
    assert set(ORDER_RULES) == {"cross-pass-war", "sem-carryover",
                                "prefetch-raw", "dma-priority"}


# ---------------------------------------------------------------------------
# shipped kernels: prefetch-on == prefetch-off, bit for bit
# ---------------------------------------------------------------------------


def _matrix(seed=7):
    return BSR.random(np.random.default_rng(seed), (96, 128), (32, 32), 0.4)


def _rhs(seed=1, n=64):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal((128, n)).astype(np.float32))


@pytest.mark.parametrize("n_lanes,unroll", [(1, 1), (2, 1), (2, 2)])
@pytest.mark.parametrize("quantize", [None, "int8", "fp8"])
def test_prefetch_numerical_parity(n_lanes, unroll, quantize):
    a = _matrix()
    x = _rhs()
    kw = dict(policy="segment", n_lanes=n_lanes, unroll=unroll, fold_len=3,
              quantize=quantize, cache=False)
    base = plan_matmul(a, **kw)
    pf = plan_matmul(a, prefetch="cross_pass", **kw)
    assert pf.prefetch == "cross_pass" and base.prefetch is None
    # bn=32 over 64 columns -> two N-tile passes, so the cross-pass tail
    # really executes; the mode re-times copies and must change nothing
    want = np.asarray(base(x, bn=32, backend="interpret"))
    got = np.asarray(pf(x, bn=32, backend="interpret"))
    np.testing.assert_array_equal(got, want)


def test_prefetch_parity_through_transposed_backward_pass():
    a = BSR.random(np.random.default_rng(8), (96, 128), (32, 32), 0.4)
    x = _rhs(2, 64)

    def grad_of(plan):
        def loss(xx):
            return jnp.sum(apply_plan(plan, xx, bn=32,
                                      backend="interpret") ** 2)
        return np.asarray(jax.grad(loss)(x))

    base = plan_matmul(a, with_grad=True, n_lanes=2, unroll=2, cache=False)
    pf = plan_matmul(a, with_grad=True, n_lanes=2, unroll=2, cache=False,
                     prefetch="cross_pass")
    # the knob propagates into the transposed (transpose_lhs) grad plan
    assert pf.grad_plan.prefetch == "cross_pass"
    assert pf.grad_plan.transpose_lhs
    np.testing.assert_array_equal(grad_of(pf), grad_of(base))


def test_shipped_prefetch_kernel_is_certified_non_vacuously():
    a = _matrix()
    x = _rhs()
    pf = plan_matmul(a, n_lanes=2, unroll=2, cache=False,
                     prefetch="cross_pass")
    fn = lambda xx: pf(xx, bn=32, backend="interpret")
    assert analyze_callable(fn, x, label="spmm-prefetch-cert") == []
    # the proof is about a real two-pass model: prefetch demotes the
    # N-tile axis to "arbitrary", so the ordering rules are not vacuous
    irs = trace_kernel_irs(fn, x, label="spmm-prefetch-cert")
    assert any(build_order(ir).n_passes == 2 for ir in irs)
    # the drained schedule keeps the N-tile axis parallel: single pass
    base = plan_matmul(a, n_lanes=2, unroll=2, cache=False)
    base_irs = trace_kernel_irs(lambda xx: base(xx, bn=32,
                                                backend="interpret"), x)
    assert all(build_order(ir).n_passes == 1 for ir in base_irs)


# ---------------------------------------------------------------------------
# traffic accounting + verifier agreement
# ---------------------------------------------------------------------------


def test_prefetch_traffic_recorded_and_verifier_agrees():
    a = _matrix()
    base = plan_matmul(a, n_lanes=2, unroll=2, cache=False)
    pf = plan_matmul(a, n_lanes=2, unroll=2, cache=False,
                     prefetch="cross_pass")
    t_base, t_pf = dict(base.traffic_items), dict(pf.traffic_items)
    # re-timing copies moves no extra bytes and drops none
    for key in ("a_bytes", "b_bytes", "c_bytes", "total",
                "a_fetches", "b_fetches"):
        assert t_base[key] == t_pf[key], key
    assert t_base["prefetch_fetches"] == 0
    assert t_pf["prefetch_fetches"] > 0
    verify_plan(pf, level="full").raise_if_findings()
    # a plan lying about its overlapped-fetch count is rejected
    bad_items = tuple((k, v + 1 if k == "prefetch_fetches" else v)
                      for k, v in pf.traffic_items)
    res = verify_plan(pf.replace(traffic_items=bad_items), level="full")
    assert any(f.invariant == "traffic-agreement" and "prefetch" in f.message
               for f in res.findings)


def test_fetch_flags_identical_under_prefetch():
    stream = np.array([5, 5, 7, 7, 3, 3, 3, 9])
    valid = np.array([1, 1, 1, 0, 1, 1, 1, 1])
    f0, s0 = fetch_flags(stream, valid, 2)
    f1, s1 = fetch_flags(stream, valid, 2, prefetch="cross_pass")
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(s0, s1)
    with pytest.raises(ValueError, match="prefetch"):
        fetch_flags(stream, valid, 2, prefetch="bogus")


def test_lane_traffic_prefetch_fetch_counts():
    m = np.zeros(4, np.int64)
    k = np.array([0, 0, 1, 1])
    seg = np.array([1, 0, 0, 0])
    valid = np.ones(4, bool)
    base = lane_traffic_spmm(m, k, seg, valid, 1, 32, 32, 64, unroll=1)
    assert base["prefetch_fetches"] == 0
    # one lane, unroll=1 head window: one A fetch + one B fetch
    pf1 = lane_traffic_spmm(m, k, seg, valid, 1, 32, 32, 64, unroll=1,
                            prefetch="cross_pass")
    assert pf1["prefetch_fetches"] == 2
    # unroll=2 widens the window to [0, 0]: two A fetches, one B fetch
    pf2 = lane_traffic_spmm(m, k, seg, valid, 1, 32, 32, 64, unroll=2,
                            prefetch="cross_pass")
    assert pf2["prefetch_fetches"] == 3
    # two lanes: each lane's first item fetches A and B
    pf3 = lane_traffic_spmm(m, k, np.array([1, 0, 1, 0]), valid, 2,
                            32, 32, 64, unroll=1, prefetch="cross_pass")
    assert pf3["prefetch_fetches"] == 4
    # byte totals never move
    for key in ("a_bytes", "b_bytes", "c_bytes", "total"):
        assert base[key] == pf1[key] == pf2[key]
    with pytest.raises(ValueError, match="prefetch"):
        lane_traffic_spmm(m, k, seg, valid, 1, 32, 32, 64, prefetch="eager")
    # spgemm has no N-tile pass axis: the knob is a validated no-op
    two = lane_traffic_spgemm(np.array([0, 1]), np.array([0, 1]),
                              np.array([0, 0]), np.array([1, 0]),
                              np.ones(2, bool), 1, 32, 32, 32,
                              prefetch="cross_pass")
    assert two["prefetch_fetches"] == 0
    with pytest.raises(ValueError, match="prefetch"):
        lane_traffic_spgemm(np.array([0]), np.array([0]), np.array([0]),
                            np.array([1]), np.ones(1, bool), 1, 32, 32, 32,
                            prefetch="now")


# ---------------------------------------------------------------------------
# plumbing: plan aux, planner validation, cost model, autotuner
# ---------------------------------------------------------------------------


def test_prefetch_survives_pytree_roundtrip_and_fingerprints():
    a = _matrix()
    pf = plan_matmul(a, cache=False, prefetch="cross_pass")
    leaves, treedef = jax.tree_util.tree_flatten(pf)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.prefetch == "cross_pass"
    # a different schedule mode is a different cached plan
    assert pf.fingerprint != plan_matmul(a, cache=False).fingerprint


def test_plan_matmul_validates_prefetch():
    a = _matrix()
    assert None in PREFETCH_MODES and "cross_pass" in PREFETCH_MODES
    with pytest.raises(ValueError, match="prefetch"):
        plan_matmul(a, cache=False, prefetch="bogus")
    with pytest.raises(ValueError, match="pipeline"):
        plan_matmul(a, cache=False, pipeline=False, prefetch="cross_pass")


def test_cost_model_prefetch_credit():
    m = CostModel(bytes_per_us=1.0, step_us=2.0, prefetch_step_credit=1.0)
    kw = dict(traffic_bytes=0.0, n_lanes=1, lane_len=4, unroll=1)
    # one hidden boundary drain per N-tile transition
    off = m.cost_us(n_tiles_n=3, **kw)
    on = m.cost_us(n_tiles_n=3, prefetch=True, **kw)
    assert off - on == pytest.approx(2 * 2.0)
    # a single tile has no boundary to hide
    assert m.cost_us(n_tiles_n=1, prefetch=True, **kw) \
        == m.cost_us(n_tiles_n=1, **kw)
    # the legacy path never earns the credit
    assert m.cost_us(n_tiles_n=3, pipelined=False, prefetch=True, **kw) \
        == m.cost_us(n_tiles_n=3, pipelined=False, **kw)
    # shipped defaults: hardware overlaps the drain, the interpreter
    # replays copies inline and must not prefer prefetch on phantom credit
    assert DEFAULT_TPU.prefetch_step_credit == 1.0
    assert DEFAULT_INTERPRET.prefetch_step_credit == 0.0


def test_autotune_sweeps_and_pins_prefetch():
    a = _matrix()
    res = autotune_matmul(a, n_cols_hint=256, cache=False)
    swept = {s.candidate.prefetch for s in res.candidates}
    assert swept == {None, "cross_pass"}
    # cross-pass prefetch only exists on the explicit DMA pipeline
    assert all(s.candidate.prefetch is None
               for s in res.candidates if not s.candidate.pipeline)
    # the default knob point still exists (Candidate defaults prefetch=None)
    assert any(s.candidate == Candidate("segment", None, 1, 1, 512, True)
               for s in res.candidates)
    # interpret objective: zero credit + tie-break keep the drained mode
    res_i = autotune_matmul(a, n_cols_hint=256, objective="interpret",
                            cache=False)
    assert res_i.best.candidate.prefetch is None
    # a pinned knob flows through plan_kwargs into a verified plan
    pinned = autotune_matmul(a, n_cols_hint=256, cache=False,
                             pins={"pipeline": True,
                                   "prefetch": "cross_pass"})
    assert pinned.best.candidate.prefetch == "cross_pass"
    kw = pinned.plan_kwargs()
    assert kw["prefetch"] == "cross_pass"
    plan = plan_matmul(a, 256, cache=False, **kw)
    assert plan.prefetch == "cross_pass"
    verify_plan(plan, level="full").raise_if_findings()
