"""The program's observability: ``Engine.counters()`` against a hand count,
the ``segfold.*`` spans in a CPU profiler trace read back through the
benchmark's trace reader, the names of the Engine's compiled programs, the
named scopes in the decode program, and the Segment SpMM column report."""
import dataclasses
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.configs import REGISTRY, reduced_config
from repro.core.formats import BSR
from repro.models import build_model
from repro.runtime import Engine, Request
from repro.runtime.serve import COUNTERS

BENCH = Path(__file__).resolve().parents[1] / "bench"

# (prompt tokens, new tokens); two slots, buckets (16, 8)
MIX = ((5, 3), (21, 4), (9, 2))


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(reduced_config(REGISTRY["phi3-mini-3.8b"]),
                              dtype="float32", ffn_block_sparse=True,
                              ffn_block=32)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(tiny):
    cfg, model, params = tiny
    return Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8),
                  backend="interpret")


def _requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                    max_new_tokens=m) for n, m in MIX]


def test_engine_counters_match_a_hand_count(tiny):
    cfg, _, params = tiny
    eng = _engine(tiny)
    # float32 compute: the engine holds the weights as given
    weights = {"weights_cast_bytes": 0, "weights_kept_bytes": sum(
        a.nbytes for a in jax.tree.leaves(params))}
    assert eng.counters() == dict.fromkeys(COUNTERS, 0) | weights
    eng.generate(_requests(cfg))
    # chunks: 5 -> [8]; 21 -> [16, 8]; 9 -> [8, 8]
    chunks = [8, 16, 8, 8, 8]
    # slots 0, 1 take requests 0 and 1; step 1 decodes both; step 2,
    # dispatched while step 1 was unread, retires request 0 (3 tokens), so
    # nothing is dispatched after it; request 2 takes slot 0 and step 3
    # retires both
    steps, rows = 3, 2 + 2 + 2
    assert rows == sum(m - 1 for _, m in MIX)   # the first token is prefill's
    calls = 3 * cfg.n_layers                    # up, gate, down in each layer

    def computed(n):
        bn, pad = api.pick_bn(n, 512)           # the interpret backend's
        return n + pad

    assert eng.counters() == {
        "decode_steps": steps, "decode_steps_ahead": 1, "decode_rows": rows,
        "decode_rows_discarded": 0,
        "prefill_chunks": len(chunks), "prefill_tokens": 35,
        "prefill_padded_tokens": sum(chunks),
        "spmm_cols_useful": calls * (rows + 35),
        "spmm_cols_computed": calls * (steps * computed(2)
                                       + sum(map(computed, chunks))),
        # a model without MoE layers routes nothing
        "moe_rows_routed": 0, "moe_rows_computed": 0, "moe_expert_loads": 0,
        **weights}


def test_spmm_report_counts_the_padded_tile_and_repeats():
    rng = np.random.default_rng(0)
    a = BSR.random(rng, (256, 256), (128, 128), 0.5)
    plan = api.plan_matmul(a, (256, 7), backend="pallas")
    x = jax.ShapeDtypeStruct((256, 7), np.float32)
    with obs.spmm_columns() as calls:
        with obs.repeated(4):
            jax.make_jaxpr(lambda x: api.apply_plan(plan, x))(x)
        jax.make_jaxpr(lambda x: api.execute_plan(plan, x))(x)
    assert calls == [(7, 128, 4), (7, 128, 1)]
    assert api.pick_bn(7, 512, align=api.LANE) == (128, 121)
    obs.report_spmm(7, 128)                     # nothing collects: no-op


def test_kernel_variants_carry_distinct_names():
    from repro.analysis.jaxpr_lint import find_pallas_kernels
    rng = np.random.default_rng(0)
    a = BSR.random(rng, (256, 256), (128, 128), 0.5)
    plan = api.plan_matmul(a, (256, 8), backend="pallas", with_grad=True)
    x = jax.ShapeDtypeStruct((256, 8), np.float32)
    # forward y = W x, backward dx = W^T dy on the forward storage
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda x: api.apply_plan(plan, x).sum()))(x)
    assert sorted(name for name, _ in find_pallas_kernels(jaxpr)) == [
        "segment_spmm_pipeline", "segment_spmm_pipeline_tlhs"]


def test_spans_nest_in_a_cpu_profiler_trace(tiny, tmp_path):
    sys.path.append(str(BENCH))
    from harness import trace
    cfg = tiny[0]
    eng = _engine(tiny)
    eng.generate(_requests(cfg, seed=1))        # compiles every shape
    rng = np.random.default_rng(0)
    a = BSR.random(rng, (64, 64), (32, 32), 0.5)
    plan = api.plan_matmul(a, a, backend="interpret")
    api.execute_plan(plan).block_until_ready()
    before = eng.counters()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(_requests(cfg))
        api.execute_plan(plan).block_until_ready()
        _engine(tiny)
    finally:
        jax.profiler.stop_trace()
    after = eng.counters()
    spans = trace.load(trace.find_xplane(str(tmp_path)),
                       span_prefix="segfold.").spans

    def named(name):
        return [s for s in spans if s.name == "segfold." + name]

    def inside(child, parents):
        return [p for p in parents
                if p.start <= child.start and child.end <= p.end]

    admits, steps = named("engine.admit"), named("engine.step")
    assert len(admits) == len(MIX)
    assert len(steps) == after["decode_steps"] - before["decode_steps"]
    prefills = named("engine.prefill")
    assert len(prefills) == after["prefill_chunks"] - before["prefill_chunks"]
    for child in prefills + named("engine.first_token"):
        assert len(inside(child, admits)) == 1
    assert len(named("engine.first_token")) == len(MIX)
    # each call dispatches the next step (prepare, dispatch), then books
    # the one in flight (sync, update); no MIX request ends early, so each
    # call books exactly one step
    phases = {"segfold.engine." + p: p[0] for p in ("prepare", "dispatch",
                                                    "sync", "update")}
    for phase in ("dispatch", "sync"):
        assert len(named("engine." + phase)) == len(steps)
    for step in steps:
        held = sorted((s.start, phases[s.name]) for s in spans
                      if s.name in phases and inside(s, [step]))
        assert re.fullmatch("(pd)?(pd)?su", "".join(p for _, p in held))
        assert not inside(step, admits)
    (execute,), (launch,) = named("execute"), named("execute.launch")
    assert inside(launch, [execute])
    # the engine built inside the trace cast its weights once
    (cast,) = named("engine.cast_weights")
    assert not inside(cast, admits + steps)


def test_engine_programs_are_named(tiny):
    eng = _engine(tiny)
    tok = np.zeros((eng.slots, 1), np.int32)
    pos = np.zeros((eng.slots,), np.int32)
    decode = eng._decode.lower(eng.params, eng.cache, tok, pos)
    prefill = eng._prefill.lower(eng.params, eng.cache, np.int32(0),
                                 np.zeros((1, 8), np.int32), np.int32(0),
                                 np.zeros((1,), np.int32), fresh=True)
    assert decode.as_text().startswith("module @jit_engine_decode")
    assert prefill.as_text().startswith("module @jit_engine_prefill")


def test_decode_program_carries_the_named_scopes(tiny):
    eng = _engine(tiny)
    tok = np.zeros((eng.slots, 1), np.int32)
    pos = np.zeros((eng.slots,), np.int32)
    text = eng._decode.lower(eng.params, eng.cache, tok, pos).as_text(
        debug_info=True)
    for scope in ("attn", "ffn", "lm_head"):
        assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope
