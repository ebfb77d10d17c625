"""The reduction of the program's own spans and programs
(``harness/program.py``), on hand-built inputs and on a small trace
recorded on a v5e chip (``data/program_probe.xplane.pb``), the five metrics
that read them, and ``bench/trace_cell.py``'s plumbing on the CPU."""
import collections
import json
import os
import subprocess
import sys

import pytest

import tiny
from harness import program, spec, trace
from harness.record import Run, Spans

GB = "segfold.engine."


def _reader(name):
    return spec.metric_reader(name).read


def _run(config=None, counters=None, prog=None, summary=None):
    run = Run(workload="tiny", config=config or {}, traffic={},
              device_kind="TPU v5 lite", setup_s=1.0, window=(0.0, 10.0),
              spans=Spans(), counters=dict(counters or {}))
    run.trace = summary
    if prog is not None:
        run.extra["program"] = prog
    return run


def _prog(idle=(), spans=(), modules=None):
    return program.ProgramSummary(idle_by_program_span=list(idle),
                                  span_names=frozenset(spans),
                                  module_runs=modules or {})


def test_module_names_drop_the_fingerprint():
    assert program.module_name("jit_engine_decode(8812630943)") \
        == "jit_engine_decode"
    assert program.module_name("jit_segment_spgemm(77)") \
        == "jit_segment_spgemm"
    assert program.module_name("jit_f") == "jit_f"


def test_summarize_splits_idle_by_the_innermost_program_span():
    ops = [trace.Op("fusion", 10, 40, False, 0),
           trace.Op("segment_spmm", 60, 90, True, 0)]
    tr = trace.Trace(ops, [trace.Span("bench.window", 0, 100)], 1, 0.0)
    spans = [trace.Span(GB + "step", 0, 100),
             trace.Span(GB + "prepare", 0, 10),
             trace.Span(GB + "sync", 40, 95)]
    runs = [trace.Op("jit_engine_decode", 5, 95, False, 0),
            trace.Op("jit_engine_decode", 95, 120, False, 0)]
    s = program.summarize(tr, spans, runs, 0, 100)
    # gaps [0, 10] in prepare, [40, 60] and [90, 95] in sync, [95, 100] in
    # the step alone
    assert dict(s.idle_by_program_span) == {
        GB + "prepare": pytest.approx(10e-9), GB + "sync": pytest.approx(
            25e-9), GB + "step": pytest.approx(5e-9)}
    assert s.module_runs == {"jit_engine_decode": [pytest.approx(90e-9)]}
    assert s.modules() == [("jit_engine_decode", pytest.approx(90e-9), 1)]
    assert s.idle_in([GB + "prepare", GB + "update"]) == pytest.approx(10e-9)
    assert s.idle_in([GB + "admit"]) is None


def test_decode_step_ms_is_the_median_decode_run():
    read = _reader("decode_step_ms.offline")
    prog = _prog(modules={"jit_engine_decode": [0.08, 0.07, 0.09, 0.5],
                          "jit_engine_prefill": [0.02]})
    assert read(_run(prog=prog)) == pytest.approx(85.0)
    assert read(_run(prog=_prog())) is None
    assert read(_run()) is None


def test_host_gap_share_reads_the_engines_own_host_work():
    read = _reader("host_gap_share.offline")
    prog = _prog(idle=[("host:none", 1.0), (GB + "sync", 0.5),
                       (GB + "dispatch", 0.2), (GB + "prepare", 0.1)],
                 spans=[GB + s for s in ("step", "prepare", "dispatch",
                                         "sync", "update")])
    assert read(_run(prog=prog)) == pytest.approx(3.0)
    # a program without the spans reads nothing, not 0
    assert read(_run(prog=_prog(idle=[("host:none", 1.0)]))) is None


def test_dispatch_gap_share_reads_idle_inside_execute():
    read = _reader("dispatch_gap_share.spgemm")
    prog = _prog(idle=[("host:none", 0.3), ("segfold.execute", 0.4),
                       ("segfold.execute.launch", 0.1)],
                 spans=["segfold.execute", "segfold.execute.launch"])
    assert read(_run(prog=prog)) == pytest.approx(5.0)
    assert read(_run(prog=_prog(idle=[("host:none", 0.3)]))) is None


def test_ffn_col_util_is_useful_over_computed_columns():
    read = _reader("ffn_col_util.offline")
    run = _run(counters={"spmm_cols_useful": 42, "spmm_cols_computed": 768})
    assert read(run) == pytest.approx(100 * 42 / 768)
    assert read(_run(counters={"compiles_in_window": 0})) is None


def test_segment_spmm_roofline_counts_stored_blocks_per_program_run():
    read = _reader("segment_spmm_roofline.offline")
    cfg = {"ffn_block": 128, "num_hidden_layers": 2, "hidden_size": 256,
           "intermediate_size": 512}
    counters = {"decode_steps": 10, "prefill_chunks": 2, "decode_rows": 30,
                "prefill_tokens": 50}
    summary = trace.Summary(window_s=10.0, busy_s=9.0,
                            mosaic_s={"segment_spmm_pipeline": 2e-3,
                                      "segment_spgemm_pipeline": 1.0},
                            top_ops=[], idle_by_span=[])
    run = _run(cfg, counters, summary=summary)
    run.extra["ffn_blocks"] = [4, 4, 4]
    blocks = 12 * 2                                 # 3 projections, 2 layers
    weights = 12 * blocks * 128 * 128 * 2           # 12 program runs, bf16
    acts = 3 * (256 + 512) * 2 * 80 * 2             # 80 real tokens
    flops = 2 * blocks * 128 * 128 * 80
    least = max((weights + acts) / 819e9, flops / 197e12)
    assert read(run) == pytest.approx(100 * least / 2e-3)
    run.counters = {}
    assert read(run) is None                        # no engine counters


def test_trace_cell_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "bench/trace_cell.py", "--workload", "table3.banded",
         "--seed", str(2**31 + 99), "--seconds", "1"],
        cwd=tiny.BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and "needs 1 tpu chip" in p.stderr


def test_program_tracer_snapshots_the_engine_at_the_window_edges():
    """A serving run through ``ProgramTracer`` on the CPU: the
    engine's counters change by exactly the window's work."""
    trace_cell = spec.load_module(tiny.BENCH / "trace_cell.py")
    serving = spec.driver("serving")
    tracer = trace_cell.ProgramTracer()
    ctx = tiny.context(tiny.phi3_config(), tiny.offline_traffic(), seed=3,
                       seconds=1.0)
    ctx.tracer = tracer
    run = serving.run(ctx, patch=tracer.attach)
    assert tracer.engine is None and len(tracer.counts) == 2
    change = tracer.counter_change()
    # every decode step in the window is one ``bench.step`` span
    steps = sum(1 for name, s, e in run.spans.items
                if name == "step" and run.window[0] <= s <= run.window[1])
    assert change["decode_steps"] == steps > 0
    summary, prog = tracer.program_summary()
    assert not os.path.exists(tracer.dir)
    assert {GB + "step", GB + "sync"} <= prog.span_names
    run.trace = summary
    run.extra["program"] = prog
    run.counters.update(change)
    util = _reader("ffn_col_util.offline")(run)
    assert 0 < util <= 100
    json.dumps(prog.idle_by_program_span)


# -- on a trace recorded on a v5e chip (data/record_program_probe.py) -------

PROBE = str(tiny.BENCH / "tests" / "data" / "program_probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    tr, spans, runs = program.load(PROBE)
    lo, hi = trace.window_of(tr, "bench.window")
    return tr, spans, runs, lo, hi


def _count(spans, name):
    return sum(s.name == "segfold." + name for s in spans)


def test_probe_keeps_the_benchmark_spans_apart(probe):
    tr, spans, runs, lo, hi = probe
    assert sorted({s.name for s in tr.spans}) == ["bench.sleep",
                                                  "bench.window"]
    assert spans and all(s.name.startswith("segfold.") for s in spans)


def test_probe_program_runs_match_the_program_spans(probe):
    tr, spans, runs, lo, hi = probe
    inside = collections.Counter(r.name for r in runs
                                 if lo <= r.start and r.end <= hi)
    # three requests: prompts of 40, 9 and 21 tokens in 16-token chunks
    assert _count(spans, "engine.admit") == 3
    assert inside["jit_engine_prefill"] == _count(spans, "engine.prefill") \
        == 3 + 1 + 2
    assert inside["jit_engine_decode"] == _count(spans, "engine.step") \
        == _count(spans, "engine.dispatch") > 0
    assert _count(spans, "execute") == _count(spans, "execute.launch") == 3
    assert inside["jit_segment_spgemm"] == 3


def test_probe_decode_runs_end_inside_their_step(probe):
    """On the host clock each decode run starts after its dispatch span
    starts and ends before its sync span ends (within 0.1 ms)."""
    tr, spans, runs, lo, hi = probe
    syncs = [s for s in spans if s.name == "segfold.engine.sync"]
    dispatches = [s for s in spans if s.name == "segfold.engine.dispatch"]
    decodes = [r for r in runs if r.name == "jit_engine_decode"
               and lo <= r.start and r.end <= hi]
    for d, sync, run in zip(dispatches, syncs, decodes):
        assert run.start >= d.start - 1e5 and run.end <= sync.end + 1e5


def test_probe_idle_split_adds_up_and_leaves_the_bench_split_alone(probe):
    tr, spans, runs, lo, hi = probe
    s = trace.summarize(tr, lo, hi)
    p = program.summarize(tr, spans, runs, lo, hi)
    idle = dict(p.idle_by_program_span)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-9)
    # the 10 ms sleep lies outside every program span
    assert idle["host:none"] > 0.0095
    assert dict(s.idle_by_span)["bench.sleep"] > 0.0095
    assert p.idle_in(["segfold.engine.sync"]) is not None
    assert {row[0] for row in p.modules()} >= {"jit_engine_decode",
                                           "jit_engine_prefill"}


def test_probe_kernels_carry_their_variant_names(probe):
    tr = probe[0]
    kernels = {o.name for o in tr.ops if o.mosaic}
    assert "segment_spgemm_pipeline" in kernels
    assert "segment_spmm_pipeline" in kernels
    assert all(k.startswith(("segment_spmm", "segment_spgemm"))
               for k in kernels)
