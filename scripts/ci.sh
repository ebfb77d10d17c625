#!/usr/bin/env bash
# Tier-1 CI: import hygiene for the repro.api layering + the full test suite.
#
#   scripts/ci.sh            # run everything
#
# The import checks run each entry point in a FRESH interpreter so
# order-dependent circular imports can't hide behind a warmed sys.modules
# (repro.api sits above repro.core and beside repro.kernels; ops.py shims
# back into repro.api, which is only legal because core never imports api).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== import-cycle lint =="
# layering rule: repro.core must never import repro.api (registry lives in
# core precisely so the dependency points one way).
if grep -rnE "^[^#]*(from|import) +repro\.api" src/repro/core; then
    echo "FAIL: repro.core imports repro.api (layering violation)" >&2
    exit 1
fi
# repro.tune sits above repro.api (the search builds schedules through the
# planner), so api may only reach back into tune lazily inside a function
# body — a module-level import would be a cycle.
if grep -rnE "^(from|import) +repro\.tune" src/repro/api; then
    echo "FAIL: repro.api imports repro.tune at module level (cycle)" >&2
    exit 1
fi
# every entry point must import clean in isolation (both directions of the
# kernels<->api shim seam, plus the consumers).
for m in repro.api repro.core repro.kernels repro.kernels.ops \
         repro.models.sparse_ffn repro.runtime.serve repro.models \
         repro.tune; do
    python -c "import $m" || { echo "FAIL: import $m" >&2; exit 1; }
done
# the seam both ways in one process
python -c "import repro.api, repro.kernels"
python -c "import repro.kernels, repro.api"
# analysis sits between core and api: it must import without either kernels
# or the planner warmed (core-only at module level)
python -c "import repro.analysis"
echo "import lint OK"

echo "== static verification =="
# (1) kernel analyzer: trace every shipped Pallas kernel (Segment spmm/
# spgemm variants x the knob grid, flash_attention, moe_gemm, rg_lru) and
# run the syntactic hazard lint plus the symbolic proofs — index-range,
# parallel-race, ring-slot-war, sem-balance, vmem-budget (see
# repro.analysis: accesses/ranges/races/budget).  (2) plan verifier sweep:
# build plans from the sim pattern corpus across the knob grid (lanes x
# unroll x quantize, spmm + spgemm + degenerates), prove the full
# invariant catalog on each, and emit the machine-readable findings
# artifact (VERIFY_plans.json) for upload/diffing.  Both exit 1 on any
# finding.
python -m repro.analysis.jaxpr_lint -q
python scripts/verify_plans.py --level full -q --json VERIFY_plans.json
python - <<'EOF'
import json
d = json.load(open("VERIFY_plans.json"))
assert d["summary"]["ok"] and d["summary"]["n_findings"] == 0, d["summary"]
assert d["summary"]["n_plans"] > 100, d["summary"]   # the sweep ran fully
# every pattern autotuned under both objectives, each winner checked
assert d["summary"]["n_autotuned"] >= 12, d["summary"]
print(f"verify artifact OK: {d['summary']['n_plans']} plans clean "
      f"({d['summary']['n_autotuned']} autotuned winners) "
      f"at level={d['level']!r}")
EOF

echo "== serve bench smoke =="
# end-to-end continuous-batching engine + throughput tracking from this PR
# on: BENCH_serve.json carries prefill/decode tok/s for the perf trajectory.
python benchmarks/serve_bench.py --smoke --quant-repeats 5 --out BENCH_serve.json
python - <<'EOF'
import json
d = json.load(open("BENCH_serve.json"))
assert d["prefill_tok_s"] > 0 and d["decode_tok_s"] > 0, d
assert not d["retraced_after_warmup"], d["compiled_shapes"]
# quantized serving (fp32/int8/fp8 engines on the block-sparse-FFN
# variant): every quantized plan the bench builds verifies clean at
# level="full", no engine retraces after warmup, greedy drift vs fp32
# stays inside the documented bounds, and int8 decode throughput is no
# worse than fp32 modulo noise — the 0.85 floor is what the tiny smoke
# decode phase (requests x max_new = 16 tokens/pass) supports on a loaded
# runner even with 5 interleaved best-of passes (interpret mode moves the
# same flops either way; the weight-byte win needs real hardware), while
# the full-config artifact tracks the raw ratio for the perf trajectory
q = d["quant"]["modes"]
for mode in ("fp32", "int8", "fp8"):
    assert q[mode]["decode_tok_s"] > 0, (mode, q[mode])
    assert not q[mode]["retraced_after_warmup"], (mode, q[mode])
for mode, drift_bound in (("int8", 0.25), ("fp8", 0.5)):
    assert q[mode]["verify_findings"] == 0, (mode, q[mode])
    assert q[mode]["greedy_drift_fraction"] <= drift_bound, (mode, q[mode])
    # the deterministic form of the quantization win: modeled FFN weight
    # bytes per decode step must drop at least 2x vs fp32 (1-byte payloads
    # + fp32 scales price out near 4x; 2x leaves headroom for rowwise)
    assert q[mode]["ffn_weight_traffic_cut_vs_fp32"] >= 2.0, (mode, q[mode])
assert q["int8"]["decode_tok_s"] >= 0.85 * q["fp32"]["decode_tok_s"], \
    (q["int8"]["decode_tok_s"], q["fp32"]["decode_tok_s"])
print(f"serve bench OK: prefill {d['prefill_tok_s']:.1f} tok/s, "
      f"decode {d['decode_tok_s']:.1f} tok/s; quant decode fp32 "
      f"{q['fp32']['decode_tok_s']:.1f} / int8 "
      f"{q['int8']['decode_tok_s']:.1f} / fp8 "
      f"{q['fp8']['decode_tok_s']:.1f} tok/s, int8 drift "
      f"{q['int8']['greedy_drift_fraction']:.3f}, int8 weight-byte cut "
      f"{q['int8']['ffn_weight_traffic_cut_vs_fp32']:.2f}x")
EOF

echo "== kernel bench smoke =="
# lane-parallel Segment kernels: BENCH_kernels.json carries the traffic
# ratios, interpret wall time, and dense-oracle parity for 1/2/4 lanes.
python -m benchmarks.kernel_bench --repeats 12 --out BENCH_kernels.json
python - <<'EOF'
import json
d = json.load(open("BENCH_kernels.json"))
lanes = d["lanes"]
# structural guard first: the balanced bench case must pack lanes with zero
# padding, so every lane count executes the same grid-step total (interpret
# mode emulates the grid sequentially — lanes can only tie on wall time
# here; the concurrency win needs real hardware)
for n, row in lanes.items():
    assert row["padded_items"] == 0, (n, row)
    assert row["max_err"] < 1e-4, (n, row["max_err"])
single = lanes["1"]["interpret_us_min"]
multi = min(lanes[n]["interpret_us_min"] for n in lanes if n != "1")
# best multi-lane config must not lose to single-lane (min of interleaved
# warm calls — the floor is far more load-stable than the median).  The
# padded_items==0 guard above already pins equal step counts, so this bound
# only catches gross per-step overhead creep; the slack is generous because
# wall time on a loaded runner is noise-vs-noise
assert multi <= single * 1.25, (multi, single)
# segment must stay no worse than the two static built-in baselines (same
# 0.1% tolerance as the test suite; custom-registered policies are reported
# in the JSON but deliberately not gated)
for case, ratios in d["traffic"].items():
    for p in ("gustavson", "outer"):
        r = ratios[f"segment_traffic_saving_vs_{p}"]
        assert r >= 0.999, (case, p, r)
# quantized block storage: the standard weight-bound case must cut modeled
# traffic bytes (int8 payload + per-block scales vs fp32 tiles) by >= 1.67x
# (<= 0.6x fp32) and stay under the documented normalized error bounds
# (docs/API.md: int8 5e-2, fp8 1e-1 vs the dense fp32 oracle)
q = d["quant"]
for mode in ("int8", "fp8"):
    assert q[mode]["traffic_total_bytes"] <= 0.6 * q["fp32"]["traffic_total_bytes"], \
        (mode, q[mode]["traffic_total_bytes"], q["fp32"]["traffic_total_bytes"])
assert q["fp32"]["max_err"] < 1e-4, q["fp32"]
assert q["int8"]["max_err"] < 5e-2, q["int8"]
assert q["fp8"]["max_err"] < 1e-1, q["fp8"]
# DMA pipeline: the bench plans must verify clean under the full static
# invariant catalog (repro.analysis.verify_plan level="full") — which
# includes the traffic-agreement invariant, the exact model-vs-fetch-flag
# count equality this block used to assert inline.  The raw counts stay in
# the JSON for trending; the spgemm case must carry real work (an empty
# triple list would verify vacuously).
p = d["pipeline"]
assert p["verify_findings"] == 0, (p["verify_findings"],
                                   p["verify_finding_ids"])
assert p["spgemm_model_b_fetches"] > 0, p
# verify="full" must stay cheap: < 10% amortized plan-build wall time
# (one template verification per cache miss + an O(1) per-realize check)
assert p["verify_build_overhead"] < 0.10, p["verify_build_overhead"]
assert p["max_err_pipelined"] < 1e-4, p
# static VMEM budgets (repro.analysis.plan_vmem_bytes) must be reported per
# case and fit the per-core limit the planner's vmem_limit_bytes gate uses
from repro.analysis import DEFAULT_VMEM_LIMIT_BYTES
for n, row in lanes.items():
    assert 0 < row["vmem_bytes"] <= DEFAULT_VMEM_LIMIT_BYTES, (n, row)
for mode, row in q.items():
    assert 0 < row["vmem_bytes"] <= DEFAULT_VMEM_LIMIT_BYTES, (mode, row)
for key in ("vmem_bytes_pipelined", "vmem_bytes_spgemm"):
    assert 0 < p[key] <= DEFAULT_VMEM_LIMIT_BYTES, (key, p[key])
# cross-pass DMA prefetch: the mode only moves WHEN copies issue, never
# WHICH — so the two modes must agree bit-exactly, the traffic model's
# overlapped-fetch count must equal the independent head-window fetch-flag
# sum EXACTLY, and both modes must certify clean under the full invariant
# catalog plus the happens-before rules (cross-pass-war / sem-carryover /
# prefetch-raw / dma-priority): no prefetch schedule ships uncertified.
pf = d["prefetch"]
assert pf["parity_err"] == 0.0, pf
assert pf["max_err"] < 1e-4, pf
assert pf["n_tiles_n"] >= 2, pf            # cross-pass tail actually ran
assert pf["model_prefetch_fetches"] == pf["flag_prefetch_fetches"] > 0, pf
assert pf["verify_findings"] == 0, pf
assert pf["order_findings"] == 0, pf
# interpret wall ratio: the interpreter replays every DMA inline AND
# evaluates the prefetch tail/prologue guards each grid step, so prefetch
# cannot win here (steady state ~1.25-1.3x; the overlap win needs real
# hardware — cost model prices it via prefetch_step_credit, zero on the
# interpret objective).  Gate generously to catch pathological creep only.
assert pf["interpret_ratio_vs_no_prefetch"] <= 1.5, pf
# autotuner: on every case the searched schedule must match or beat the
# default knobs on modeled traffic bytes (the search objective is exact
# there) and stay within wall-time noise of the default (min of interleaved
# warm calls; the model can only trade bytes for steps it also prices).
# Every winner must verify clean at level="full", fit the static VMEM
# budget, and stay numerically exact; at least one case must dispatch a
# non-segment dataflow (the staircase pattern breaks SELECTA chaining, so
# gustavson wins it statically).
at = d["autotune"]
n_cases = 0
non_segment = []
for case, row in at.items():
    if case == "cost_model":
        continue
    n_cases += 1
    assert row["tuned_traffic_bytes"] <= row["default_traffic_bytes"], \
        (case, row["tuned_traffic_bytes"], row["default_traffic_bytes"])
    assert row["tuned_us_min"] <= row["default_us_min"] * 1.25, \
        (case, row["tuned_us_min"], row["default_us_min"])
    assert row["verify_findings"] == 0, (case, row["verify_findings"])
    assert 0 < row["vmem_bytes"] <= DEFAULT_VMEM_LIMIT_BYTES, \
        (case, row["vmem_bytes"])
    assert row["tuned_max_err"] < 1e-4, (case, row["tuned_max_err"])
    assert row["default_max_err"] < 1e-4, (case, row["default_max_err"])
    if row["policy"] != "segment":
        non_segment.append((case, row["policy"]))
assert n_cases >= 4, n_cases
assert non_segment, {c: r["policy"] for c, r in at.items()
                     if c != "cost_model"}
cm = at["cost_model"]
assert cm["bytes_per_us"] > 0 and cm["step_us"] > 0, cm
saved = sum(r["default_traffic_bytes"] - r["tuned_traffic_bytes"]
            for c, r in at.items() if c != "cost_model")
print(f"kernel bench OK: interpret 1-lane {single:.0f}us, "
      f"best multi-lane {multi:.0f}us, "
      f"max_err {max(r['max_err'] for r in lanes.values()):.2e}, "
      f"int8 traffic {q['int8']['traffic_ratio_vs_fp32']:.2f}x smaller "
      f"(err {q['int8']['max_err']:.2e}), "
      f"pipeline fetch contract exact "
      f"(a={p['flag_a_fetches']}, b={p['flag_b_fetches']}), "
      f"pipelined {p['pipelined_us']:.0f}us, "
      f"prefetch certified ({pf['model_prefetch_fetches']} overlapped "
      f"fetches, parity {pf['parity_err']:.1f}, "
      f"{pf['interpret_ratio_vs_no_prefetch']:.2f}x interpret wall), "
      f"autotune {n_cases} cases ({saved} bytes saved, "
      f"non-segment: {non_segment})")
EOF

echo "== tier-1 tests =="
python -m pytest -x -q
