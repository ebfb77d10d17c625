"""The Moonlight expert-share configuration on the CPU at small widths: the
plain reference against the program through the whole serving run, the
float8 control and faults of the timed path failing the configuration's
check (the mean gap off the routing near-ties), the near-tie margin, and
the four readers of the ``moonlight-ep8.offline`` cell."""
import copy

import jax.numpy as jnp
import numpy as np
import pytest

import test_correctness as tc
import tiny
from harness import spec
from harness.record import Run, Spans
from harness.trace import Summary

run_py = spec.load_module(tiny.BENCH / "run.py")
serving_moe = spec.driver("serving_moe")
CONFIG = "moonlight-16b-a3b-ep8"


def moonlight_config(**over) -> dict:
    """The Moonlight configuration file at CPU widths, on the interpret
    backend: 3 layers (the dense one and 2 MoE), 4 of 16 experts held."""
    cfg = copy.deepcopy(spec.load_json(tiny.BENCH / "configs"
                                       / f"{CONFIG}.json"))
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=64,
               num_hidden_layers=3, n_routed_experts=4, vocab_size=500,
               slots=2, max_len=160, backend="interpret", sample_tokens=24)
    cfg["published"] = {"num_hidden_layers": 27, "n_routed_experts": 16}
    cfg.update(over)
    return cfg


def _correct(cfg, run):
    return run_py.judge(cfg, run.extra["checks"])[0]


@pytest.mark.parametrize("fault", [tc._keep_cache, tc._alter_token],
                         ids=["state-unchanged", "token-altered"])
def test_moonlight_fault_is_not_correct(fault):
    cfg = moonlight_config()
    run = serving_moe.run(tiny.context(cfg, tiny.offline_traffic(), seed=5,
                                       seconds=2.0), patch=fault)
    assert run.extra["checks"], "the run finished requests to compare"
    assert not _correct(cfg, run)


def _fp8_experts(engine):
    """The control: every routed and shared expert weight rounded to
    float8 e4m3 with one scale per matrix (its largest magnitude at the
    format's 448)."""
    def fp8(w):
        s = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True) / 448.0
        return ((w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s).astype(w.dtype)
    m = engine.params["layers"]["moe"]
    for k in ("gate", "up", "down"):
        m[k] = fp8(m[k])
        m["shared"][k]["w"] = fp8(m["shared"][k]["w"])


def test_sound_moonlight_run_is_correct():
    cfg = moonlight_config()
    run = serving_moe.run(tiny.context(cfg, tiny.offline_traffic(), seed=5,
                                       seconds=2.0))
    assert run.extra["checks"]["tokens_compared"] >= cfg["sample_tokens"] // 2
    assert _correct(cfg, run)
    c = run.counters
    assert c["compiles_in_window"] == 0
    assert 0 < c["moe_rows_routed"] <= c["moe_rows_computed"]
    assert 0 < c["moe_expert_loads"]


class _Tracer:
    def start(self):
        pass

    def stop(self):
        pass


def test_traced_window_ends_before_the_profiler_is_full(monkeypatch):
    """A traced run serves for at most ``TRACED_SECONDS``; an untraced one
    for the whole ``seconds``."""
    cfg = moonlight_config()
    monkeypatch.setattr(serving_moe, "TRACED_SECONDS", 1.0)
    ctx = tiny.context(cfg, tiny.offline_traffic(), seed=5, seconds=3.0)
    ctx.tracer = _Tracer()
    traced = serving_moe.run(ctx)
    assert 1.0 <= traced.window_s < 2.0
    plain = serving_moe.run(tiny.context(cfg, tiny.offline_traffic(), seed=5,
                                         seconds=3.0))
    assert plain.window_s >= 3.0


def _long_traffic() -> dict:
    """Longer prompts and answers than ``tiny.offline_traffic``: a route
    swapped at a near-tie echoes through the latent cache into the later
    positions of a short sequence far more than into those of the cell's
    ShareGPT-length ones."""
    traffic = tiny.offline_traffic()
    traffic.update(prompt={"dist": "lognormal", "mean": 60, "sigma": 0.8,
                           "min": 4, "max": 120},
                   output={"dist": "lognormal", "mean": 40, "sigma": 0.5,
                           "min": 8, "max": 96})
    return traffic


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_expert_control_is_not_correct(seed):
    """Eight layers, a 32,000-token vocabulary and ShareGPT-like lengths:
    under the configuration's own check (``near_tie`` and the
    ``mean_logit_gap`` limit) a sound run is correct and the float8
    control, every routed and shared expert weight in e4m3, is not."""
    cfg = moonlight_config(hidden_size=256, num_hidden_layers=8,
                           vocab_size=32000, sample_tokens=300, max_len=224)
    ctx = lambda: tiny.context(cfg, _long_traffic(),  # noqa: E731
                               seed=seed, seconds=5.0)
    sound = serving_moe.run(ctx())
    control = serving_moe.run(ctx(), patch=_fp8_experts)
    assert control.extra["checks"]["tokens_compared"] >= 60
    assert _correct(cfg, sound)
    assert not _correct(cfg, control)


def test_near_ties_are_the_held_experts_at_the_top_k_boundary():
    """Six of eight scores chosen, two experts held: a held expert's
    margin is its distance from the other side of the boundary, and the
    least one decides."""
    ref = spec.reference(CONFIG)
    cfg = {"num_experts_per_tok": 6, "n_routed_experts": 2,
           "expert_rank": 0}
    sel = jnp.asarray([[0.90, 0.50, 0.80, 0.70, 0.60, 0.85, 0.95, 0.40],
                       [0.40, 0.59, 0.80, 0.70, 0.60, 0.85, 0.95, 0.90]])
    # row 0: expert 0 chosen (0.90 - 0.50 beyond the 7th), expert 1 out
    # (the 6th, 0.60, is 0.10 above it); row 1: both out, 0.59 is 0.01
    # under the 6th
    np.testing.assert_allclose(np.asarray(ref._held_margin(cfg, sel)),
                               [0.10, 0.01], atol=1e-6)
    # experts 6 and 7: row 0, 7 is out 0.20 under the 6th; row 1, both
    # chosen, 7 is 0.31 above the 7th
    cfg["expert_rank"] = 3
    np.testing.assert_allclose(np.asarray(ref._held_margin(cfg, sel)),
                               [0.20, 0.31], atol=1e-6)


def _reader(name):
    return spec.metric_reader(name).read


def _run(counters=None, summary=None, requests=()):
    run = Run(workload="tiny", config=moonlight_config(), traffic={},
              device_kind="TPU v5 lite", setup_s=1.0, window=(0.0, 10.0),
              spans=Spans(), counters=dict(counters or {}),
              requests=list(requests))
    run.trace = summary
    return run


NEW = ("moe_gemm_roofline.moonlight", "moe_row_util.moonlight",
       "moe_share.moonlight", "mfu.moonlight")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_need_their_counters_and_trace(name):
    """A program without the MoE counters, and a run without a trace,
    give nothing, never 0."""
    from harness.record import RequestRecord
    req = RequestRecord(prompt_len=8, max_new=4, arrival=0.0,
                        token_times=[1.0, 2.0])
    assert _reader(name)(_run(requests=[req])) is None
    summary = Summary(window_s=10.0, busy_s=9.0, mosaic_s={}, top_ops=[],
                      idle_by_span=[])
    assert _reader(name)(_run(summary=summary)) is None


def test_new_readers_on_known_counts():
    cfg = moonlight_config()
    counters = {"moe_rows_routed": 600, "moe_rows_computed": 1200,
                "moe_expert_loads": 40}
    summary = Summary(window_s=10.0, busy_s=9.0,
                      mosaic_s={"moe_gemm_bfloat16": 2.0,
                                "segment_spmm_pipeline": 1.0},
                      top_ops=[], idle_by_span=[])
    run = _run(counters, summary)
    assert _reader("moe_row_util.moonlight")(run) == pytest.approx(50.0)
    assert _reader("moe_share.moonlight")(run) == pytest.approx(20.0)
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = max((40 * 3 * d * ff + 600 * 3 * (d + ff)) * 2 / 819e9,
                600 * 6 * d * ff / 197e12)
    assert _reader("moe_gemm_roofline.moonlight")(run) == pytest.approx(
        100 * least / 2.0)
