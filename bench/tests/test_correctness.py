"""The comparison that decides ``correct``, driven through the whole run on
the CPU at small widths (the chip check skipped), with the timed path
broken underneath: each fault has to come out not correct, and a sound run
correct.  The limits are the configuration files' own."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import spec

run_py = spec.load_module(tiny.BENCH / "run.py")
serving = spec.driver("serving")
spgemm = spec.driver("spgemm")


def _correct(cfg, run):
    ok, _ = run_py.judge(cfg, run.extra["checks"])
    return ok


def _serve(seed=5, **kw):
    cfg = tiny.phi3_config()
    run = serving.run(tiny.context(cfg, tiny.chat_traffic(),
                                   seed=seed, seconds=1.5), **kw)
    return cfg, run


def _keep_cache(engine):
    """Fault: the decode step returns the KV cache it was given."""
    decode = engine._decode

    def stale(params, cache, tok, pos):
        nxt, _ = decode(params, jax.tree.map(jnp.copy, cache), tok, pos)
        return nxt, cache
    engine._decode = stale


def _alter_token(engine):
    """Fault: every decoded token is replaced by its neighbour id."""
    decode = engine._decode

    def off_by_one(params, cache, tok, pos):
        nxt, cache = decode(params, cache, tok, pos)
        return (nxt + 1) % engine.model.cfg.vocab, cache
    engine._decode = off_by_one


def _half_ffn(engine):
    """Fault: every other stored block of each FFN projection is zero, as
    if the Segment SpMM left half of its work out."""
    mlp = engine.params["layers"]["mlp"]
    for name in ("up", "gate", "down"):
        mlp[name]["blocks"] = mlp[name]["blocks"].at[:, ::2].set(0)


def test_sound_serving_run_is_correct():
    cfg, run = _serve()
    assert run.extra["checks"]["tokens_compared"] >= cfg["sample_tokens"] // 2
    assert _correct(cfg, run)
    assert run.counters["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [_keep_cache, _alter_token, _half_ffn],
                         ids=["state-unchanged", "token-altered",
                              "half-ffn-left-out"])
def test_serving_fault_is_not_correct(fault):
    cfg, run = _serve(patch=fault)
    assert run.extra["checks"], "the run finished requests to compare"
    assert not _correct(cfg, run)


@pytest.mark.parametrize("seed", [1, 2])
def test_serving_control_is_not_correct(seed):
    """The control: the program's own float8 path (``Engine(quantize=
    "fp8")``: the FFN blocks in e4m3 with per-block scales)."""
    cfg = tiny.phi3_mid_config()
    sound = serving.run(tiny.context(cfg, tiny.chat_traffic(),
                                     seed=seed, seconds=5.0))
    control = serving.run(tiny.context(cfg, tiny.chat_traffic(),
                                       seed=seed, seconds=5.0),
                          engine_kw={"quantize": "fp8"})
    assert control.extra["checks"]["tokens_compared"] >= 100
    assert _correct(cfg, sound)
    assert not _correct(cfg, control)


def _spgemm(seed=3, **kw):
    cfg = tiny.table3_config()
    run = spgemm.run(tiny.context(cfg, tiny.table3_traffic(),
                                  seed=seed, seconds=0.3), **kw)
    return cfg, run


def test_sound_spgemm_run_is_correct():
    cfg, run = _spgemm()
    assert _correct(cfg, run)
    assert run.counters["passes"] >= 1


def _alter_block(outs):
    """Fault: one output block of the first matrix comes back altered."""
    first = outs[0].at[0].add(1.0)
    return [first] + list(outs[1:])


def _drop_half(outs):
    """Fault: half of each matrix's output blocks are left out (zero)."""
    return [o.at[::2].set(0.0) for o in outs]


def _misplace(outs):
    """Fault: the first matrix's blocks come back in reversed order."""
    return [outs[0][::-1]] + list(outs[1:])


@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("quantize", ["int8", "fp8"])
def test_spgemm_control_is_not_correct(quantize, seed):
    """The controls: the program's own int8 and float8 paths
    (``plan_matmul(..., quantize=...)``, per-block scales)."""
    cfg, run = _spgemm(seed=seed, plan_kw={"quantize": quantize})
    assert not _correct(cfg, run)


@pytest.mark.parametrize("fault", [_alter_block, _drop_half, _misplace],
                         ids=["answer-altered", "half-left-out",
                              "blocks-misplaced"])
def test_spgemm_fault_is_not_correct(fault):
    cfg, run = _spgemm(corrupt=fault)
    assert not _correct(cfg, run)


def test_judge_refuses_missing_and_nonfinite_numbers():
    cfg = {"checks": {"x": 1.0}}
    assert run_py.judge(cfg, {"x": 0.5})[0]
    assert not run_py.judge(cfg, {"x": 1.5})[0]
    assert not run_py.judge(cfg, {})[0]
    assert not run_py.judge(cfg, {"x": float("nan")})[0]
    assert run_py.judge(cfg, {"x": float("inf")})[1]["x"]["value"] is None


def test_served_positions_and_gaps():
    ref = spec.reference(tiny.phi3_config()["reference"])
    prompt, out = np.array([5, 6, 7]), np.array([8, 9])
    seq, pos = ref.served_positions(prompt, out)
    assert seq.tolist() == [5, 6, 7, 8] and pos.tolist() == [2, 3]
    logits = np.zeros((4, 10))
    logits[2, 8], logits[3, 1] = 3.0, 2.0
    g = ref.logit_gaps(logits, pos, out)
    assert g[0] == 0.0
    assert g[1] == pytest.approx(2.0 / logits[3].std())
    assert ref.logit_gaps(logits, pos, np.array([8, 12]))[1] == np.inf
