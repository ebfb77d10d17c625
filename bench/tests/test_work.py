"""Work counts and the peak table against brute force and the program's
own parameter arithmetic."""
import dataclasses

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import peaks, spec
from harness.work import decoder_flops_per_token, spgemm_work


def _pattern(rng, gm, gk, p):
    mask = rng.random((gm, gk)) < p
    return np.nonzero(mask)


@pytest.mark.parametrize("seed", range(5))
def test_spgemm_counts_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    gm, gk, gn = rng.integers(2, 12, size=3)
    ab, bb = _pattern(rng, gm, gk, 0.3), _pattern(rng, gk, gn, 0.3)
    w = spgemm_work(*ab, *bb, (128, 64, 32), 4)
    a = np.zeros((gm, gk), int)
    a[ab] = 1
    b = np.zeros((gk, gn), int)
    b[bb] = 1
    c = a @ b
    assert w.products == c.sum()
    assert w.c_blocks == (c > 0).sum()
    assert w.flops == 2 * 128 * 64 * 32 * c.sum()
    assert w.bytes == 4 * (a.sum() * 128 * 64 + b.sum() * 64 * 32
                           + (c > 0).sum() * 128 * 32)
    assert w.least_seconds(1e12, 1e9) == max(w.flops / 1e12, w.bytes / 1e9)


def test_a_times_a_transpose_of_a_table3_matrix():
    gen = spec.generator("table3")
    cfg = tiny.table3_config()
    a = gen.block_sparse("tiny-hub", cfg["tiny-hub"], 32, seed=3)
    at = a.transpose()
    w = spgemm_work(a.brow, a.bcol, at.brow, at.bcol, (32, 32, 32), 4)
    mask = np.zeros((a.brow.max() + 1, a.bcol.max() + 1), int)
    mask[a.brow, a.bcol] = 1
    assert w.products == (mask @ mask.T).sum()


def test_decoder_flops_match_the_model_config_parameter_count():
    """With every FFN block kept and no context, a token costs two FLOPs
    per weight the program's ``ModelConfig.param_count`` counts, less the
    input embedding (a lookup)."""
    from repro.configs import get_config
    cfg = tiny.phi3_config()
    mc = dataclasses.replace(get_config(cfg["registry"]),
                             d_model=cfg["hidden_size"],
                             d_ff=cfg["intermediate_size"],
                             n_heads=cfg["num_attention_heads"],
                             n_kv=cfg["num_key_value_heads"],
                             n_layers=cfg["num_hidden_layers"],
                             vocab=cfg["vocab_size"], tie_embeddings=False)
    full = (cfg["hidden_size"] * cfg["intermediate_size"]
            // cfg["ffn_block"] ** 2)
    got = decoder_flops_per_token(cfg, [full] * 3, np.array([-1]))[0]
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    assert got == 2 * (mc.param_count() - embed)


def test_decoder_flops_count_ffn_at_its_blocks_and_causal_context():
    cfg = tiny.phi3_config()
    base = decoder_flops_per_token(cfg, [10, 10, 10], np.array([0, 9]))
    fewer = decoder_flops_per_token(cfg, [5, 10, 10], np.array([0, 9]))
    b2 = cfg["ffn_block"] ** 2
    assert base[0] - fewer[0] == 2 * cfg["num_hidden_layers"] * 5 * b2
    # 9 more keys at each of H heads of hd for QK and PV, every layer
    assert base[1] - base[0] == 4 * cfg["num_hidden_layers"] \
        * cfg["hidden_size"] * 9


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
