"""The traffic generators: seeded, inside their clips, at their rate, and
independent of ``PYTHONHASHSEED``."""
import hashlib
import itertools
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import tiny
from harness import spec

requests = spec.generator("requests")
table3 = spec.generator("table3")
#: the offline cell's file, and an open-loop Poisson mix at CPU sizes
TRAFFIC = {"phi3-bsffn.offline": lambda: spec.traffic_file("phi3-bsffn.offline"),
           "poisson": tiny.chat_traffic}


def _take(traffic, seed, n, vocab=32064):
    return list(itertools.islice(requests.stream(traffic, seed, vocab), n))


@pytest.mark.parametrize("mix", TRAFFIC)
def test_same_seed_same_requests(mix):
    t = TRAFFIC[mix]()
    a, b = _take(t, 2**31 + 12345, 80), _take(t, 2**31 + 12345, 80)
    assert [(x.offset, x.max_new) for x in a] == \
        [(x.offset, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _take(t, 7, 80)
    assert [x.max_new for x in a] != [x.max_new for x in c]


@pytest.mark.parametrize("mix", TRAFFIC)
def test_every_seed_serves_the_same_lengths_in_another_order(mix):
    t = TRAFFIC[mix]()
    pool = t["pool"]
    a, b = _take(t, 1, pool), _take(t, 2**33 + 5, pool)
    assert sorted(x.prompt.size for x in a) == sorted(x.prompt.size for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]


@pytest.mark.parametrize("mix", TRAFFIC)
def test_lengths_inside_their_clips(mix):
    t = TRAFFIC[mix]()
    for x in _take(t, 3, 3 * t["pool"], vocab=100):
        assert t["prompt"]["min"] <= x.prompt.size <= t["prompt"]["max"]
        assert t["output"]["min"] <= x.max_new <= t["output"]["max"]
        assert x.prompt.dtype == np.int32
        assert 0 <= x.prompt.min() and x.prompt.max() < 100


@pytest.mark.parametrize("mix", TRAFFIC)
def test_lengths_follow_their_mean(mix):
    """The lognormal's median is ``mean * exp(-sigma^2/2)``; before its
    clips the pool's mean is the one the traffic file gives."""
    t = TRAFFIC[mix]()
    for part in ("prompt", "output"):
        d = dict(t[part], min=1, max=10**6)
        q = requests.quantiles(d, 4096)
        assert q.mean() == pytest.approx(d["mean"], rel=0.02)
        assert np.median(q) == pytest.approx(
            d["mean"] * np.exp(-d["sigma"] ** 2 / 2), abs=1)


def test_poisson_mean_rate():
    t = tiny.chat_traffic(rate=0.6)
    rate = t["arrivals"]["rate"]
    xs = _take(t, 11, 10 * t["pool"], vocab=10)
    gaps = np.diff([0.0] + [x.offset for x in xs])
    assert len(xs) / xs[-1].offset == pytest.approx(rate, rel=0.03)
    assert requests.mean_rate(t) == pytest.approx(rate, rel=0.03)
    # exponential: the spread of the gaps equals their mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.15)


def test_closed_traffic_has_no_gaps():
    t = spec.traffic_file("phi3-bsffn.offline")
    assert all(x.offset == 0 for x in _take(t, 5, 20))


_PRINT_DIGEST = """
import hashlib, sys
sys.path[:0] = [{bench!r}]
from harness import spec
gen = spec.generator("table3")
cfg = spec.load_json(spec.BENCH / "configs" / "table3-spgemm.json")
h = hashlib.sha256()
for name in ("tols4000", "ca-GrQc", "lp_woodw"):
    r, c = gen.pattern(name, cfg[name])
    h.update(r.tobytes()); h.update(c.tobytes())
print(h.hexdigest())
"""


def test_table3_patterns_do_not_depend_on_the_hash_seed():
    code = _PRINT_DIGEST.format(bench=str(tiny.BENCH))
    digests = set()
    for hash_seed in ("0", "1", "12345"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True)
        digests.add(out.stdout.strip())
    assert len(digests) == 1


def test_table3_pattern_is_the_matrix_values_are_the_seed():
    cfg = tiny.table3_config()
    a = table3.block_sparse("tiny-band", cfg["tiny-band"], 32, seed=1)
    b = table3.block_sparse("tiny-band", cfg["tiny-band"], 32, seed=2**32 + 1)
    assert np.array_equal(a.brow, b.brow) and np.array_equal(a.bcol, b.bcol)
    assert not np.array_equal(a.blocks, b.blocks)


@pytest.mark.parametrize("name", ["tiny-band", "tiny-hub"])
def test_table3_blocks_hold_the_coordinates(name):
    """The BSR built straight from the coordinates equals the dense
    matrix they describe, and its transpose is the transpose."""
    cfg = tiny.table3_config()
    spec_ = cfg[name]
    rows, cols = table3.pattern(name, spec_)
    a = table3.block_sparse(name, spec_, 32, seed=4)
    dense = np.zeros((-(-spec_["m"] // 32) * 32, -(-spec_["n"] // 32) * 32),
                     np.float32)
    for i, (r, c) in enumerate(zip(a.brow, a.bcol)):
        dense[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32] = a.blocks[i]
    nz = np.zeros_like(dense, bool)
    nz[rows, cols] = True
    assert np.array_equal(dense != 0, nz)
    assert np.all(np.diff(a.brow.astype(np.int64) * 10**6 + a.bcol) > 0)
    t = a.transpose()
    dt = np.zeros(dense.T.shape, np.float32)
    for i, (r, c) in enumerate(zip(t.brow, t.bcol)):
        dt[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32] = t.blocks[i]
    assert np.array_equal(dt, dense.T)


@pytest.mark.parametrize("name,m,n,density", [
    ("fv1", 9604, 9604, 9.24e-4), ("pcb3000", 3960, 7732, 1.88e-3)])
def test_table3_matrices_keep_their_original_size(name, m, n, density):
    cfg = spec.load_json(tiny.BENCH / "configs" / "table3-spgemm.json")
    assert (cfg[name]["m"], cfg[name]["n"]) == (m, n)
    rows, cols = table3.pattern(name, cfg[name])
    assert rows.max() < m and cols.max() < n
    assert rows.size == pytest.approx(density * m * n, rel=0.35)


def test_pattern_digest_is_stable():
    """A pinned digest: a change to a generator changes the benchmark's
    work and shows here."""
    cfg = spec.load_json(tiny.BENCH / "configs" / "table3-spgemm.json")
    h = hashlib.sha256()
    for name in ("tols4000", "ca-GrQc", "lp_woodw"):
        r, c = table3.pattern(name, cfg[name])
        h.update(r.tobytes())
        h.update(c.tobytes())
    assert h.hexdigest() == Path(__file__).with_name(
        "data").joinpath("table3_digest.txt").read_text().strip()
