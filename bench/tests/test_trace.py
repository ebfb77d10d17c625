"""The trace reduction, on a small trace recorded on a v5e chip
(``data/probe.xplane.pb``, see ``data/record_probe.py``): three rounds of a
Segment SpGEMM call, a dense bf16 matmul and a 10 ms host sleep."""
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import trace

PROBE = str(Path(__file__).parent / "data" / "probe.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(PROBE)


def _brute_busy(ops, lo, hi, step=250.0):
    """Covered time by sampling the window every ``step`` ns."""
    t = np.arange(lo, hi, step) + step / 2
    hit = np.zeros(t.size, bool)
    for o in ops:
        hit |= (t >= o.start) & (t < o.end)
    return hit.sum() * step


def test_reads_device_ops_and_host_spans(tr):
    names = {o.name for o in tr.ops}
    assert "segment_spgemm" in names
    assert [s.name for s in tr.spans].count("bench.pass") == 3
    assert sum(o.mosaic for o in tr.ops) == 3       # one kernel call a pass
    assert tr.n_devices == 1


def test_clock_offset_orders_device_work_inside_its_host_span(tr):
    # a chip's clock runs about 1.6-1.9 ms behind the host's here; once
    # shifted, each kernel runs inside the host span that launched it
    assert 1.0e6 < tr.offset_ns < 2.5e6
    passes = [s for s in tr.spans if s.name == "bench.pass"]
    kernels = [o for o in tr.ops if o.mosaic]
    for s, k in zip(passes, kernels):
        assert s.start <= k.start and k.end <= s.end


def test_busy_union_matches_brute_force(tr):
    lo = min(s.start for s in tr.spans)
    hi = max(s.end for s in tr.spans)
    s = trace.summarize(tr, lo, hi)
    want = _brute_busy(tr.ops, lo, hi) * 1e-9
    assert s.busy_s == pytest.approx(want, rel=0.02)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)


def test_mosaic_time_is_the_kernels_durations(tr):
    lo, hi = tr.ops[0].start - 1, tr.ops[-1].end + 1
    s = trace.summarize(tr, lo, hi)
    want = sum(o.end - o.start for o in tr.ops if o.mosaic) * 1e-9
    assert s.mosaic_s == {"segment_spgemm": pytest.approx(want)}
    assert trace.kernel_time(s, lambda k: k.startswith("segment_")) \
        == pytest.approx(want)


def test_idle_time_is_attributed_to_the_innermost_host_span(tr):
    lo = min(s.start for s in tr.spans)
    hi = max(s.end for s in tr.spans)
    s = trace.summarize(tr, lo, hi)
    idle = dict(s.idle_by_span)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-9)
    # the three 10 ms sleeps hold most of the idle time
    assert idle["bench.sleep"] > 0.029
    assert max(idle, key=idle.get) == "bench.sleep"


def test_self_time_removes_nested_ops():
    ops = [trace.Op("while", 0, 100, False, 0),
           trace.Op("fusion", 10, 30, False, 0),
           trace.Op("segment_spmm", 40, 90, True, 0),
           trace.Op("copy", 95, 120, False, 0)]
    assert trace.self_times(ops) == [30, 20, 50, 25]
    s = trace.summarize(trace.Trace(ops, [], 1, 0.0), 0, 200)
    assert s.busy_s == pytest.approx(120e-9)
    assert dict(s.top_ops)["while"] == pytest.approx(30e-9)
    assert dict(s.idle_by_span) == {"host:none": pytest.approx(80e-9)}


def test_short_names():
    assert trace.short_name("%segment_spgemm.1 = f32[79,128,128] "
                            "custom-call(...)") == "segment_spgemm"
    assert trace.short_name("%copy-start = (bf16[1024]) copy-start(x)") \
        == "copy-start"
    assert trace.short_name("%fusion.12.3 = f32[] fusion()") == "fusion"
    assert trace.short_name("%copy.76.remat = f32[] copy(x)") == "copy.remat"
