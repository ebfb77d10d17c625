"""``bench/run.py`` refuses to run without its chips or without the
program, and every name in ``BENCHMARK.json`` finds its files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny
from harness import spec

ROOT = tiny.BENCH.parent
BENCHMARK = spec.load_json(ROOT / "BENCHMARK.json")


def _run(cwd, workload="table3.banded"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**31 + 99), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            return not isinstance(json.loads(line), dict)
        except ValueError:
            return True
    return True


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      BENCHMARK["workloads"]])
def test_no_accelerator_exits_nonzero_without_a_result(workload):
    p = _run(ROOT, workload)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs 1 tpu chip" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCHMARK["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not in this checkout" in p.stderr


def test_unknown_workload_is_refused():
    p = _run(ROOT, "no-such-cell")
    assert p.returncode != 0 and "no workload" in p.stderr


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    bench = spec.Benchmark()
    cfg = spec.config_file(bench.config(cell["config"]))
    spec.driver(cfg["driver"])
    spec.reference(cfg["reference"])
    traffic = spec.traffic_file(cell["traffic"])
    spec.generator(traffic["generator"])
    for traced in (False, True):
        metrics = bench.metrics_for(cell["name"], traced)
        assert metrics, (cell["name"], traced)
        for m in metrics:
            assert callable(spec.metric_reader(m["name"]).read)
    names = {m["name"] for m in bench.metrics_for(cell["name"], False)}
    assert "setup_s" in names and len(names) >= 2
    assert set(cfg["checks"]), "every configuration holds a limit"


def test_every_metric_has_a_reader_and_a_known_moves():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
