"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names (see ``bench/harness/spec.py``).  Set-up is timed
from process start to the window's start; the window runs ``--seconds``;
then the outputs of the timed path are compared with the configuration's
plain reference.  With ``--trace 0`` the result line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, then ``compiles_in_window`` (expected 0) and, last,
``checks``: each number compared with its limit, also printed as the last
lines of standard error.  Without the chips the cell asks for, or outside
a checkout that holds the program, the run exits non-zero and prints no
result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import device, spec  # noqa: E402

EXIT_SPEC, EXIT_NO_CHIP = 2, 3


class Tracer:
    """A profiler trace of the window, written under ``TMPDIR``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def summary(self, top: int = 10):
        from harness import trace
        try:
            tr = trace.load(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        window = trace.window_of(tr, "bench.window")
        if window is None:
            raise RuntimeError("the trace holds no bench.window span")
        return trace.summarize(tr, *window, top=top)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def judge(config, checks):
    """``(correct, lines)``: every number the configuration holds a limit
    for has to be present and at or under it."""
    table, ok = {}, True
    for name, limit in config["checks"].items():
        value = checks.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        table[name] = {"value": finite(value), "limit": limit}
    return ok, table


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = spec.Benchmark()
        cell = bench.workload(args.workload)
        cfg_entry = bench.config(cell["config"])
        cfg = spec.config_file(cfg_entry)
        traffic = spec.traffic_file(cell["traffic"])
        drv = spec.driver(cfg["driver"])
        metrics = bench.metrics_for(args.workload, traced=bool(args.trace))
        readers = {m["name"]: spec.metric_reader(m["name"]) for m in metrics}
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_SPEC
    if importlib.util.find_spec("repro") is None:
        print("bench: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return EXIT_SPEC

    device.use_compile_cache()
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    from harness.context import Context
    from harness.record import Spans
    tracer = Tracer() if args.trace else None
    ctx = Context(workload=args.workload, config=cfg, traffic=traffic,
                  seed=args.seed, seconds=args.seconds, spans=Spans(),
                  counter=device.CompileCounter(), t_start=T_START,
                  device_kind=devices[0].device_kind,
                  describe=lambda: device.describe(devices), tracer=tracer)
    run = drv.run(ctx)
    dev = run.extra["device"]
    if tracer is not None:
        run.trace = tracer.summary()
        dev.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct, table = judge(cfg, run.extra["checks"])
    line = {"correct": correct, "attempted": run.extra["attempted"],
            "failed": run.extra["failed"], "metrics": values, "device": dev}
    if tracer is not None:
        line["breakdown"] = {"device_ops": run.trace.top_ops,
                             "idle_gaps": run.trace.idle_by_span}
    line["compiles_in_window"] = run.counters["compiles_in_window"]
    line["checks"] = table
    info = {k: v for k, v in run.extra["checks"].items() if k not in table}
    phases = {}
    for name, s, e in run.spans.items:
        if e <= run.window[0]:
            phases[name] = round(phases.get(name, 0.0) + e - s, 3)
    print(f"bench: {args.workload} seed {args.seed}: setup {run.setup_s:.3f}s"
          f" {phases}, window {run.window_s:.3f}s, compiles in window "
          f"{run.counters['compiles_in_window']}, {info}", file=sys.stderr)
    for name, c in table.items():
        verdict = ("ok" if c["value"] is not None and c["value"] <= c["limit"]
                   else "FAIL")
        print(f"check {name} = {c['value']} (limit {c['limit']}) {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
