"""Trace one benchmark cell with the program's own spans and counters.

    python3 bench/trace_cell.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does, and reads besides what
that run leaves out:

- the program's ``segfold.*`` host spans and its compiled programs' runs
  (``harness/program.py``): device idle time split by the innermost program
  span, and each program's device time and run count;
- ``Engine.counters()`` at the window's start and end, where the cell
  serves through an ``Engine``: the window's change goes into
  ``run.counters``;
- the metrics that read them (:data:`PROGRAM_METRICS`, files under
  ``bench/metrics/``), beside the cell's end-to-end and per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``metrics``, ``counters``, ``breakdown`` (``bench/run.py``'s) and
``program`` (``idle_by_program_span``; ``modules``: program, device
seconds, runs).
"""
import inspect
import json
import shutil
import sys

import run as bench
from harness import device, program, spec, trace

#: metrics read from the program's spans and counters
PROGRAM_METRICS = ("decode_step_ms.offline", "host_gap_share.offline",
                   "ffn_col_util.offline", "segment_spmm_roofline.offline",
                   "dispatch_gap_share.spgemm")


class ProgramTracer(bench.Tracer):
    """The window's trace, with the program's spans and runs, and the
    counters of the engine handed to :meth:`attach` at its two edges."""

    def __init__(self):
        super().__init__()
        self.engine = None
        self.counts = []

    def attach(self, engine):
        self.engine = engine

    def _snapshot(self):
        if self.engine is not None and hasattr(self.engine, "counters"):
            self.counts.append(self.engine.counters())

    def start(self):
        self._snapshot()
        super().start()

    def stop(self):
        super().stop()
        self._snapshot()
        self.engine = None      # the run frees the engine after the window

    def counter_change(self):
        if len(self.counts) != 2:
            return {}
        a, b = self.counts
        return {k: b[k] - a[k] for k in b}

    def program_summary(self):
        try:
            tr, spans, runs = program.load(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        window = trace.window_of(tr, "bench.window")
        if window is None:
            raise RuntimeError("the trace holds no bench.window span")
        return (trace.summarize(tr, *window),
                program.summarize(tr, spans, runs, *window))


def main(argv=None) -> int:
    args = bench.parse(argv)
    try:
        marks = spec.Benchmark()
        cell = marks.workload(args.workload)
        cfg = spec.config_file(marks.config(cell["config"]))
        traffic = spec.traffic_file(cell["traffic"])
        drv = spec.driver(cfg["driver"])
        names = [m["name"] for traced in (False, True)
                 for m in marks.metrics_for(args.workload, traced)]
        readers = {n: spec.metric_reader(n)
                   for n in names + list(PROGRAM_METRICS)}
    except spec.SpecError as e:
        print(f"trace_cell: {e}", file=sys.stderr)
        return bench.EXIT_SPEC
    device.use_compile_cache()
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"trace_cell: {e}", file=sys.stderr)
        return bench.EXIT_NO_CHIP
    from harness.context import Context
    from harness.record import Spans
    tracer = ProgramTracer()
    ctx = Context(workload=args.workload, config=cfg, traffic=traffic,
                  seed=args.seed, seconds=args.seconds, spans=Spans(),
                  counter=device.CompileCounter(), t_start=bench.T_START,
                  device_kind=devices[0].device_kind,
                  describe=lambda: device.describe(devices), tracer=tracer)
    kw = ({"patch": tracer.attach}
          if "patch" in inspect.signature(drv.run).parameters else {})
    run = drv.run(ctx, **kw)
    run.trace, prog = tracer.program_summary()
    run.extra["program"] = prog
    run.counters.update(tracer.counter_change())
    values = {}
    for name, reader in readers.items():
        v = reader.read(run)
        if v is not None:
            values[name] = float(v)
    correct, _ = bench.judge(cfg, run.extra["checks"])
    split = {k: round(v, 4) for k, v in prog.idle_by_program_span}
    print(f"trace_cell: {args.workload} seed {args.seed}: idle by program "
          f"span {split}", file=sys.stderr, flush=True)
    print(json.dumps({
        "correct": correct, "metrics": values, "counters": run.counters,
        "breakdown": {"device_ops": run.trace.top_ops,
                      "idle_gaps": run.trace.idle_by_span},
        "program": {"idle_by_program_span": prog.idle_by_program_span,
                    "modules": prog.modules()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
