"""Read the numbers that decide ``correct`` over many seeds in one process:
the program's own, and its control's.  The limits in the configuration
files are set from these readings (``PERF.md`` gives them).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--control program-fp8|program-int8]

The controls run the cell with the program's own low-precision path
switched on (``Engine(quantize=...)`` for serving, ``plan_matmul(
quantize=...)`` for SpGEMM).  One JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import device, spec  # noqa: E402

CONTROLS = {
    None: {},
    "program-int8": {"serving": {"engine_kw": {"quantize": "int8"}},
                     "spgemm": {"plan_kw": {"quantize": "int8"}}},
    "program-fp8": {"serving": {"engine_kw": {"quantize": "fp8"}},
                    "spgemm": {"plan_kw": {"quantize": "fp8"}}},
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", choices=[c for c in CONTROLS if c])
    args = ap.parse_args()
    bench = spec.Benchmark()
    cell = bench.workload(args.workload)
    cfg = spec.config_file(bench.config(cell["config"]))
    kw = CONTROLS[args.control].get(cfg["driver"]) if args.control else {}
    if kw is None:
        print(f"calibrate: no {args.control} control for {cfg['driver']}",
              file=sys.stderr)
        return 2
    device.use_compile_cache()
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    from harness.context import Context
    from harness.record import Spans
    drv = spec.driver(cfg["driver"])
    traffic = spec.traffic_file(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        ctx = Context(workload=args.workload, config=cfg,
                      traffic=traffic, seed=seed,
                      seconds=args.seconds, spans=Spans(),
                      counter=device.CompileCounter(), t_start=t,
                      device_kind=devices[0].device_kind,
                      describe=lambda: device.describe(devices))
        run = drv.run(ctx, **kw)
        print(json.dumps({"seed": seed, "control": args.control,
                          "checks": run.extra["checks"],
                          "seconds": time.time() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
