"""Record ``probe.xplane.pb``, the small chip trace the trace-reduction tests
read.

    python3 bench/tests/data/record_probe.py     # on a TPU host, from the root

Three rounds of: one Segment SpGEMM call (a banded 2048x2048 pattern in
128x128 blocks), one jitted dense bf16 matmul, and a 10 ms host sleep,
each inside a ``bench.*`` host span.  The trace is written under
``chiprun_out/probe/trace``.
"""
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import execute_plan, plan_matmul  # noqa: E402
from repro.core.formats import BSR  # noqa: E402
from repro.sim import matrices  # noqa: E402


def main() -> None:
    rng = np.random.default_rng(0)
    csr = matrices.banded(rng, 2048, 2048, density=16 / 2048)
    a = BSR.from_dense(csr.to_dense(), (128, 128))
    plan = plan_matmul(a, a, backend="pallas")
    execute_plan(plan).block_until_ready()
    dense = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    dense(x).block_until_ready()
    out = ROOT / "chiprun_out" / "probe" / "trace"
    jax.profiler.start_trace(str(out))
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.pass", i=i):
            execute_plan(plan).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.dense"):
            dense(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    print(f"trace written under {os.path.relpath(out, ROOT)}")


if __name__ == "__main__":
    main()
