"""Record ``program_probe.xplane.pb``, the small chip trace the tests of the
program-span reduction read.

    python3 bench/tests/data/record_program_probe.py   # on a TPU host, root

Inside one ``bench.window`` span: a small block-sparse-FFN transformer (two
layers, d 256, 128x128 FFN blocks) serves three requests through ``Engine``
(two slots, so one waits for a free slot), then three eager Segment SpGEMM
calls (``execute_plan``) run, then the host sleeps 10 ms in ``bench.sleep``,
outside every program span.  The program writes its ``segfold.*`` spans
itself.  The trace is written under ``chiprun_out/program_probe``.
"""
import dataclasses
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import execute_plan, plan_matmul  # noqa: E402
from repro.core.formats import BSR  # noqa: E402
from repro.launch.serve import serving_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import Engine, Request  # noqa: E402
from repro.sim import matrices  # noqa: E402

PROMPTS = ((40, 6), (9, 4), (21, 3))    # (prompt tokens, new tokens)


def main() -> None:
    cfg = dataclasses.replace(
        serving_config("phi3-mini-3.8b", reduced=True, sparse_ffn=True,
                       ffn_block=128),
        n_layers=2, d_model=256, n_heads=4, n_kv=4, d_ff=512, vocab=1024)
    model = build_model(cfg)
    engine = Engine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                    max_len=128, backend="pallas")
    rng = np.random.default_rng(0)

    def requests():
        return [Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                        max_new_tokens=m) for n, m in PROMPTS]

    engine.generate(requests())                      # compiles every shape
    csr = matrices.banded(rng, 1024, 1024, density=16 / 1024)
    a = BSR.from_dense(csr.to_dense(), (128, 128))
    plan = plan_matmul(a, a, backend="pallas")
    execute_plan(plan).block_until_ready()

    out = ROOT / "chiprun_out" / "program_probe"
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        engine.generate(requests())
        for _ in range(3):
            execute_plan(plan).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    print(f"trace written under {os.path.relpath(out, ROOT)}")


if __name__ == "__main__":
    main()
