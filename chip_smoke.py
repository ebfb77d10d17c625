"""Smoke run of the repo's main path on TPU chips.

    python chip_smoke.py             # one chip: Segment kernels + serving
    python chip_smoke.py --chips 4   # four chips: the sharded Trainer only

One chip, in one process:

1. ``kernels`` — ``plan_matmul`` + ``execute_plan`` on ``backend="pallas"``
   for every case in :data:`KERNEL_CASES`: the phi3-mini FFN projections
   (8192x3072 up, 3072x8192 down, 128x128 blocks, density 0.25) at N = 8
   and N = 512 with bf16 and fp32 right-hand sides, int8 per-block and
   rowwise scales, one 2-lane unroll-2 cross-pass-prefetch plan, and
   SpGEMM on a seeded 4096x4096 banded pattern (fp32 and int8).  Each is
   compared with a float64 NumPy product of the same operands.
2. ``serving`` — phi3-mini-3.8b at its published widths, all 32 layers,
   block-sparse FFN (128 blocks, density 0.25), built as
   ``python -m repro.launch.serve`` builds it, serves 8 mixed-length
   requests through ``Engine``; then prefill + two decode steps of one
   prompt are compared on logits with the model's full forward pass in
   float32 on the reference backend.

``--chips 4`` runs only a 2x2 (data, model) sharded ``Trainer`` (phi3-mini
widths, 2 layers, dense FFN) for 3 steps and compares its losses with the
same steps on one device.

Every phase prints one line per check.  The last line of stdout is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core.formats import BSR, dequantize_blocks, quantize_blocks  # noqa: E402
from repro.sim import matrices  # noqa: E402

BLOCK = 128
UP = (8192, 3072)      # phi3-mini FFN weights as (d_out, d_in)
DOWN = (3072, 8192)
SPGEMM_DIM = 4096

# Relative error bounds for the kernel cases, max|y - ref| / max|ref|, with
# the reference in float64 of the very operands the kernel reads
# (bf16-rounded rhs, dequantized int8 blocks).  The compiled kernels
# accumulate in fp32, but Mosaic runs their fp32 x fp32 dot (precision
# unset) as one bf16 MXU pass, rounding each operand to 8 mantissa bits
# (u = 2**-9): 1.8e-3 measured on a v5e.  So:
# - EXACT_TOL where every operand is exact in bf16 (int8 payloads with the
#   per-block scale applied after the dot, bf16 or int8 rhs): only fp32
#   accumulation is left (~1e-6);
# - BF16_PASS_TOL otherwise: one bf16 pass gives ~2e-3, an fp8 pass
#   (u = 2**-4) would give ~3e-2.
EXACT_TOL = 1e-4
BF16_PASS_TOL = 1e-2

# Serving logits, max|dlogit| / max|ref|.  The served path computes in the
# configured bfloat16; the reference is the same weights' forward pass in
# float32.  bf16 activations (u = 2**-9) put them ~1e-2 apart after 32
# layers (measured on CPU at smaller widths: 1.2e-2 at d=256, 7.5e-3 at
# d=768; the fp32 served path matches the reference to 7e-7).  An fp8
# activation path (u = 2**-4) would land near 0.3.
LOGIT_TOL = 3e-2

# Sharded vs one-device training loss, absolute, on a loss near 10.  Same
# bf16 model and data, different reduction order and layout over 3 AdamW
# steps: 1.9e-4 on CPU virtual devices at smaller widths.  A wrong sharding
# rule or a dropped shard moves the loss by far more.
LOSS_TOL = 5e-3


def check(ok: bool, what) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One Segment kernel variant the smoke run executes (and
    ``tests/test_chip_compile.py`` compiles)."""
    name: str
    shape: tuple                    # (M, K) of the sparse left operand
    n: int = 0                      # dense rhs width; 0 = SpGEMM (A @ A)
    rhs_dtype: str = "float32"
    quantize: Optional[str] = None
    n_lanes: int = 1
    unroll: int = 1
    prefetch: Optional[str] = None


KERNEL_CASES = tuple(
    KernelCase(f"spmm {tag} N={n} {dt}", shape, n, dt)
    for tag, shape in (("up", UP), ("down", DOWN))
    for n in (8, 512) for dt in ("bfloat16", "float32")) + (
    KernelCase("spmm up N=8 int8", UP, 8, "bfloat16", quantize="int8"),
    KernelCase("spmm down N=512 int8", DOWN, 512, "bfloat16",
               quantize="int8"),
    KernelCase("spmm up N=512 int8.rowwise", UP, 512, "bfloat16",
               quantize="int8.rowwise"),
    KernelCase("spmm up N=1024 lanes=2 unroll=2 cross_pass", UP, 1024,
               "float32", n_lanes=2, unroll=2, prefetch="cross_pass"),
    KernelCase("spgemm 4096 fp32", (SPGEMM_DIM, SPGEMM_DIM)),
    KernelCase("spgemm 4096 int8", (SPGEMM_DIM, SPGEMM_DIM), quantize="int8"),
)


def kernel_operands(case: KernelCase, seed: int = 0):
    """Seeded ``(A, rhs)``: rhs is a float32 ``(K, N)`` array for SpMM, or
    ``A`` itself for SpGEMM (a banded stencil-like pattern from
    ``repro.sim.matrices``, ~16 nonzeros per row, tiled into 128 blocks)."""
    rng = np.random.default_rng(seed)
    if case.n:
        a = BSR.random(rng, case.shape, (BLOCK, BLOCK), 0.25)
        return a, rng.standard_normal((case.shape[1], case.n)).astype(
            np.float32)
    csr = matrices.banded(rng, *case.shape, density=16 / case.shape[1])
    a = BSR.from_dense(csr.to_dense(), (BLOCK, BLOCK))
    return a, a


def kernel_plan(case: KernelCase, a, rhs):
    from repro.api import plan_matmul
    return plan_matmul(a, rhs if not case.n else rhs.shape,
                       backend="pallas", quantize=case.quantize,
                       n_lanes=case.n_lanes, unroll=case.unroll,
                       prefetch=case.prefetch)


def _dense64(a: BSR, quantize: Optional[str]) -> np.ndarray:
    """float64 dense A holding exactly the values the kernel reads."""
    blocks = a.blocks if quantize is None else dequantize_blocks(
        quantize_blocks(a.blocks, quantize))
    return dataclasses.replace(a, blocks=blocks.astype(np.float64)).to_dense()


def _rel_err(got, want) -> float:
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def run_kernel_case(case: KernelCase) -> float:
    import jax.numpy as jnp
    from repro.api import execute_plan
    a, rhs = kernel_operands(case)
    plan = kernel_plan(case, a, rhs)
    check(plan.backend == "pallas", plan.backend)
    a64 = _dense64(a, case.quantize)
    if case.n:
        x = jnp.asarray(rhs, case.rhs_dtype)
        got = np.asarray(execute_plan(plan, x))
        want = a64 @ np.asarray(x.astype(jnp.float32), np.float64)
        return _rel_err(got, want)
    got = np.asarray(execute_plan(plan))
    c = a64 @ a64
    want = np.stack([c[r * BLOCK:(r + 1) * BLOCK, k * BLOCK:(k + 1) * BLOCK]
                     for r, k in zip(plan.c_brow, plan.c_bcol)])
    return _rel_err(got, want)


def kernel_tol(case: KernelCase) -> float:
    exact = case.quantize == "int8" and (case.rhs_dtype == "bfloat16"
                                         or not case.n)
    return EXACT_TOL if exact else BF16_PASS_TOL


def phase_kernels() -> None:
    failed = []
    for case in KERNEL_CASES:
        err, tol = run_kernel_case(case), kernel_tol(case)
        status = "ok" if err <= tol else "FAIL"
        print(f"[kernels] {case.name}: max|err|/max|ref| = {err:.3e} "
              f"(tol {tol:.0e}) {status}", flush=True)
        if err > tol:
            failed.append(case.name)
    check(not failed, failed)


def _cache_logits(model, params, seq, n_prompt: int, backend: str):
    """Prefill ``seq[:n_prompt]`` as one chunk into a fresh one-slot cache,
    then decode two tokens — the calls ``Engine`` makes — returning the
    logits at positions ``n_prompt - 1 .. n_prompt + 1``."""
    import jax
    import jax.numpy as jnp
    from repro.api import use_backend
    cache = model.init_cache(1, 2 * n_prompt)
    step = jax.jit(model.decode_step)
    out = []
    with use_backend(backend):
        for start, stop in ((0, n_prompt), (n_prompt, n_prompt + 1),
                            (n_prompt + 1, n_prompt + 2)):
            logits, cache = step(params, cache, seq[None, start:stop],
                                 jnp.int32(start))
            out.append(np.asarray(logits[0], np.float64))
    return np.stack(out)


def _reference_logits(cfg, params, seq, n_prompt: int):
    """The full forward pass of ``cfg`` in float32 on the reference backend
    (pure jnp, no kernels, no cache) at the highest matmul precision."""
    import jax
    from repro.api import use_backend
    from repro.models import build_model
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    with use_backend("reference"), jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.forward)(params, seq[None, :n_prompt + 2])
    return np.asarray(logits[0, n_prompt - 1:n_prompt + 2], np.float64)


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import serving_config
    from repro.models import build_model
    from repro.runtime import Engine, Request

    cfg = serving_config("phi3-mini-3.8b", sparse_ffn=True, ffn_block=BLOCK,
                         ffn_density=0.25)
    t0 = time.time()
    model = build_model(cfg)
    # the float32 weights the reference reads; the engine holds its own
    # compute-dtype copy
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, slots=4, max_len=1024, backend="pallas")
    check(engine.backend == "pallas", engine.backend)
    mlp = engine.model.sparse_mlp
    plans = [lin.plan for lin in (mlp.up, mlp.gate, mlp.down)]
    plans += [p.grad_plan for p in plans if p.grad_plan is not None]
    check(all(p.backend in (None, "pallas") for p in plans),
          [p.backend for p in plans])
    print(f"[serving] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, block-sparse FFN "
          f"{cfg.ffn_block}x{cfg.ffn_block} at density {cfg.ffn_density}; "
          f"engine on backend {engine.backend!r}, plans "
          f"{sorted({str(p.backend) for p in plans})} "
          f"({time.time() - t0:.1f}s to build)", flush=True)

    rng = np.random.default_rng(0)

    def requests(lengths, max_new):
        return [Request(prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
                        max_new_tokens=max_new) for n in lengths]

    # warm-up covers every compiled shape: 150 tokens prefill as fresh 64,
    # then 64, 16, 16 (padded); 16 tokens as a fresh 16; then decode
    t0 = time.time()
    engine.generate(requests((150, 16), 2))
    warm = dict(engine.compiled_shapes)
    print(f"[serving] warm-up compiled {warm} in {time.time() - t0:.1f}s",
          flush=True)
    lengths = rng.integers(16, 301, size=8)
    t0 = time.time()
    reqs = engine.generate(requests(lengths, 16))
    got = [int(r.out_tokens.size) for r in reqs]
    check(got == [16] * len(reqs), got)
    check(engine.compiled_shapes == warm, (engine.compiled_shapes, warm))
    print(f"[serving] {len(reqs)} requests (prompts {sorted(lengths.tolist())}) each "
          f"retired at 16 new tokens in {time.time() - t0:.1f}s; compiled "
          f"shapes stayed {engine.compiled_shapes}", flush=True)

    n_prompt = 64
    seq = jnp.asarray(rng.integers(0, cfg.vocab, n_prompt + 2,
                                   dtype=np.int32))
    got = _cache_logits(engine.model, engine.params, seq, n_prompt,
                        engine.backend)
    want = _reference_logits(cfg, params, seq, n_prompt)
    err = _rel_err(got, want)
    status = "ok" if err <= LOGIT_TOL else "FAIL"
    print(f"[serving] prefill {n_prompt} + 2 decode steps (bf16) vs fp32 "
          f"reference forward (highest precision): max|dlogit|/max|logit| = "
          f"{err:.3e} (tol {LOGIT_TOL:.0e}), argmax agree "
          f"{int((got.argmax(-1) == want.argmax(-1)).sum())}/3 {status}",
          flush=True)
    check(err <= LOGIT_TOL, err)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[serving] device peak_bytes_in_use = "
          f"{stats.get('peak_bytes_in_use')}", flush=True)


def phase_trainer_4chips() -> None:
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.runtime import Trainer, TrainerConfig

    cfg = dataclasses.replace(get_config("phi3-mini-3.8b"), n_layers=2)
    shape = ShapeConfig("smoke", "train", seq_len=512, global_batch=8)
    tcfg = TrainerConfig(steps=3, log_every=1)
    devices = jax.devices()[:4]
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    sharded = Trainer(build_model(cfg), cfg, shape, tcfg, mesh=mesh)
    with jax.set_mesh(mesh):
        out_mesh = sharded.run()
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    print(f"[trainer] {cfg.name} widths, {cfg.n_layers} layers, dense FFN, "
          f"2x2 (data, model) mesh: bytes_in_use per device {in_use}",
          flush=True)
    del sharded
    single = Trainer(build_model(cfg), cfg, shape, tcfg)
    out_one = single.run()
    l_mesh = [h["loss"] for h in out_mesh["history"]]
    l_one = [h["loss"] for h in out_one["history"]]
    diff = max(abs(a - b) for a, b in zip(l_mesh, l_one))
    status = "ok" if diff <= LOSS_TOL else "FAIL"
    print(f"[trainer] losses sharded {l_mesh} vs one device {l_one}: "
          f"max|diff| = {diff:.3e} (tol {LOSS_TOL:.0e}) {status}", flush=True)
    check(len(l_mesh) == len(l_one) == 3 and diff <= LOSS_TOL, diff)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    phases = ((phase_trainer_4chips,) if args.chips == 4
              else (phase_kernels, phase_serving))
    failed = []
    for phase in phases:
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
