"""Continuous-batching serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --sparse-ffn --backend pallas --requests 8 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --reduced --backend interpret      # CPU-sized smoke run

Without ``--reduced`` the model runs at its published widths.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.api.backends import BACKENDS
from repro.configs import REGISTRY, get_config, reduced_config
from repro.configs.base import ModelConfig
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import Engine, Request


def serving_config(arch: str, *, reduced: bool = False,
                   sparse_ffn: bool = False, ffn_block: Optional[int] = None,
                   ffn_density: Optional[float] = None) -> ModelConfig:
    """The registry config of ``arch``, optionally cut to smoke size and
    switched to the block-sparse (Segment) FFN."""
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    over = {}
    if sparse_ffn:
        over["ffn_block_sparse"] = True
    if ffn_block is not None:
        over["ffn_block"] = ffn_block
    if ffn_density is not None:
        over["ffn_density"] = ffn_density
    return dataclasses.replace(cfg, **over) if over else cfg


def build_engine(cfg: ModelConfig, *, backend: Optional[str] = None,
                 slots: int = 4, max_len: int = 256, seed: int = 0) -> Engine:
    """Model with seeded random weights behind an :class:`Engine`."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return Engine(model, params, slots=slots, max_len=max_len,
                  backend=backend)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list(REGISTRY))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size widths (CPU); default is published widths")
    ap.add_argument("--sparse-ffn", action="store_true",
                    help="block-sparse FFN through the Segment kernels")
    ap.add_argument("--ffn-block", type=int, default=None)
    ap.add_argument("--ffn-density", type=float, default=None)
    ap.add_argument("--backend", default=None, choices=BACKENDS,
                    help="kernel backend (default: pallas on TPU, else "
                         "interpret)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--eos", type=int, default=None,
                    help="retire a request early when it emits this token")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = serving_config(args.arch, reduced=args.reduced,
                         sparse_ffn=args.sparse_ffn, ffn_block=args.ffn_block,
                         ffn_density=args.ffn_density)
    engine = build_engine(cfg, backend=args.backend, slots=args.slots,
                          max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, rng.integers(4, 32),
                                        dtype=np.int32),
                    max_new_tokens=args.max_new, eos_token=args.eos)
            for _ in range(args.requests)]
    t0 = time.time()
    engine.generate(reqs)
    dt = time.time() - t0
    total = sum(r.out_tokens.size for r in reqs)
    print(f"{len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s) on backend {engine.backend!r} — compiled "
          f"shapes: {engine.compiled_shapes}")
    for i, r in enumerate(reqs[:4]):
        print(f"req{i}: prompt_len={len(r.prompt)} out={r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
