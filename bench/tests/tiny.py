"""CPU-sized configurations and a run context that skips the chip check."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness import spec  # noqa: E402
from harness.context import Context  # noqa: E402

PHI3 = "phi3-mini-3.8b-bsffn"
TABLE3 = "table3-spgemm"


def phi3_config() -> dict:
    """The phi3 configuration file at CPU widths, on the interpret backend."""
    cfg = copy.deepcopy(spec.load_json(BENCH / "configs" / f"{PHI3}.json"))
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=500,
               ffn_block=32, slots=2, max_len=160, backend="interpret",
               sample_tokens=24)
    return cfg


def phi3_mid_config() -> dict:
    """Eight layers and an 8000-token vocabulary: deep and wide enough on
    the CPU that the float8 control moves the greedy tokens, as it does at
    the published widths on the chip."""
    cfg = phi3_config()
    cfg.update(hidden_size=256, intermediate_size=1024, num_hidden_layers=8,
               vocab_size=8000, ffn_block=128, sample_tokens=150)
    return cfg


def chat_traffic(rate: float = 4.0) -> dict:
    return {"generator": "requests", "arrivals": {"kind": "poisson",
                                                  "rate": rate},
            "prompt": {"dist": "lognormal", "mean": 33, "sigma": 0.8,
                       "min": 4, "max": 80},
            "output": {"dist": "lognormal", "mean": 7, "sigma": 0.5,
                       "min": 2, "max": 12}, "pool": 16}


def offline_traffic() -> dict:
    t = chat_traffic()
    t["arrivals"] = {"kind": "closed", "depth": 4}
    return t


def table3_config() -> dict:
    cfg = copy.deepcopy(spec.load_json(BENCH / "configs" / f"{TABLE3}.json"))
    cfg.update(backend="interpret", block=32)
    cfg["tiny-band"] = {"m": 300, "n": 256, "density": 0.02,
                        "family": "banded"}
    cfg["tiny-hub"] = {"m": 200, "n": 320, "density": 0.01,
                       "family": "powernet"}
    return cfg


def table3_traffic() -> dict:
    return {"generator": "table3", "matrices": ["tiny-band", "tiny-hub"]}


class _NoCompileCount:
    armed = False
    count = 0


def context(cfg, traffic, seed=1, seconds=1.0, spans=None):
    from harness.record import Spans
    return Context(workload="tiny", config=cfg,
                   traffic=traffic, seed=seed, seconds=seconds,
                   spans=spans or Spans(), counter=_NoCompileCount(),
                   t_start=time.time(), device_kind="TPU v5 lite")
