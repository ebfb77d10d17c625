"""What the harness hands a driver."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass
class Context:
    workload: str
    config: Dict[str, Any]        # the configuration file
    traffic: Dict[str, Any]       # the cell's traffic file
    seed: int
    seconds: float
    spans: Any                    # harness.record.Spans
    counter: Any                  # harness.device.CompileCounter
    t_start: float                # process start, time.time()
    device_kind: str = ""
    describe: Callable[[], Dict] = dict     # -> result line's ``device``
    tracer: Optional[Any] = None  # .start() / .stop() around the window
