"""Serve a traffic mix through the program's continuous-batching ``Engine``.

Set-up builds the model from the configuration file through the program's
``serving_config``/``build_model``, makes the weights on the device from
the seed in one jitted call, builds the ``Engine``, serves one warm-up
request set that compiles every program shape the traffic uses, and, for a
closed backlog, fills every slot.  The window then serves the traffic for
``seconds``:

- the benchmark keeps the queue: an arrived request is handed to the engine
  (``submit`` + ``admit_pending``) only when a slot is free, so each
  ``admit_pending`` admits exactly one request and returns once its first
  token is on the host;
- each ``step`` gives every admitted, unretired request one token, on the
  host when it returns; ``out_tokens`` marks retirement;
- in an open loop, requests that arrived inside the window are served to
  their end after it closes, while arrivals go on, unmeasured, to keep the
  load.

Afterwards a sample of the finished requests, drawn from the seed and
holding the longest, is compared with the configuration's plain reference.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import spec
from harness.context import Context
from harness.record import RequestRecord, Run

#: an open loop serves the window's requests to their end within this long
DRAIN_LIMIT_S = 120.0


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` holding the configuration file's sizes."""
    from repro.launch.serve import serving_config
    base = serving_config(cfg["registry"], sparse_ffn=True,
                          ffn_block=cfg["ffn_block"],
                          ffn_density=cfg["ffn_density"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def make_params(cfg: Dict, shapes, seed: int):
    """The weights, on the device, in one jitted call from ``seed``.

    ``shapes`` is the program's parameter tree as ``jax.eval_shape`` gives
    it; each leaf is drawn by its path: embedding tables N(0, 0.02^2) with
    the rows past ``vocab_size`` zero, norm scales 1 + N(0, 0.1^2), weights
    N(0, 1/fan_in), biases zero.  An FFN projection keeps only
    ``ffn_density`` of its blocks, so its stored blocks are drawn
    N(0, 1/(ffn_density * fan_in)): each output then sums as much variance
    as a dense projection's, and the FFN weighs in the residual stream as
    much as a dense FFN would."""
    import jax
    import jax.numpy as jnp

    fan_in = {"up": cfg["hidden_size"], "gate": cfg["hidden_size"],
              "down": cfg["intermediate_size"]}

    def leaf(key, path, sd):
        names = [str(getattr(p, "key", p)) for p in path]
        k = jax.random.fold_in(key, zlib.crc32("/".join(names).encode()))
        z = jax.random.normal(k, sd.shape, jnp.float32)
        if names[-1] == "table":
            rows = jnp.arange(sd.shape[0]) < cfg["vocab_size"]
            return (0.02 * z * rows[:, None]).astype(sd.dtype)
        if names[-1] == "scale":
            return (1.0 + 0.1 * z).astype(sd.dtype)
        if names[-1] == "b":
            return jnp.zeros(sd.shape, sd.dtype)
        if names[-1] == "blocks":
            std = 1.0 / np.sqrt(cfg["ffn_density"] * fan_in[names[-2]])
            return (z * std).astype(sd.dtype)
        return (z / np.sqrt(sd.shape[-2])).astype(sd.dtype)

    def init(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, sd: leaf(key, path, sd), shapes)

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                             int(seed) >> 32)
    return jax.block_until_ready(jax.jit(init)(key))


def sample(records: List[RequestRecord], outputs: Dict[int, np.ndarray],
           seed: int, tokens: int) -> List[int]:
    """Finished requests to compare: the longest, then others drawn from
    the seed until ``tokens`` served tokens are held."""
    done = sorted(outputs)
    if not done:
        return []
    longest = max(done, key=lambda i: records[i].prompt_len + outputs[i].size)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    picked, held = [longest], outputs[longest].size
    for i in rng.permutation(done):
        if held >= tokens:
            break
        if int(i) != longest:
            picked.append(int(i))
            held += outputs[int(i)].size
    return picked


class _Server:
    """The benchmark's side of the engine: its queue and timestamps."""

    def __init__(self, engine, spans, request_cls):
        self.engine, self.spans, self.request_cls = engine, spans, request_cls
        self.records: List[RequestRecord] = []
        self.prompts: Dict[int, np.ndarray] = {}
        self.outputs: Dict[int, np.ndarray] = {}
        self.queue = collections.deque()
        self.live: Dict[int, object] = {}

    def take(self, arrival: float, a, measured: bool) -> None:
        self.records.append(RequestRecord(
            prompt_len=int(a.prompt.size), max_new=a.max_new, arrival=arrival,
            submitted=time.perf_counter(), measured=measured))
        i = len(self.records) - 1
        self.prompts[i] = a.prompt
        self.queue.append((i, self.request_cls(prompt=a.prompt,
                                               max_new_tokens=a.max_new)))

    def admit(self) -> None:
        """Hand queued requests to free slots, one ``admit_pending`` each."""
        while self.queue and len(self.live) < self.engine.slots:
            i, req = self.queue.popleft()
            self.engine.submit(req)
            with self.spans("admit"):
                self.engine.admit_pending()
            self.records[i].token_times.append(time.perf_counter())
            self.live[i] = req
            self._retire(i)

    def step(self) -> None:
        with self.spans("step"):
            self.engine.step()
        t = time.perf_counter()
        for i in list(self.live):
            self.records[i].token_times.append(t)
            self._retire(i)

    def _retire(self, i: int) -> None:
        req = self.live[i]
        if req.out_tokens is not None:
            self.outputs[i] = np.asarray(req.out_tokens)
            self.records[i].done = True
            del self.live[i]

    def pending(self) -> bool:
        return any(r.measured and not r.done for r in self.records)


def run(ctx: Context, *, patch: Optional[Callable] = None,
        engine_kw: Optional[Dict] = None) -> Run:
    """One serving run.  ``patch(engine)`` and ``engine_kw`` let tests break
    the timed path or switch on the program's own low-precision path (the
    control)."""
    import jax
    from repro.models import build_model
    from repro.runtime import Engine, Request

    cfg, traffic, spans = ctx.config, ctx.traffic, ctx.spans
    gen = spec.generator(traffic["generator"])
    reference = spec.reference(cfg["reference"])
    with spans("build_model"):
        model = build_model(model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with spans("make_params"):
        params = make_params(cfg, shapes, ctx.seed)
    with spans("engine"):
        engine = Engine(model, params, slots=cfg["slots"],
                        max_len=cfg["max_len"], backend=cfg["backend"],
                        **(engine_kw or {}))
    # the engine holds what it serves; a quantizing engine holds a copy,
    # and the float32 original would not fit beside it
    del params
    with spans("warmup"):
        # a prompt of twice each bucket runs that bucket both as a
        # prompt's first chunk and as a later one; then decode
        rng = np.random.default_rng(0)
        engine.generate([Request(
            prompt=rng.integers(0, cfg["vocab_size"], 2 * b, dtype=np.int32),
            max_new_tokens=2) for b in engine.prefill_buckets])
    shapes_warm = dict(engine.compiled_shapes)
    if patch is not None:
        patch(engine)

    server = _Server(engine, spans, Request)
    arrivals = gen.stream(traffic, ctx.seed, cfg["vocab_size"])
    closed = traffic["arrivals"]["kind"] == "closed"
    if closed:
        depth = int(traffic["arrivals"]["depth"])
        for _ in range(engine.slots):       # the backlog is already running
            server.take(0.0, next(arrivals), measured=False)
        server.admit()
    nxt = next(arrivals)
    setup_s = time.time() - ctx.t_start
    ctx.counter.armed = True
    if ctx.tracer is not None:
        ctx.tracer.start()
    t0 = time.perf_counter()
    t1 = t0 + ctx.seconds
    with spans("window"):
        while True:
            t = time.perf_counter()
            if t >= t1:
                break
            if closed:
                while len(server.queue) < depth:
                    server.take(t, nxt, measured=True)
                    nxt = next(arrivals)
            else:
                while t0 + nxt.offset <= t:
                    server.take(t0 + nxt.offset, nxt, measured=True)
                    nxt = next(arrivals)
            server.admit()
            if server.live:
                server.step()
            elif not closed:
                with spans("wait_arrival"):
                    time.sleep(max(0.0, min(t0 + nxt.offset, t1)
                                   - time.perf_counter()))
    t_end = time.perf_counter()
    if ctx.tracer is not None:
        ctx.tracer.stop()
    drain_end = t_end + DRAIN_LIMIT_S
    while not closed and server.pending() and time.perf_counter() < drain_end:
        t = time.perf_counter()
        while t0 + nxt.offset <= t:
            server.take(t0 + nxt.offset, nxt, measured=t0 + nxt.offset < t1)
            nxt = next(arrivals)
        server.admit()
        if server.live:
            server.step()
        else:
            time.sleep(max(0.0, min(t0 + nxt.offset, drain_end)
                           - time.perf_counter()))
    t_drained = time.perf_counter()
    ctx.counter.armed = False
    compiles = ctx.counter.count + sum(
        engine.compiled_shapes[k] - shapes_warm[k] for k in shapes_warm)
    device = ctx.describe()

    # the engine and its cache are freed, and the weights made again from
    # the seed, before the reference runs
    records, prompts, outputs = server.records, server.prompts, server.outputs
    del engine, server
    gc.collect()
    params = make_params(cfg, shapes, ctx.seed)
    picked = sample(records, outputs, ctx.seed, int(cfg["sample_tokens"]))
    checks = reference.compare(
        cfg, params, [(prompts[i], outputs[i]) for i in picked]) \
        if picked else {}
    result = Run(workload=ctx.workload, config=cfg, traffic=traffic,
                 device_kind=ctx.device_kind, setup_s=setup_s,
                 window=(t0, t_end), spans=spans, requests=records)
    if closed:      # every request that was served inside the window
        attempted = [r for r in records
                     if any(t0 <= t <= t_end for t in r.token_times)]
    else:           # every request that arrived inside it
        attempted = [r for r in records if r.measured]
    result.counters.update(compiles_in_window=compiles)
    result.extra.update(
        checks=checks, device=device, attempted=len(attempted),
        failed=0 if closed else sum(not r.done for r in attempted),
        t_drained=t_drained,
        ffn_blocks=[brow.size for brow, _ in reference.pattern(cfg).values()])
    return result
