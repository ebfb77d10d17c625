"""Serve a closed backlog through the program's ``Engine`` on a
DeepSeek-V3-block configuration (latent attention, an expert share).

Set-up builds the model from the configuration file's keys through the
program's ``serving_config``/``build_model``, makes the weights on the
device from the seed in one jitted call, builds the ``Engine``, serves one
warm-up request set that compiles every program shape, and fills every
slot.  The window then serves the backlog for ``seconds`` (traced, at most
``TRACED_SECONDS``) exactly as the ``serving`` driver does (its
``_Server``: the benchmark keeps the queue and hands a request to the
engine when a slot is free), and the window's change of
``Engine.counters()`` goes into the run's counters.

Afterwards a sample of the finished requests, drawn from the seed and
holding the longest (the ``serving`` driver's ``sample``), is compared with
the configuration's plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time
import zlib
from typing import Callable, Dict, Optional

import numpy as np

from harness import spec
from harness.context import Context
from harness.record import Run

#: the longest traced window: the TPU profiler keeps about 4.4 million
#: device events, which this cell's ~135,000 ops a second fill ~33 s into
#: a window, and a trace that ends early reads the rest of the window as
#: idle (a traced run reports per-layer metrics only)
TRACED_SECONDS = 25.0

_serving = spec.driver("serving")
_Server, sample = _serving._Server, _serving.sample


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` holding the configuration file's sizes:
    the router scores ``published["n_routed_experts"]`` experts and the
    model holds ``n_routed_experts`` of them, the ``expert_rank``-th share."""
    from repro.launch.serve import serving_config
    if cfg["q_lora_rank"] is not None or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid" \
            or not cfg["norm_topk_prob"]:
        raise ValueError("serving_moe runs latent attention without a query "
                         "LoRA and one routing group of sigmoid scores, "
                         "normalized")
    return dataclasses.replace(
        serving_config(cfg["registry"]),
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["n_routed_experts"], expert_rank=cfg["expert_rank"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"], moe_score="sigmoid",
        moe_route_scale=cfg["routed_scaling_factor"])


def make_params(cfg: Dict, shapes, seed: int):
    """The weights, on the device, in one jitted call from ``seed``.

    ``shapes`` is the program's parameter tree as ``jax.eval_shape`` gives
    it; each leaf is drawn by its path from ``fold_in(key, crc32(path))``:
    embedding tables N(0, 0.02^2) with the rows past ``vocab_size`` zero,
    norm scales 1 + N(0, 0.1^2), the router's correction bias
    (``score_bias``) N(0, 0.01^2), biases zero, every other weight
    N(0, 1/fan_in), its fan-in the second-last axis."""
    import jax
    import jax.numpy as jnp

    def leaf(key, path, sd):
        names = [str(getattr(p, "key", p)) for p in path]
        k = jax.random.fold_in(key, zlib.crc32("/".join(names).encode()))
        z = jax.random.normal(k, sd.shape, jnp.float32)
        if names[-1] == "table":
            rows = jnp.arange(sd.shape[0]) < cfg["vocab_size"]
            return (0.02 * z * rows[:, None]).astype(sd.dtype)
        if names[-1] == "scale":
            return (1.0 + 0.1 * z).astype(sd.dtype)
        if names[-1] == "score_bias":
            return (0.01 * z).astype(sd.dtype)
        if names[-1] == "b":
            return jnp.zeros(sd.shape, sd.dtype)
        return (z / np.sqrt(sd.shape[-2])).astype(sd.dtype)

    def init(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, sd: leaf(key, path, sd), shapes)

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                             int(seed) >> 32)
    return jax.block_until_ready(jax.jit(init)(key))


def run(ctx: Context, *, patch: Optional[Callable] = None) -> Run:
    """One serving run of a closed backlog.  ``patch(engine)`` lets tests
    break the timed path or round its weights (the control)."""
    import jax
    from repro.models import build_model
    from repro.runtime import Engine, Request

    cfg, traffic, spans = ctx.config, ctx.traffic, ctx.spans
    if traffic["arrivals"]["kind"] != "closed":
        raise ValueError("serving_moe serves a closed backlog")
    gen = spec.generator(traffic["generator"])
    reference = spec.reference(cfg["reference"])
    with spans("build_model"):
        model = build_model(model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with spans("make_params"):
        params = make_params(cfg, shapes, ctx.seed)
    with spans("engine"):
        engine = Engine(model, params, slots=cfg["slots"],
                        max_len=cfg["max_len"], backend=cfg["backend"])
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
    depth = int(traffic["arrivals"]["depth"])
    for _ in range(engine.slots):       # the backlog is already running
        server.take(0.0, next(arrivals), measured=False)
    server.admit()
    setup_s = time.time() - ctx.t_start
    ctx.counter.armed = True
    if ctx.tracer is not None:
        ctx.tracer.start()
    before = engine.counters()
    t0 = time.perf_counter()
    t1 = t0 + (ctx.seconds if ctx.tracer is None
               else min(ctx.seconds, TRACED_SECONDS))
    with spans("window"):
        while time.perf_counter() < t1:
            while len(server.queue) < depth:
                server.take(time.perf_counter(), next(arrivals),
                            measured=True)
            server.admit()
            if server.live:
                server.step()
    t_end = time.perf_counter()
    after = engine.counters()
    if ctx.tracer is not None:
        ctx.tracer.stop()
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
    attempted = [r for r in records
                 if any(t0 <= t <= t_end for t in r.token_times)]
    result.counters.update({k: after[k] - before[k] for k in after})
    result.counters.update(compiles_in_window=compiles)
    result.extra.update(checks=checks, device=device,
                        attempted=len(attempted), failed=0)
    return result
