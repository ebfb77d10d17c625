"""Training runtime: sharded train step, grad accumulation, fault tolerance.

The train step lowered here is also what the multi-pod dry-run compiles:

    state = (params fp32 [FSDP+TP sharded], AdamW m/v [same], step)
    step:  scan over `accum_steps` microbatches → mean grads → clip → AdamW

Fault tolerance:
* async atomic checkpoints every ``ckpt_every`` (checkpoint/),
* ``resume="auto"`` restarts from the latest commit,
* the data pipeline is a pure function of the step → replaying after
  restart or re-mesh is exact (no data loss / duplication),
* ``failure_hook`` lets tests inject a crash at a chosen step (the restart
  test exercises the full save→crash→restore→bitwise-continue path),
* elastic re-mesh lives in runtime/elastic.py (restore onto a smaller mesh).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticDataset
from repro.optim import AdamW, cosine_with_warmup
from repro.sharding import (batch_pspecs, constrain_like_params,
                            make_shardings, params_pspecs)


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    peak_lr: float = 3e-4
    warmup: int = 10
    accum_steps: int = 1
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    resume: str = "auto"          # auto | none
    grad_compression: Optional[str] = None   # None | int8 | topk


def make_train_step(model, opt: AdamW, accum_steps: int,
                    mesh: Optional[Mesh] = None, accum_dtype=jnp.float32,
                    fsdp="data"):
    """Build the jitted (state, batch) → (state, metrics) step.

    ``accum_dtype=bf16`` halves the gradient-accumulation buffer for
    state-dominated giants (llama4-class); loss scale is unaffected because
    microbatch grads are averaged, not summed, into the buffer."""

    def loss_fn(params, microbatch):
        loss, metrics = model.loss_fn(params, microbatch)
        return loss, metrics

    def step_fn(state, batch):
        params, opt_state = state

        if accum_steps > 1:
            def split(x):
                return x.reshape(accum_steps, x.shape[0] // accum_steps,
                                 *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                gsum, lsum = carry
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                grads = constrain_like_params(grads, fsdp)  # FSDP reduce-scatter
                gsum = jax.tree.map(
                    lambda a, g: (a.astype(jnp.float32)
                                  + g.astype(jnp.float32) / accum_steps
                                  ).astype(accum_dtype), gsum, grads)
                return (gsum, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (gsum, lsum), _ = jax.lax.scan(acc, (zeros, 0.0), micro)
            grads = gsum
            loss = lsum / accum_steps
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            grads = constrain_like_params(grads, fsdp)

        new_params, new_opt, om = opt.update(grads, opt_state, params)
        metrics = {"loss": loss, **om}
        return (new_params, new_opt), metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,))
    return step_fn  # caller jits with explicit shardings


class Trainer:
    def __init__(self, model, model_cfg, shape_cfg, tcfg: TrainerConfig,
                 mesh: Optional[Mesh] = None, seed: int = 0):
        self.model = model
        self.model_cfg = model_cfg
        self.shape_cfg = shape_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.data = SyntheticDataset(model_cfg, shape_cfg, seed=seed + 1)
        self.opt = AdamW(lr=cosine_with_warmup(tcfg.peak_lr, tcfg.warmup,
                                               tcfg.steps))
        key = jax.random.PRNGKey(seed)
        self._step_fn = make_train_step(model, self.opt, tcfg.accum_steps,
                                        mesh)
        state_sh = None
        if mesh is None:
            params = model.init(key)
            opt_state = self.opt.init(params)
        else:
            from repro.optim import AdamWState
            abstract = jax.eval_shape(model.init, key)
            pspecs = params_pspecs(abstract)
            p_sh = make_shardings(mesh, pspecs, abstract)
            opt_abs = jax.eval_shape(self.opt.init, abstract)
            opt_sh = AdamWState(
                step=NamedSharding(mesh, P()),
                m=make_shardings(mesh, pspecs, opt_abs.m),
                v=make_shardings(mesh, pspecs, opt_abs.v))
            # each device materializes only its own shard of the params and
            # of the AdamW state — nothing is built whole on one device
            params = jax.jit(model.init, out_shardings=p_sh)(key)
            opt_state = jax.jit(self.opt.init, out_shardings=opt_sh)(params)
            state_sh = (p_sh, opt_sh)
            # pin outputs to the same shardings as inputs: the state is
            # donated and fed straight back in, so compiler-chosen output
            # shardings would mismatch in_shardings on the second call.
            self._step_fn = jax.jit(
                self._step_fn, donate_argnums=(0,),
                in_shardings=(state_sh, None),
                out_shardings=(state_sh, None))
        self.state = (params, opt_state)
        self.start_step = 0
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir)
                     if tcfg.ckpt_dir else None)
        if self.ckpt and tcfg.resume == "auto":
            latest = self.ckpt.latest_step()
            if latest is not None:
                self.state = self.ckpt.restore(latest, self.state,
                                               shardings=state_sh)
                self.start_step = latest

    def run(self, failure_hook: Optional[Callable[[int], None]] = None
            ) -> Dict[str, Any]:
        history = []
        for step in range(self.start_step, self.tcfg.steps):
            batch = jax.tree.map(jnp.asarray, self.data.batch(step))
            self.state, metrics = self._step_fn(self.state, batch)
            if step % self.tcfg.log_every == 0 or step == self.tcfg.steps - 1:
                history.append({"step": step,
                                "loss": float(metrics["loss"]),
                                "grad_norm": float(metrics["grad_norm"])})
            if self.ckpt and (step + 1) % self.tcfg.ckpt_every == 0:
                self.ckpt.save(step + 1, self.state)
            if failure_hook is not None:
                failure_hook(step)   # may raise to simulate a crash
        if self.ckpt:
            self.ckpt.save(self.tcfg.steps, self.state, wait=True)
        return {"history": history, "final_loss": history[-1]["loss"]}
