"""Continuous-batching serving engine: slot-level admission, per-slot
positions, immediate retirement.

The paper's thesis — fine-grained *dynamic* work assignment beats static
lockstep scheduling for utilization and load balance — applied at the
request level.  The old ``Server`` formed lockstep groups: pad every
prompt to the group max, decode ``max(max_new_tokens)`` steps, retire the
whole group at once.  That shape was slow (head-of-line blocking,
over-decode) and *wrong*: a single shared scalar position meant every
request shorter than the group max sampled its first token from padding
and decoded every subsequent token at a shifted position.

:class:`Engine` is a continuous batcher that fixes the bug by
construction:

* **slots** — a fixed number of batch rows backed by one persistent KV
  cache allocated at engine construction.  A request occupies exactly one
  slot from admission to retirement, and every slot tracks its own
  absolute position: the decode dispatch passes a per-row ``(B,)``
  position vector to ``model.decode_step``, so no row ever reads another
  row's timeline or padding.
* **admission** — whenever a slot is free and the queue is non-empty, the
  next request is prefilled into that slot: chunked, length-bucketed, and
  jitted, so steady-state serving executes a *fixed set of compiled
  shapes* (one decode shape + one per prefill bucket) with no retracing
  across arrivals.  The first prefill chunk zeroes the slot's cache row,
  wiping any state left by the previous occupant (attention junk is
  position-masked anyway, but recurrent-state rows must be reset).
* **retirement** — a request leaves its slot the moment it emits
  ``eos_token`` or reaches its own ``max_new_tokens``; the slot is handed
  to the next queued request immediately.  No lockstep groups, no
  over-decode to a group max.

Free slots ride along in the batched decode with ``pos=0`` and a dummy
token; their writes land in rows that the next admission's fresh prefill
resets/overwrites, and attention masking keeps them invisible.  MoE rows
are independent too: expert dispatch drops no token, so each row is
computed as if alone.

Kernel backend selection goes through :mod:`repro.api.backends`: an
engine constructed with ``backend="interpret"`` (CPU correctness runs) or
``backend="pallas"`` (TPU) traces its jitted step functions under that
backend, so Segment-plan layers in the model bake the right execution
mode in.

Observability: the two jitted programs are named ``engine_decode`` and
``engine_prefill`` (``jit_engine_decode``/``jit_engine_prefill`` in a
profiler trace); the host work is in ``segfold.engine.*`` spans
(:mod:`repro.obs`): ``admit`` per admitted request holding a ``prefill``
per chunk and the ``first_token`` sync, and ``step`` per decode step
holding ``prepare``, ``dispatch``, ``sync`` and ``update``, and
``cast_weights`` once in construction.  :meth:`Engine.counters` counts
the work; an MoE model's counts are device data that come back with the
tokens each program returns, so they add no sync.

Weights: the engine holds the tree the model's ``compute_params`` gives,
every weight the programs read only in the compute dtype cast to it once
at construction, and serves from that tree alone (``Engine.params``).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.backends import resolve_backend, use_backend
from repro.models.moe import COUNTERS as MOE_COUNTERS


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                 # (T,) int32
    max_new_tokens: int = 16
    eos_token: Optional[int] = None    # retire early on this token (kept in
                                       # the output, vLLM-style)
    out_tokens: Optional[np.ndarray] = None
    rid: int = -1                      # assigned by Engine.submit


@dataclasses.dataclass
class _Slot:
    """Host-side per-slot decode state."""
    request: Request
    pos: int                           # tokens in cache == next write index
    last_tok: int                      # token to feed at the next step
    out: List[int] = dataclasses.field(default_factory=list)


#: the work :meth:`Engine.counters` counts
COUNTERS = ("decode_steps", "decode_rows", "prefill_chunks", "prefill_tokens",
            "prefill_padded_tokens", "spmm_cols_useful", "spmm_cols_computed"
            ) + MOE_COUNTERS


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _nbytes(a) -> int:
    """Bytes of an array or a ``jax.ShapeDtypeStruct``."""
    return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize


class Engine:
    """Greedy continuous-batching generation over a fixed slot count.

    ``prefill_buckets`` (descending chunk sizes; each a multiple of the
    smallest) defines the compiled prefill shapes: a prompt is fed through
    the largest bucket that fits the remaining tokens, and the final
    partial chunk is zero-padded up to the smallest bucket — the padded
    region is position-masked out of attention and never advances the
    slot's position.  Models with recurrent state (hybrid/ssm families)
    force ``(1,)``: a recurrent scan has no mask lane, so padded tokens
    would corrupt the carried state.
    """

    def __init__(self, model, params, *, slots: int = 4, max_len: int = 512,
                 backend: Optional[str] = None,
                 prefill_buckets: Tuple[int, ...] = (64, 16),
                 quantize: Optional[str] = None):
        if getattr(model.cfg, "family", None) == "enc_dec":
            raise NotImplementedError(
                "enc_dec serving needs encoder output plumbing; the engine "
                "currently serves decoder-only families")
        if quantize is not None:
            # freeze the block-sparse FFN weights for low-precision decode:
            # the engine's jitted step functions then trace over quantized
            # plans (int8/fp8 payload + fp32-scale leaves) and every weight
            # fetch in the Segment kernels moves ~4x fewer bytes
            model, params = model.quantize(params, quantize)
        # the programs read the weights in the compute dtype: cast them
        # once here, before the cache is made, and keep only the cast tree
        with obs.span("engine.cast_weights"):
            held = model.compute_params(params)
            jax.block_until_ready(held)
        cast = jax.tree.leaves(jax.tree.map(
            lambda a, h: _nbytes(h) if a.dtype != h.dtype else 0,
            params, held))
        del params
        self.weights_cast_bytes = sum(cast)
        self.weights_kept_bytes = sum(map(_nbytes, jax.tree.leaves(held))) \
            - self.weights_cast_bytes
        self.quantize = quantize
        self.model = model
        self.params = held
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.backend = resolve_backend(backend)

        buckets = tuple(sorted({int(c) for c in prefill_buckets}, reverse=True))
        if not buckets or buckets[-1] < 1:
            raise ValueError(f"bad prefill_buckets {prefill_buckets!r}")
        if any(c % buckets[-1] for c in buckets):
            raise ValueError(
                f"prefill_buckets {buckets} must all be multiples of the "
                f"smallest bucket (chunk starts must stay bucket-aligned)")
        if self._has_recurrent_state():
            buckets = (1,)   # padding would pollute the carried state
        elif getattr(model.cfg, "kv_cache_dtype", "bfloat16") == "int8":
            # the factored-scale int8 attention path is decode-sized only
            buckets = tuple(c for c in buckets if c <= 8) or (8,)
        if self._has_kind("local"):
            # a chunk wider than the ring would scatter duplicate slot
            # indices in one write (undefined survivor order)
            w = int(model.cfg.local_window)
            buckets = tuple(c for c in buckets if c <= w) or (max(1, min(w, 8)),)
        self.prefill_buckets = buckets
        # cache rounded up so a final padded chunk never writes past the end
        # (a clamped dynamic_update_slice would silently corrupt the tail)
        self._cache_len = _round_up(self.max_len, buckets[-1])
        self.cache = model.init_cache(self.slots, self._cache_len)

        self._queue: Deque[Request] = collections.deque()
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._next_rid = 0
        self.completed = 0
        # trace counters: incremented by the traced python bodies, i.e. only
        # when jit actually (re)compiles — the retrace regression tests
        # assert these stay flat across request arrivals/retirements
        self.decode_traces = 0
        self.prefill_traces = 0
        self._counts = dict.fromkeys(COUNTERS, 0)
        # the counts each program returns after its tokens
        self._device_counts = MOE_COUNTERS if model.has_moe() else ()
        # per compiled program: the Segment SpMM calls its trace reported
        self._spmm_calls: Dict[tuple, List[obs.SpmmCall]] = {}

        # the cache is donated: each step replaces it, so the device never
        # holds two copies of it
        def engine_decode(params, cache, tok, pos):
            return self._decode_fn(params, cache, tok, pos)

        def engine_prefill(params, cache, slot, tok, pos, last_idx, fresh):
            return self._prefill_fn(params, cache, slot, tok, pos, last_idx,
                                    fresh)

        self._decode = jax.jit(engine_decode, donate_argnums=(1,))
        self._prefill = jax.jit(engine_prefill, static_argnames=("fresh",),
                                donate_argnums=(1,))

    # -- model introspection -------------------------------------------------

    def _has_kind(self, *wanted: str) -> bool:
        for (_, kinds, _) in getattr(self.model, "groups", ()):
            kinds = kinds if isinstance(kinds, tuple) else (kinds,)
            if any(k in wanted for k in kinds):
                return True
        return False

    def _has_recurrent_state(self) -> bool:
        return self._has_kind("rec", "rwkv")

    # -- jitted step functions ----------------------------------------------

    def _with_counts(self, logits, counts):
        """The greedy tokens, then the program's device counts."""
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.concatenate(
            [tok] + [counts[k][None] for k in self._device_counts])

    def _add_counts(self, out: np.ndarray, n_tokens: int) -> None:
        for k, v in zip(self._device_counts, out[n_tokens:]):
            self._counts[k] += int(v)

    def _decode_fn(self, params, cache, tok, pos):
        """tok (S, 1), pos (S,) — one batched decode step at per-slot
        positions; returns (greedy next token (S,) then the device counts,
        new cache)."""
        self.decode_traces += 1
        with use_backend(self.backend), obs.spmm_columns() as calls:
            logits, cache, counts = self.model.decode_step_counted(
                params, cache, tok, pos)
        self._spmm_calls[("decode",)] = calls
        return self._with_counts(logits, counts), cache

    def _prefill_fn(self, params, cache, slot, tok, pos, last_idx, fresh):
        """Prefill one chunk of one slot: slice the slot's cache row out,
        run the chunk at absolute offset ``pos``, write the row back.

        ``last_idx`` indexes the chunk's last *valid* token — the returned
        greedy token is sampled there, never from padding.  ``fresh``
        (static) zeroes the row first: admission wipes the previous
        occupant's recurrent state / ring buffer."""
        self.prefill_traces += 1
        row = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), cache)
        if fresh:
            row = jax.tree.map(jnp.zeros_like, row)
        with use_backend(self.backend), obs.spmm_columns() as calls:
            logits, row, counts = self.model.decode_step_counted(
                params, row, tok, pos, logit_idx=last_idx)
        self._spmm_calls[("prefill", tok.shape[1], fresh)] = calls
        cache = jax.tree.map(
            lambda full, r: jax.lax.dynamic_update_slice_in_dim(
                full, r, slot, axis=1),
            cache, row)
        return self._with_counts(logits, counts), cache

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request: Request) -> Request:
        """Validate and enqueue. Raises ``ValueError`` if the request could
        not fit the cache — the old server silently clamped the cache write
        index and corrupted the tail instead."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{request.max_new_tokens}")
        total = prompt.size + request.max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"request needs {prompt.size} prompt + "
                f"{request.max_new_tokens} new = {total} positions but "
                f"max_len={self.max_len}; longer contexts need a larger "
                f"engine (or chunk the request)")
        request.prompt = prompt
        request.rid = self._next_rid
        self._next_rid += 1
        self._queue.append(request)
        return request

    def _chunk_schedule(self, length: int) -> List[int]:
        """Bucket sizes covering ``length`` prompt tokens (the last chunk
        may be zero-padded; starts stay aligned to the smallest bucket)."""
        chunks, done = [], 0
        while done < length:
            rem = length - done
            c = next((c for c in self.prefill_buckets if c <= rem),
                     self.prefill_buckets[-1])
            chunks.append(c)
            done += c
        return chunks

    def _count_spmm(self, program: tuple, useful: int) -> None:
        """Add one run of ``program`` with ``useful`` real rows or tokens
        to the SpMM column counters."""
        for n, computed, runs in self._spmm_calls.get(program, ()):
            self._counts["spmm_cols_useful"] += runs * min(useful, n)
            self._counts["spmm_cols_computed"] += runs * computed

    def _admit(self, s: int, req: Request) -> None:
        prompt = req.prompt
        length = int(prompt.shape[0])
        done = 0
        outs = []
        with obs.span("engine.admit", rid=req.rid):
            for i, c in enumerate(self._chunk_schedule(length)):
                with obs.span("engine.prefill"):
                    n = min(c, length - done)
                    buf = np.zeros((1, c), np.int32)
                    buf[0, :n] = prompt[done:done + n]
                    out, self.cache = self._prefill(
                        self.params, self.cache, jnp.int32(s),
                        jnp.asarray(buf), jnp.int32(done),
                        jnp.asarray([n - 1], jnp.int32), fresh=(i == 0))
                    outs.append(out)
                self._counts["prefill_chunks"] += 1
                self._counts["prefill_tokens"] += n
                self._counts["prefill_padded_tokens"] += c
                self._count_spmm(("prefill", c, i == 0), n)
                done += n
            # only the final chunk's token matters — one host sync per
            # admission, which brings every chunk's counts along
            with obs.span("engine.first_token"):
                got = (jax.device_get(outs) if self._device_counts
                       else [np.asarray(outs[-1])])
                tok = int(got[-1][0])
                for g in got:
                    self._add_counts(g, 1)
        slot = _Slot(request=req, pos=length, last_tok=tok, out=[tok])
        self._slots[s] = slot
        if self._finished(slot):
            self._retire(s)

    def _finished(self, slot: _Slot) -> bool:
        r = slot.request
        return (len(slot.out) >= r.max_new_tokens
                or (r.eos_token is not None and slot.out
                    and slot.out[-1] == r.eos_token))

    def _retire(self, s: int) -> None:
        slot = self._slots[s]
        slot.request.out_tokens = np.asarray(slot.out, np.int32)
        self._slots[s] = None
        self.completed += 1

    # -- the serving loop ----------------------------------------------------

    def admit_pending(self) -> int:
        """Prefill queued requests into free slots; returns slots filled."""
        filled = 0
        for s in range(self.slots):
            if self._slots[s] is None and self._queue:
                self._admit(s, self._queue.popleft())
                filled += 1
        return filled

    def step(self) -> int:
        """Admit into free slots, then run one batched decode step.
        Returns the number of live slots that advanced."""
        self.admit_pending()
        live = [s for s in range(self.slots) if self._slots[s] is not None]
        if not live:
            return 0
        with obs.span("engine.step"):
            with obs.span("engine.prepare"):
                tok = np.zeros((self.slots, 1), np.int32)
                pos = np.zeros((self.slots,), np.int32)
                for s in live:
                    tok[s, 0] = self._slots[s].last_tok
                    pos[s] = self._slots[s].pos
                tok, pos = jnp.asarray(tok), jnp.asarray(pos)
            with obs.span("engine.dispatch"):
                nxt, self.cache = self._decode(self.params, self.cache, tok,
                                               pos)
            with obs.span("engine.sync"):
                nxt = np.asarray(nxt)
            with obs.span("engine.update"):
                self._add_counts(nxt, self.slots)
                self._counts["decode_steps"] += 1
                self._counts["decode_rows"] += len(live)
                self._count_spmm(("decode",), len(live))
                for s in live:
                    slot = self._slots[s]
                    slot.pos += 1               # last_tok now sits in cache
                    slot.last_tok = int(nxt[s])
                    slot.out.append(slot.last_tok)
                    if self._finished(slot):
                        self._retire(s)
        return len(live)

    def run(self) -> None:
        """Drain the queue and all occupied slots."""
        while self._queue or any(s is not None for s in self._slots):
            self.step()

    def generate(self, requests: List[Request]) -> List[Request]:
        """Submit + drain; fills each request's ``out_tokens`` in place."""
        for r in requests:
            self.submit(r)
        self.run()
        return requests

    # -- introspection -------------------------------------------------------

    @property
    def compiled_shapes(self) -> Dict[str, int]:
        """Trace counts per step function — flat after warmup."""
        return {"decode": self.decode_traces, "prefill": self.prefill_traces}

    def counters(self) -> Dict[str, int]:
        """The work done since construction (:data:`COUNTERS`): decode
        steps and the live rows they carried; prefill chunks, their real
        prompt tokens and their bucket sizes; and the columns the Segment
        SpMM kernels computed against those that held a real row or token,
        summed over every SpMM call of every program run (N-tile padding,
        free slots and a chunk's padding are the difference); for an MoE
        model, summed over its MoE layers and program runs, the rows routed
        to held experts (free slots' and a chunk's padding tokens route
        too), the rows the grouped GEMM computed (whole used chunks) and
        the held experts with a route (expert weight loads).  Read it
        before and after a window and subtract.  Two constants of the
        build come with them: ``weights_cast_bytes``, the bytes of the
        weights the model's ``compute_params`` cast to the compute
        dtype, and ``weights_kept_bytes``, those of the weights held as
        given."""
        return dict(self._counts, weights_cast_bytes=self.weights_cast_bytes,
                    weights_kept_bytes=self.weights_kept_bytes)


class Server(Engine):
    """Back-compat surface of the old lockstep batcher.

    Same constructor keywords (``batch_slots``); ``generate`` now runs the
    continuous-batching engine, so mixed-length batches decode correctly
    (the lockstep version sampled short prompts' first tokens from
    padding) and mixed ``max_new_tokens`` no longer over-decode.
    """

    def __init__(self, model, params, *, batch_slots: int = 4,
                 max_len: int = 512, backend: Optional[str] = None, **kw):
        super().__init__(model, params, slots=batch_slots, max_len=max_len,
                         backend=backend, **kw)
