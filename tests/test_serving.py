"""Serving-engine correctness: the mixed-length oracle (headline bug
regression), steady-state retrace flatness, admission/retirement dynamics,
and cache-overflow validation.

The oracle test is the regression for the lockstep server's padding bug:
left-aligned zero-padded prompts with one shared scalar position meant any
request shorter than its group's max sampled its first token from padding
and decoded every later token at a shifted position.  The continuous
engine must make a batched mixed-length run token-for-token identical to
generating each request alone.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced_config
from repro.models import build_model
from repro.runtime import Engine, Request, Server

KEY = jax.random.PRNGKey(0)


def _setup(arch="granite-3-8b", **over):
    # f32 so greedy argmax is bitwise batch-size invariant on CPU
    cfg = dataclasses.replace(reduced_config(REGISTRY[arch]),
                              **{"dtype": "float32", **over})
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, l, dtype=np.int32) for l in lens]


# ---------------------------------------------------------------------------
# the headline-bug oracle
# ---------------------------------------------------------------------------


def test_mixed_length_batch_matches_single_request_oracle():
    """Batched mixed-length generation must equal per-request single-slot
    generation token-for-token — no request ever reads padding or a wrong
    position.  slots < requests also exercises retirement + re-admission
    mid-run."""
    cfg, model, params = _setup()
    prompts = _prompts(cfg, (4, 17, 31))

    alone = []
    for p in prompts:
        eng = Engine(model, params, slots=1, max_len=64,
                     prefill_buckets=(16, 8))
        r = Request(prompt=p.copy(), max_new_tokens=6)
        eng.generate([r])
        alone.append(r.out_tokens.tolist())

    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8))
    reqs = [Request(prompt=p.copy(), max_new_tokens=6) for p in prompts]
    eng.generate(reqs)
    batched = [r.out_tokens.tolist() for r in reqs]
    assert batched == alone


def test_mixed_max_new_tokens_no_over_decode():
    """Each request stops at its *own* max_new_tokens (the lockstep server
    decoded everyone to the group max), and shorter budgets are prefixes of
    longer ones from the same prompt."""
    cfg, model, params = _setup()
    prompt = _prompts(cfg, (9,))[0]
    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8))
    reqs = [Request(prompt=prompt.copy(), max_new_tokens=m) for m in (2, 7)]
    eng.generate(reqs)
    a, b = reqs[0].out_tokens.tolist(), reqs[1].out_tokens.tolist()
    assert len(a) == 2 and len(b) == 7
    assert b[:2] == a


def test_eos_frees_slot_early():
    cfg, model, params = _setup()
    prompt = _prompts(cfg, (7,))[0]
    probe = Engine(model, params, slots=1, max_len=64, prefill_buckets=(8,))
    r = Request(prompt=prompt.copy(), max_new_tokens=8)
    probe.generate([r])
    full = r.out_tokens.tolist()
    eos = full[2]
    eng = Engine(model, params, slots=1, max_len=64, prefill_buckets=(8,))
    r2 = Request(prompt=prompt.copy(), max_new_tokens=8, eos_token=eos)
    eng.generate([r2])
    # retired at the first eos occurrence (kept in the output)
    stop = full.index(eos) + 1
    assert r2.out_tokens.tolist() == full[:stop]
    assert eng.completed == 1 and all(s is None for s in eng._slots)


# ---------------------------------------------------------------------------
# steady-state compiled-shape flatness
# ---------------------------------------------------------------------------


def test_no_retrace_across_arrivals_and_retirements():
    """After a warmup wave covering the bucket shapes, further waves of
    different lengths/budgets must not trigger any recompilation."""
    cfg, model, params = _setup()
    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16,))
    # warmup: single-chunk fresh + multi-chunk (fresh + continuation)
    eng.generate([Request(prompt=p, max_new_tokens=3)
                  for p in _prompts(cfg, (5, 20), seed=1)])
    warm = dict(eng.compiled_shapes)
    assert warm == {"decode": 1, "prefill": warm["prefill"], "feed": 1}
    # new arrivals: different lengths, different budgets, queueing + slot
    # churn — all served by the warm shapes
    eng.generate([Request(prompt=p, max_new_tokens=m)
                  for p, m in zip(_prompts(cfg, (3, 21, 13, 16, 30), seed=2),
                                  (2, 5, 1, 4, 3))])
    assert eng.compiled_shapes == warm


def test_persistent_cache_reused_across_generations():
    """The KV cache is allocated once at construction; repeated generate()
    calls reuse the same buffers (no per-batch re-allocation)."""
    cfg, model, params = _setup()
    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16,))
    shapes0 = jax.tree.map(lambda a: a.shape, eng.cache)
    first = jax.tree.leaves(eng.cache)
    p1, p2 = _prompts(cfg, (6, 12), seed=3)
    eng.generate([Request(prompt=p1, max_new_tokens=2)])
    eng.generate([Request(prompt=p2, max_new_tokens=2)])
    assert jax.tree.map(lambda a: a.shape, eng.cache) == shapes0
    # each step donates the cache it replaces: never two copies on device
    assert all(a.is_deleted() for a in first)


def test_slot_reuse_does_not_leak_previous_request():
    """A request admitted into a just-freed slot decodes exactly as it
    would in a fresh engine — admission wipes the previous occupant."""
    cfg, model, params = _setup()
    p_a, p_b = _prompts(cfg, (23, 9), seed=4)
    eng = Engine(model, params, slots=1, max_len=64, prefill_buckets=(16, 8))
    ra = Request(prompt=p_a.copy(), max_new_tokens=5)
    rb = Request(prompt=p_b.copy(), max_new_tokens=5)
    eng.generate([ra, rb])          # rb reuses ra's slot
    fresh = Engine(model, params, slots=1, max_len=64,
                   prefill_buckets=(16, 8))
    rb2 = Request(prompt=p_b.copy(), max_new_tokens=5)
    fresh.generate([rb2])
    assert rb.out_tokens.tolist() == rb2.out_tokens.tolist()


def test_int8_kv_cache_mixed_lengths():
    """The factored-scale int8 KV path is decode-sized (t ≤ 8): the engine
    caps prefill buckets and still matches the single-request oracle."""
    cfg, model, params = _setup(kv_cache_dtype="int8")
    prompts = _prompts(cfg, (4, 17), seed=8)
    alone = []
    for p in prompts:
        e1 = Engine(model, params, slots=1, max_len=64)
        r = Request(prompt=p.copy(), max_new_tokens=4)
        e1.generate([r])
        alone.append(r.out_tokens.tolist())
    eng = Engine(model, params, slots=2, max_len=64)
    assert max(eng.prefill_buckets) <= 8
    reqs = [Request(prompt=p.copy(), max_new_tokens=4) for p in prompts]
    eng.generate(reqs)
    assert [r.out_tokens.tolist() for r in reqs] == alone


def test_recurrent_family_mixed_lengths():
    """Stateful families (hybrid rec + local ring, rwkv) serve mixed
    lengths correctly through the token-wise prefill path."""
    for arch in ("recurrentgemma-9b", "rwkv6-1.6b"):
        cfg, model, params = _setup(arch)
        assert Engine(model, params, slots=1, max_len=64).prefill_buckets \
            == (1,)
        prompts = _prompts(cfg, (3, 14), seed=5)
        alone = []
        for p in prompts:
            e1 = Engine(model, params, slots=1, max_len=64)
            r = Request(prompt=p.copy(), max_new_tokens=4)
            e1.generate([r])
            alone.append(r.out_tokens.tolist())
        eng = Engine(model, params, slots=2, max_len=64)
        reqs = [Request(prompt=p.copy(), max_new_tokens=4) for p in prompts]
        eng.generate(reqs)
        assert [r.out_tokens.tolist() for r in reqs] == alone, arch


# ---------------------------------------------------------------------------
# one decode step in flight
# ---------------------------------------------------------------------------

#: arch, config overrides, slots, prompt lengths, max_new_tokens, the index
#: of each request's own token that is its eos_token (None: no eos), and how
#: many requests arrive last, while a decode step is in flight
PIPELINE_CASES = {
    "eos": ("granite-3-8b", {}, 2, (5, 17, 9), (8, 6, 7), (2, None, 3), 0),
    "max_new_1": ("granite-3-8b", {}, 2, (5, 12, 9, 4), (1, 5, 1, 3),
                  (None,) * 4, 0),
    "admit_in_flight": ("granite-3-8b", {}, 3, (6, 11, 8), (7, 6, 4),
                        (None, 4, None), 1),
    "rwkv": ("rwkv6-1.6b", {}, 1, (3, 6), (6, 4), (1, None), 0),
    "hybrid": ("recurrentgemma-9b", {}, 2, (3, 6, 4), (5, 4, 3),
               (1, None, None), 1),
    "int8_kv": ("granite-3-8b", {"kv_cache_dtype": "int8"}, 1, (4, 11),
                (6, 4), (2, None), 0),
}


@pytest.mark.parametrize("case", sorted(PIPELINE_CASES))
def test_pipelined_engine_matches_single_request_oracle(case):
    """With one decode step in flight, every request still gets the tokens
    it gets alone: mixed lengths, EOS part-way (the row a slot runs past
    its unread EOS is dropped, and the next occupant's fresh prefill wipes
    what it wrote), ``max_new_tokens=1``, an admission while a step is in
    flight, recurrent state and the int8 KV cache."""
    arch, over, slots, lens, news, eos_at, late = PIPELINE_CASES[case]
    cfg, model, params = _setup(arch, **over)
    prompts = _prompts(cfg, lens, seed=30)
    want, eos = [], []
    for p, m, k in zip(prompts, news, eos_at):
        r = Request(prompt=p.copy(), max_new_tokens=m)
        Engine(model, params, slots=1, max_len=64).generate([r])
        full = r.out_tokens.tolist()
        eos.append(None if k is None else full[k])
        want.append(full if k is None else full[:full.index(full[k]) + 1])
    eng = Engine(model, params, slots=slots, max_len=64)
    reqs = [Request(prompt=p.copy(), max_new_tokens=m, eos_token=e)
            for p, m, e in zip(prompts, news, eos)]
    early = len(reqs) - late
    for r in reqs[:early]:
        eng.submit(r)
    if late:
        eng.step()
        eng.step()
        assert eng._flight is not None
        assert any(s is None for s in eng._slots)
        for r in reqs[early:]:
            eng.submit(r)
    eng.run()
    assert [r.out_tokens.tolist() for r in reqs] == want
    assert eng._flight is None and eng.completed == len(reqs)
    c = eng.counters()
    assert c["decode_rows"] == sum(len(w) - 1 for w in want)
    assert c["decode_steps_ahead"] < c["decode_steps"]


def _booked(eng, req) -> int:
    """Tokens the engine has booked for ``req``: on the host, in order."""
    slot = next((s for s in eng._slots
                 if s is not None and s.request is req), None)
    return len(slot.out) if slot is not None else len(req.out_tokens)


def test_each_step_books_one_token_per_live_request():
    """The benchmark's contract: driven as the benchmark drives it (one
    request handed over for each free slot, then ``step``), every call
    books exactly one new token for each request that was live at the
    call, the first call after an admission included.  A step follows
    the one before on the device except after a retirement, which the
    host sees coming and dispatches nothing after, and at the start."""
    cfg, model, params = _setup()
    eng = Engine(model, params, slots=3, max_len=64, prefill_buckets=(16, 8))
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in zip(
        _prompts(cfg, (5, 9, 13, 4, 20, 7, 11), seed=31),
        (6, 2, 9, 4, 3, 7, 5))]
    queue, live, calls, behind, retired = list(reqs), [], 0, 0, True
    while queue or live:
        while queue and len(live) < eng.slots:
            live.append(queue.pop(0))
            eng.submit(live[-1])
            assert eng.admit_pending() == 1
        before = [_booked(eng, r) for r in live]
        behind += retired
        assert eng.step() == len(live)
        calls += 1
        assert [_booked(eng, r) for r in live] == [b + 1 for b in before]
        retired = any(r.out_tokens is not None for r in live)
        live = [r for r in live if r.out_tokens is None]
    c = eng.counters()
    assert c["decode_steps"] == calls
    assert c["decode_steps_ahead"] == calls - behind > 0
    assert c["decode_rows_discarded"] == 0
    assert c["decode_rows"] == sum(r.max_new_tokens - 1 for r in reqs)


def test_rows_past_an_unread_eos_are_counted_and_dropped():
    """The first request's EOS comes back while the step after it is in
    flight: that row is dropped, and the second request, admitted into the
    same slot, serves as it does alone."""
    cfg, model, params = _setup()
    p1, p2 = _prompts(cfg, (7, 10), seed=32)
    alone = []
    for p, m in ((p1, 8), (p2, 3)):
        r = Request(prompt=p.copy(), max_new_tokens=m)
        Engine(model, params, slots=1, max_len=64,
               prefill_buckets=(8,)).generate([r])
        alone.append(r.out_tokens.tolist())
    eos = alone[0][2]
    assert alone[0].index(eos) == 2
    eng = Engine(model, params, slots=1, max_len=64, prefill_buckets=(8,))
    reqs = [Request(prompt=p1.copy(), max_new_tokens=8, eos_token=eos),
            Request(prompt=p2.copy(), max_new_tokens=3)]
    eng.generate(reqs)
    assert [r.out_tokens.tolist() for r in reqs] == [alone[0][:3], alone[1]]
    c = eng.counters()
    # steps 1-2 bring the first request's two decoded tokens, step 3 runs
    # past its EOS; steps 4-5 serve the second: only step 1 found nothing
    # in flight
    assert (c["decode_steps"], c["decode_steps_ahead"], c["decode_rows"],
            c["decode_rows_discarded"]) == (5, 4, 4, 1)


def test_moe_counts_book_every_program_run_once():
    """On a tiny MoE engine the ``moe_*`` counters are the sums of what
    each program run returned, served through the pipeline with slot
    churn, an EOS and a dropped row; and each request's tokens are those
    it gets served alone."""
    from repro.models.moe import COUNTERS as MOE
    cfg, model, params = _setup("moonlight-16b-a3b", experts_held=2)
    prompts = _prompts(cfg, (5, 19, 9), seed=33)
    alone = []
    for p in prompts:
        r = Request(prompt=p.copy(), max_new_tokens=5)
        Engine(model, params, slots=1, max_len=64,
               prefill_buckets=(16, 8)).generate([r])
        alone.append(r.out_tokens.tolist())
    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8))
    ran = []

    def record(name, tokens):
        program = getattr(eng, name)

        def run(*args, **kw):
            out, cache = program(*args, **kw)
            ran.append(np.asarray(out)[tokens:])
            return out, cache
        setattr(eng, name, run)

    record("_decode", eng.slots)
    record("_prefill", 1)
    eos = alone[0][1]
    reqs = [Request(prompt=p.copy(), max_new_tokens=5) for p in prompts]
    reqs[0].eos_token = eos
    eng.generate(reqs)
    stop = alone[0].index(eos) + 1
    assert [r.out_tokens.tolist() for r in reqs] == [alone[0][:stop],
                                                     *alone[1:]]
    c = eng.counters()
    assert c["decode_steps"] + c["prefill_chunks"] == len(ran)
    assert [c[k] for k in MOE] == np.sum(ran, axis=0).tolist()
    assert c["decode_rows_discarded"] >= 1 and c["moe_rows_routed"] > 0


# ---------------------------------------------------------------------------
# admission validation (cache-overflow regression)
# ---------------------------------------------------------------------------


def test_overlong_prompt_rejected_not_clamped():
    """Prompt (or prompt + budget) exceeding max_len must raise — the old
    server let dynamic_update_slice clamp the write index, silently
    corrupting the cache tail."""
    cfg, model, params = _setup()
    eng = Engine(model, params, slots=1, max_len=32, prefill_buckets=(16, 8))
    rng = np.random.default_rng(6)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 40,
                                               dtype=np.int32)))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 30,
                                               dtype=np.int32),
                           max_new_tokens=8))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(prompt=np.zeros((0,), np.int32)))
    # a fitting request on the same engine still serves fine
    ok = Request(prompt=rng.integers(0, cfg.vocab, 24, dtype=np.int32),
                 max_new_tokens=8)
    eng.generate([ok])
    assert ok.out_tokens.shape == (8,)


def test_server_backcompat_surface():
    """The old Server constructor keywords and generate() contract hold."""
    cfg, model, params = _setup()
    srv = Server(model, params, batch_slots=3, max_len=64,
                 prefill_buckets=(16, 8))
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in _prompts(cfg, (4, 9, 13, 6), seed=7)]
    out = srv.generate(reqs)
    assert out is reqs
    assert all(r.out_tokens.shape == (4,) for r in reqs)


def test_enc_dec_rejected():
    cfg = reduced_config(REGISTRY["whisper-tiny"])
    model = build_model(cfg)
    params = model.init(KEY)
    with pytest.raises(NotImplementedError):
        Engine(model, params)


# ---------------------------------------------------------------------------
# quantized serving (int8/fp8 weight decode through the engine)
# ---------------------------------------------------------------------------


def _sparse_setup(**over):
    return _setup("granite-3-8b", ffn_block_sparse=True, ffn_block=32,
                  ffn_density=0.5, **over)


def test_quantized_engine_matches_single_request_oracle():
    """The mixed-length oracle holds *within* each quantized engine: a
    batched run with slot churn is token-for-token identical to serving
    each request alone on the same quantized weights."""
    cfg, model, params = _sparse_setup()
    prompts = _prompts(cfg, (4, 17, 9), seed=11)
    for mode in ("int8", "fp8"):
        alone = []
        for p in prompts:
            e1 = Engine(model, params, slots=1, max_len=64,
                        prefill_buckets=(16, 8), quantize=mode)
            r = Request(prompt=p.copy(), max_new_tokens=5)
            e1.generate([r])
            alone.append(r.out_tokens.tolist())
        eng = Engine(model, params, slots=2, max_len=64,
                     prefill_buckets=(16, 8), quantize=mode)
        reqs = [Request(prompt=p.copy(), max_new_tokens=5) for p in prompts]
        eng.generate(reqs)
        assert [r.out_tokens.tolist() for r in reqs] == alone, mode


def test_quantized_greedy_drift_bounded():
    """fp32 vs int8 vs fp8 engines on the same mixed-length batch: greedy
    tokens may drift where logits are near-ties, but the drift fraction
    stays small (int8 tighter than fp8)."""
    cfg, model, params = _sparse_setup()
    prompts = _prompts(cfg, (4, 17, 9, 25, 6), seed=12)

    def serve(mode):
        eng = Engine(model, params, slots=2, max_len=64,
                     prefill_buckets=(16, 8), quantize=mode)
        reqs = [Request(prompt=p.copy(), max_new_tokens=6) for p in prompts]
        eng.generate(reqs)
        return [r.out_tokens.tolist() for r in reqs]

    base = serve(None)
    total = sum(len(t) for t in base)
    for mode, bound in (("int8", 0.25), ("int8.rowwise", 0.25),
                        ("fp8", 0.5)):
        out = serve(mode)
        drift = sum(a != b for x, y in zip(base, out) for a, b in zip(x, y))
        assert drift / total <= bound, (mode, drift, total)


def test_quantized_engine_no_retrace():
    """Quantized params keep the engine's retrace-flatness contract: one
    decode trace + the same prefill trace count as the fp32 engine, flat
    across later waves of new lengths/budgets."""
    cfg, model, params = _sparse_setup()

    def warm_counts(mode):
        eng = Engine(model, params, slots=2, max_len=64,
                     prefill_buckets=(16,), quantize=mode)
        eng.generate([Request(prompt=p, max_new_tokens=3)
                      for p in _prompts(cfg, (5, 20), seed=13)])
        return eng, dict(eng.compiled_shapes)

    _, fp32_warm = warm_counts(None)
    eng, warm = warm_counts("int8")
    assert warm["decode"] == 1
    assert warm == fp32_warm
    eng.generate([Request(prompt=p, max_new_tokens=m)
                  for p, m in zip(_prompts(cfg, (3, 21, 13, 30), seed=14),
                                  (2, 5, 1, 3))])
    assert eng.compiled_shapes == warm


def test_quantized_engine_composes_with_int8_kv_cache():
    """int8 weights + int8 KV cache serve together; the bucket cap and the
    single-request oracle both hold."""
    cfg, model, params = _sparse_setup(kv_cache_dtype="int8")
    prompts = _prompts(cfg, (4, 17), seed=15)
    alone = []
    for p in prompts:
        e1 = Engine(model, params, slots=1, max_len=64, quantize="int8")
        r = Request(prompt=p.copy(), max_new_tokens=4)
        e1.generate([r])
        alone.append(r.out_tokens.tolist())
    eng = Engine(model, params, slots=2, max_len=64, quantize="int8")
    assert max(eng.prefill_buckets) <= 8
    reqs = [Request(prompt=p.copy(), max_new_tokens=4) for p in prompts]
    eng.generate(reqs)
    assert [r.out_tokens.tolist() for r in reqs] == alone


def test_engine_quantize_requires_sparse_ffn():
    cfg, model, params = _setup()   # dense SwiGLU FFN
    with pytest.raises(ValueError, match="block-sparse"):
        Engine(model, params, quantize="int8")


def test_int8_kv_long_query_raises_named_error():
    """The decode-size guard on the int8 KV path is a ValueError, not a
    bare assert (serving stacks run under ``python -O``)."""
    import jax.numpy as jnp
    cfg, model, params = _setup(kv_cache_dtype="int8")
    cache = model.init_cache(1, 64)
    with pytest.raises(ValueError, match="decode-sized"):
        model.decode_step(params, cache, jnp.zeros((1, 16), jnp.int32),
                          jnp.int32(0))


# ---------------------------------------------------------------------------
# the engine's weights in the compute dtype
# ---------------------------------------------------------------------------

#: decoder families the engine serves (enc_dec is refused above)
DECODERS = [a for a in REGISTRY if REGISTRY[a].family != "enc_dec"]

#: keys of the leaves the serving programs read as stored (norm scales,
#: router, bias, recurrent gate parameters, Segment blocks and scales)
STORED = {"scale", "router", "score_bias", "a_param", "conv", "mix",
          "cm_mix", "w_bias", "w_lora_a", "w_lora_b", "u", "blocks",
          "scales"}


def _stays_as_stored(path) -> bool:
    keys = [str(getattr(k, "key", k)) for k in path]
    # the held experts' layer-stacked gate/up/down, which the grouped GEMM
    # reads where they lie
    return bool(STORED & set(keys)) or (len(keys) > 1 and keys[-2] == "moe")


def _direct_greedy(model, params, prompt, chunk, new, cache_len):
    """Greedy tokens and the logits of each program, straight through
    ``decode_step`` as the engine runs it for one slot: the prompt in
    chunks of ``chunk`` at a shared position, then one token at a time at
    a per-row position."""
    step = jax.jit(model.decode_step)
    cache = model.init_cache(1, cache_len)
    for i in range(0, prompt.size, chunk):
        logits, cache = step(params, cache, prompt[None, i:i + chunk],
                             np.int32(i))
    toks, inputs = [int(np.argmax(logits[0]))], []
    for j in range(new - 1):
        inputs.append((cache, np.asarray([[toks[-1]]], np.int32),
                       np.asarray([prompt.size + j], np.int32)))
        logits, cache = step(params, *inputs[-1])
        toks.append(int(np.argmax(logits[0])))
    return toks, step, inputs[-1]


@pytest.mark.parametrize("arch", DECODERS)
def test_engine_serves_compute_dtype_weights_as_the_float32_tree(arch):
    """An engine built on float32 weights holds each weight its programs
    read only in the compute dtype (bf16 here) cast to it, once: its
    greedy tokens equal a direct run of the model on the float32 tree,
    the last step's logits from the held tree equal those from the float32
    tree, and every leaf read as stored keeps its float32."""
    cfg = reduced_config(REGISTRY[arch])
    assert cfg.dtype == "bfloat16"
    model = build_model(cfg)
    params = model.init(KEY)
    prompt = _prompts(cfg, (8,), seed=21)[0]
    eng = Engine(model, params, slots=1, max_len=32, prefill_buckets=(8,))
    r = Request(prompt=prompt.copy(), max_new_tokens=4)
    eng.generate([r])

    toks, step, last = _direct_greedy(model, params, prompt,
                                      eng.prefill_buckets[0], 4,
                                      eng._cache_len)
    np.testing.assert_array_equal(r.out_tokens, toks)
    want, _ = step(params, *last)
    got, _ = step(eng.params, *last)
    gap = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32))))
    print(arch, "largest logit difference", gap)
    assert gap <= 1e-6, gap

    held = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    given = jax.tree.leaves(params)
    want_dtypes = [np.float32 if _stays_as_stored(path) else jnp.bfloat16
                   for path, _ in held]
    assert [a.dtype for _, a in held] == want_dtypes, [
        (jax.tree_util.keystr(p), a.dtype) for p, a in held]
    assert all(a.dtype == np.float32 for a in given)
    c = eng.counters()
    assert c["weights_cast_bytes"] == sum(
        a.nbytes for _, a in held if a.dtype == jnp.bfloat16) > 0
    assert c["weights_kept_bytes"] == sum(
        a.nbytes for _, a in held if a.dtype == np.float32) > 0


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_engine_holds_compute_dtype_weights(mode):
    """A quantizing engine at bf16 compute casts the dense weights after it
    quantizes the Segment FFN: the payloads and their float32 scales stay
    as quantized, and it serves."""
    cfg, model, params = _sparse_setup(dtype="bfloat16")
    eng = Engine(model, params, slots=2, max_len=64, prefill_buckets=(16, 8),
                 quantize=mode)
    reqs = [Request(prompt=p, max_new_tokens=3)
            for p in _prompts(cfg, (5, 19), seed=22)]
    eng.generate(reqs)
    assert all(r.out_tokens.shape == (3,) for r in reqs)
    mlp = eng.params["layers"]["mlp"]["up"]
    assert mlp["blocks"].dtype == {"int8": np.int8,
                                   "fp8": jnp.float8_e4m3fn}[mode]
    assert mlp["scales"].dtype == np.float32
    assert eng.params["layers"]["attn"]["wq"]["w"].dtype == jnp.bfloat16
    assert eng.params["embed"]["table"].dtype == jnp.bfloat16


def test_float32_engine_holds_the_tree_as_given():
    cfg, model, params = _setup()
    eng = Engine(model, params, slots=1, max_len=64)
    assert eng.params is params
    c = eng.counters()
    assert c["weights_cast_bytes"] == 0
    assert c["weights_kept_bytes"] == sum(a.nbytes
                                          for a in jax.tree.leaves(params))
