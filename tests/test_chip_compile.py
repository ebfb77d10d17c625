"""Compile rehearsal: the Segment kernels as ``chip_smoke.py`` runs them,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see Mosaic's tiling and layout rules; this file does,
at the real widths, with no chip.  The topology is described inside a
module-scoped fixture (never at import), so only the test worker that runs
this file loads the TPU compiler.  Plus CPU checks of the compiled
backend's N-tile alignment and block-shape refusal, and that importing the
package starts no JAX backend.
"""
from __future__ import annotations

import importlib.util
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.core.formats import BSR

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


SMOKE = _chip_smoke()


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _struct(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("case", SMOKE.KERNEL_CASES, ids=lambda c: c.name)
def test_smoke_kernel_compiles_for_v5e(case, one_chip):
    a, rhs = SMOKE.kernel_operands(case)
    plan = SMOKE.kernel_plan(case, a, rhs)
    assert plan.backend == "pallas"
    args = [_struct(plan, one_chip)]
    if case.n:
        args.append(jax.ShapeDtypeStruct(rhs.shape, case.rhs_dtype,
                                         sharding=one_chip))
    compiled = jax.jit(api.execute_plan).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's custom call, and so its profiler events, carry the
    # variant's own name
    assert set(re.findall(r"%(segment_\w+?)(?:\.\d+)? = [^\n]*custom-call",
                          text)) == {_kernel_name(case)}


def _kernel_name(case) -> str:
    """The ``pallas_call`` name the case's variant is given."""
    name = "segment_spmm_pipeline" if case.n else "segment_spgemm_pipeline"
    if case.quantize is not None:
        mode = "rowwise" if case.quantize.endswith(".rowwise") else "block"
        # an SpGEMM plan quantizes both of its operands
        name += "_qa" + mode + ("" if case.n else "_qb" + mode)
    if case.prefetch == "cross_pass":
        name += "_xpass"
    return name


#: the served configuration whose decode program the rehearsal compiles
SERVED = ROOT / "bench" / "configs" / "phi3-mini-3.8b-bsffn.json"

#: an HLO instruction: name, the shape of what it outputs, its opcode
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", re.M)
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}


@pytest.fixture(scope="module")
def phi3_decode(one_chip):
    """``Engine``'s decode program at the served phi3-mini configuration
    (32 layers, 7 slots, max_len 1024, block-sparse FFN on the Segment
    kernels), built from shapes alone and compiled for a described v5e
    over the weights as the engine holds them, the cache donated."""
    import json

    from repro.launch.serve import serving_config
    from repro.models import build_model
    from repro.runtime.serve import Engine

    served = json.loads(SERVED.read_text())
    cfg = serving_config(served["registry"], sparse_ffn=True,
                         ffn_block=served["ffn_block"],
                         ffn_density=served["ffn_density"])
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff,
            cfg.vocab) == (
        served["num_hidden_layers"], served["hidden_size"],
        served["num_attention_heads"], served["num_key_value_heads"],
        served["intermediate_size"], served["vocab_size"])
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    init_cache = model.init_cache
    model.init_cache = lambda b, t: jax.eval_shape(lambda: init_cache(b, t))
    engine = Engine(model, params, slots=served["slots"],
                    max_len=served["max_len"], backend="pallas")
    slots = served["slots"]
    compiled = engine._decode.lower(
        _struct(engine.params, one_chip), _struct(engine.cache, one_chip),
        jax.ShapeDtypeStruct((slots, 1), np.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), np.int32, sharding=one_chip)).compile()
    return cfg, served, engine, compiled


#: a ``convert`` that reads a parameter (of the program or of a fusion):
#: name, the dtype and shape it outputs
_CONVERT_PARAM = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                            r"\S* convert\(%?param", re.M)


def _converted(text, shapes):
    """The program's ``convert`` ops to bf16 that read a stored array of
    one of ``shapes``: a weight cast to the compute dtype in the program
    (a fusion's float32 arithmetic on a bf16 operand is no such cast)."""
    return [(name, dtype, dims) for name, dtype, dims in
            _CONVERT_PARAM.findall(text) if dtype == "bf16"
            and tuple(int(d) for d in dims.split(",") if d) in shapes]


def test_engine_decode_updates_cache_in_place(phi3_decode):
    """The phi3-mini cell's decode program: the cache rides in the layer
    scan's carry, so no copy, dynamic slice or dynamic update outputs an
    array as large as one stacked K or V cache, and the temporaries stay
    below one stacked K+V cache."""
    cfg, served, engine, compiled = phi3_decode
    slots = served["slots"]
    k = engine.cache["layers"]["kv"]["k"]
    k_bytes = k.size * k.dtype.itemsize
    moved = []
    for name, dtype, dims, opcode in _HLO_OP.findall(compiled.as_text()):
        kind = name + " " + opcode
        if not any(w in kind for w in ("copy", "dynamic-slice",
                                       "dynamic-update-slice")):
            continue
        size = _BYTES[dtype] * int(np.prod([int(d) for d in dims.split(",")
                                            if d]))
        if size >= k_bytes:
            moved.append((name, dtype, dims))
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * k_bytes, mem.temp_size_in_bytes
    # the donated cache is the output cache
    assert mem.alias_size_in_bytes >= 2 * k_bytes, mem.alias_size_in_bytes
    # one layout for the token write and the attention read
    assert k.shape == (cfg.n_layers, slots, served["max_len"],
                       cfg.n_kv * cfg.hd)


def test_engine_decode_reads_weights_as_held(phi3_decode):
    """The phi3-mini cell's decode program reads the attention projections
    and the head in bf16 as the engine holds them: nothing converts a
    projection stack, one layer of it or the head table, and the program
    fits one v5e."""
    _, _, engine, compiled = phi3_decode
    attn = engine.params["layers"]["attn"]
    stacks = {attn[k]["w"].shape for k in ("wq", "wk", "wv", "wo")}
    head = engine.params.get("lm_head", engine.params["embed"])["table"]
    assert {attn[k]["w"].dtype for k in attn} == {head.dtype} \
        == {np.dtype(jax.numpy.bfloat16)}
    shapes = stacks | {s[1:] for s in stacks} | {head.shape}
    assert not _converted(compiled.as_text(), shapes)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print("decode argument/output/alias/temp bytes",
          mem.argument_size_in_bytes, mem.output_size_in_bytes,
          mem.alias_size_in_bytes, mem.temp_size_in_bytes)
    assert total < V5E_HBM, total


#: the expert-share configuration of the ``moonlight-ep8.offline`` cell
MOONLIGHT = ROOT / "bench" / "configs" / "moonlight-16b-a3b-ep8.json"

#: what one v5e chip's program may use (the compiler's own figure)
V5E_HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def moonlight_engine():
    """``Engine`` at the cell's configuration (13 layers, 8 of 64 experts
    held, 64 slots, max_len 1024, float32 weights), from shapes alone."""
    import dataclasses
    import json

    from repro.launch.serve import serving_config
    from repro.models import build_model
    from repro.runtime.serve import Engine

    served = json.loads(MOONLIGHT.read_text())
    cfg = dataclasses.replace(serving_config(served["registry"]),
                              n_layers=served["num_hidden_layers"],
                              experts_held=served["n_routed_experts"])
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.n_experts, cfg.top_k, cfg.moe_d_ff, cfg.n_shared_experts,
            cfg.first_k_dense, cfg.moe_route_scale) == (
        served["hidden_size"], served["num_attention_heads"],
        served["intermediate_size"], served["vocab_size"],
        served["kv_lora_rank"], served["qk_nope_head_dim"],
        served["qk_rope_head_dim"], served["v_head_dim"],
        served["published"]["n_routed_experts"],
        served["num_experts_per_tok"], served["moe_intermediate_size"],
        served["n_shared_experts"], served["first_k_dense_replace"],
        served["routed_scaling_factor"])
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    init_cache = model.init_cache
    model.init_cache = lambda b, t: jax.eval_shape(lambda: init_cache(b, t))
    engine = Engine(model, params, slots=served["slots"],
                    max_len=served["max_len"], backend="pallas")
    return engine, params, served


@pytest.mark.parametrize("program", ["decode", "prefill-64", "prefill-16"])
def test_moonlight_cell_compiles_for_v5e(program, moonlight_engine, one_chip):
    """The cell's decode program and both prefill buckets compile for a
    described v5e beside the weights as the engine holds them and the
    latent cache; the held experts run the grouped GEMM kernel in bf16 over
    their float32 stacks, and no other weight is converted; decode copies no
    whole latent cache and reads it as stored (no per-head K or V of the
    cache is made)."""
    engine, params, served = moonlight_engine
    slots, i32 = served["slots"], np.int32
    args = [_struct(engine.params, one_chip),
            _struct(engine.cache, one_chip)]
    if program == "decode":
        compiled = engine._decode.lower(
            *args, jax.ShapeDtypeStruct((slots, 1), i32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), i32, sharding=one_chip)).compile()
    else:
        c = int(program.split("-")[1])
        scalar = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
        compiled = engine._prefill.lower(
            *args, scalar, jax.ShapeDtypeStruct((1, c), i32,
                                                sharding=one_chip),
            scalar, jax.ShapeDtypeStruct((1,), i32, sharding=one_chip),
            fresh=True).compile()
    text = compiled.as_text()
    # every grouped GEMM reads its float32 layer-stacked parameter as
    # stored, and nothing converts, copies or slices the expert stack
    moe = params["layers"]["moe"]
    stacks = {k: moe[k].shape for k in ("gate", "up", "down")}
    weights = re.findall(r"%moe_gemm_bfloat16(?:\.\d+)? = [^\n]*custom-call"
                         r"[^\n]*operand_layout_constraints=\{[^\n]*?"
                         r"(\w+)\[([\d,]+)\]\{[\d,]*\}\}", text)
    assert len(weights) == 3, weights      # gate, up, down in the scan
    assert {(dt, tuple(int(d) for d in dims.split(",")))
            for dt, dims in weights} == {("f32", s) for s in stacks.values()}
    shapes = set(stacks.values()) | {s[1:] for s in stacks.values()}
    touched = [(name, dtype, dims, opcode) for name, dtype, dims, opcode in
               _HLO_OP.findall(text)
               if tuple(int(d) for d in dims.split(",") if d) in shapes
               and (dtype != "f32" or opcode not in
                    ("parameter", "get-tuple-element"))]
    assert not touched, touched
    # nothing converts a weight the engine holds in bf16 (MLA, the dense
    # layer, the shared experts, the head), whole or one layer of it
    held = [a.shape for a in jax.tree.leaves(engine.params)
            if a.dtype == jax.numpy.bfloat16]
    assert len(held) > 10, held
    assert not _converted(text, set(held) | {s[1:] for s in held
                                             if len(s) == 3})
    # one 576-wide latent a token and layer, padded to whole lanes
    latent = engine.cache["layers"]["kv"]["latent"]
    assert latent.shape == (served["num_hidden_layers"] - 1, slots,
                            served["max_len"], 640)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(program, "argument/output/alias/temp bytes",
          mem.argument_size_in_bytes, mem.output_size_in_bytes,
          mem.alias_size_in_bytes, mem.temp_size_in_bytes)
    assert total < V5E_HBM, total
    if program == "decode":
        # the cache rides in the scans in place, in the layout it is
        # stored in (prefill writes its slot's row back in place)
        stack = latent.size * latent.dtype.itemsize
        moved = [(name, dims) for name, dtype, dims, opcode in
                 _HLO_OP.findall(text)
                 if any(w in name + " " + opcode for w in
                        ("copy", "dynamic-slice", "dynamic-update-slice"))
                 and _BYTES[dtype] * np.prod([int(d) for d in dims.split(",")
                                              if d]) >= stack]
        assert not moved, moved
        # an expanded cache would be (slots, max_len, heads, >= 128)
        t_max, heads = served["max_len"], served["num_attention_heads"]
        expanded = []
        for name, _, dims, _ in _HLO_OP.findall(text):
            d = [int(x) for x in dims.split(",") if x]
            if {t_max, heads} <= set(d) and np.prod(d) >= \
                    slots * t_max * heads * served["v_head_dim"]:
                expanded.append((name, dims))
        assert not expanded, expanded


# ---------------------------------------------------------------------------
# CPU: what the compiled backend needs from the executor and the planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,bn", [(8, 512), (512, 512), (600, 512),
                                  (1024, 512), (100, 128), (4, 256),
                                  (384, 256), (2000, 1000)])
def test_pick_bn_on_pallas_is_lane_aligned(n, bn):
    bn_eff, pad = api.pick_bn(n, bn, align=api.LANE)
    assert bn_eff % api.LANE == 0 and bn_eff >= api.LANE
    assert (n + pad) % bn_eff == 0 and 0 <= pad
    assert bn_eff <= max(bn, api.LANE)
    assert (n + pad) - n < bn_eff + api.LANE   # never a whole spare tile


def test_pick_bn_decode_width_pads_to_one_tile():
    assert api.pick_bn(8, 512, align=api.LANE) == (128, 120)
    assert api.pick_bn(512, 512, align=api.LANE) == (512, 0)


def test_sub_lane_blocks_refused_on_pallas():
    rng = np.random.default_rng(0)
    a = BSR.random(rng, (256, 256), (64, 64), 0.5)
    with pytest.raises(api.BlockShapeError, match="multiple of 128"):
        api.plan_matmul(a, (256, 8), backend="pallas")
    plan = api.plan_matmul(a, (256, 8))          # fine off the chip ...
    x = np.ones((256, 8), np.float32)
    with pytest.raises(api.BlockShapeError):     # ... until run compiled
        api.execute_plan(plan, x, backend="pallas")
    got = api.execute_plan(plan, x, backend="interpret")
    np.testing.assert_allclose(got, a.to_dense() @ x, rtol=1e-5, atol=1e-4)


def test_importing_the_package_starts_no_backend():
    code = ("import repro.api, repro.models, repro.runtime, repro.kernels\n"
            "import repro.launch.serve, repro.launch.train\n"
            "import jax._src.xla_bridge as xb\n"
            "assert not xb._backends, list(xb._backends)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr


def test_compile_cache_dir(monkeypatch):
    from repro.launch.cache import DEFAULT_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
