"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware: for the
16×16 single-pod mesh and the 2×16×16 multi-pod mesh, the train / prefill /
decode step of every assigned architecture must ``.lower().compile()``
under the production shardings, fit per-device memory, and yield the
cost/collective numbers the roofline analysis (§Roofline) consumes.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all          # every live cell, subprocesses
Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json

``main`` fakes 512 host devices through ``XLA_FLAGS`` before JAX starts a
backend; importing the module changes nothing.
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import REGISTRY, SHAPES, cell_is_live, get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, cache_specs, input_specs
from repro.optim import AdamW, constant
from repro.roofline.analysis import (collective_bytes, model_flops,
                                     roofline_terms)
from repro.runtime.train_loop import make_train_step
from repro.sharding import (batch_pspecs, cache_pspec, dp_axes,
                            make_shardings, params_pspecs)

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

# grad-accumulation microbatching per arch (memory fitting, DESIGN.md §5);
# values verified against compiled memory_analysis.
TRAIN_ACCUM = {
    "internvl2-2b": 2, "whisper-tiny": 1, "phi3-mini-3.8b": 4,
    "qwen1.5-4b": 4, "granite-3-8b": 8, "command-r-plus-104b": 16,
    "recurrentgemma-9b": 4, "llama4-maverick-400b-a17b": 16,
    "phi3.5-moe-42b-a6.6b": 8, "rwkv6-1.6b": 2,
}
# low-memory (bf16) optimizer state for the largest models
BF16_OPT = {"command-r-plus-104b", "llama4-maverick-400b-a17b"}
BF16_ACCUM = {"llama4-maverick-400b-a17b"}
# cross-pod ZeRO-3 for state-dominated giants (DCN all-gathers amortized by
# the grad-accumulation microbatch loop)
CROSS_POD_FSDP = {"llama4-maverick-400b-a17b"}
# cells whose *state alone* exceeds the mesh's HBM: the dry-run proves the
# infeasibility (that is its job); compile must still succeed. llama4 400B
# AdamW state = 400e9·(4+2+2)B / 256 chips = 12.5 GiB/chip before a single
# activation — training this architecture requires the 512-chip multi-pod
# mesh (which fits).
EXPECTED_OVER_HBM = {
    ("llama4-maverick-400b-a17b", "train_4k", "pod_16x16"),
    ("llama4-maverick-400b-a17b", "train_4k", "multipod_2x16x16"),
}  # 397B AdamW state needs ≥4 pods; the 4-pod sizing run
   # (multipod_4x16x16 artifact) shows 16.79 GiB/chip — see EXPERIMENTS.md
# per-arch model overrides for the production cells
CELL_OVERRIDES = {
    "command-r-plus-104b": {"seq_shard": True},
}


def _mesh_tag(multi_pod: bool) -> str:
    return "multipod_2x16x16" if multi_pod else "pod_16x16"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = ART_DIR, overrides: Optional[dict] = None,
             serve_params_dtype=None, fsdp_override=None,
             accum_override: Optional[int] = None,
             tag: str = "") -> dict:
    """Lower + compile one cell. Hillclimb levers: ``serve_params_dtype``
    (bf16 serving checkpoints), ``fsdp_override`` (None axis = TP-only
    serving layout), ``accum_override``, plus any ModelConfig overrides."""
    cfg = get_config(arch)
    merged = dict(CELL_OVERRIDES.get(arch, {}))
    # sequence-parallel activations only pay off under training remat
    # (§Perf cell A: SP at prefill costs +67% collective for nothing)
    if SHAPES[shape_name].kind != "train":
        merged.setdefault("seq_shard", False)
        merged["seq_shard"] = merged.get("seq_shard", False) and False
    if overrides:
        merged.update(overrides)
    if merged:
        import dataclasses
        cfg = dataclasses.replace(cfg, **merged)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    t0 = time.time()

    abstract_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if serve_params_dtype is not None and shape.kind != "train":
        abstract_params = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, serve_params_dtype),
            abstract_params)
    fsdp = ("data", "pod") if arch in CROSS_POD_FSDP else "data"
    if fsdp_override is not None:
        fsdp = fsdp_override
    pspecs = params_pspecs(abstract_params, fsdp=fsdp)
    param_sh = make_shardings(mesh, pspecs, abstract_params)
    specs = input_specs(cfg, shape)

    accum = 1
    if shape.kind == "train":
        accum = accum_override or TRAIN_ACCUM.get(arch, 4)
        # microbatch must stay divisible by the total dp degree
        dp_total = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp_total *= mesh.shape[ax]
        while accum > 1 and (shape.global_batch // accum) % dp_total != 0:
            accum //= 2
        opt = AdamW(lr=constant(3e-4),
                    state_dtype=jnp.bfloat16 if arch in BF16_OPT else jnp.float32)
        opt_abs = jax.eval_shape(opt.init, abstract_params)
        opt_sh = type(opt_abs)(step=NamedSharding(mesh, P()),
                               m=make_shardings(mesh, pspecs, opt_abs.m),
                               v=make_shardings(mesh, pspecs, opt_abs.v))
        step = make_train_step(
            model, opt, accum, mesh=mesh,
            accum_dtype=jnp.bfloat16 if arch in BF16_ACCUM else jnp.float32,
            fsdp=fsdp)
        batch_sh = make_shardings(mesh, batch_pspecs(mesh, specs))
        metrics_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P()),
            {"loss": 0, "grad_norm": 0, "lr": 0})
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, donate_argnums=(0,),
                in_shardings=((param_sh, opt_sh), batch_sh),
                out_shardings=((param_sh, opt_sh), metrics_sh),
            ).lower((abstract_params, opt_abs), specs)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        def prefill(params, batch):
            logits, _ = model.forward(params, batch["tokens"],
                                      vis_embeds=batch.get("vis_embeds"),
                                      enc_embeds=batch.get("enc_embeds"))
            return logits[:, -1].astype(jnp.float32)   # last-position logits
        batch_sh = make_shardings(mesh, batch_pspecs(mesh, specs))
        dp = dp_axes(mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                prefill, in_shardings=(param_sh, batch_sh),
                out_shardings=NamedSharding(mesh, P(dp, "model")),
            ).lower(abstract_params, specs)
            compiled = lowered.compile()
    else:  # decode
        from repro.sharding import sanitize_pspec
        cache_abs = cache_specs(cfg, shape)
        cache_sh = jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(mesh, sanitize_pspec(
                mesh, cache_pspec(mesh, path, leaf), leaf.shape)),
            cache_abs)
        dp = dp_axes(mesh)

        def decode(params, cache, token, pos):
            if cfg.family == "enc_dec":
                b = token.shape[0]
                enc = jnp.zeros((b, cfg.n_frontend_tokens, cfg.d_model),
                                jnp.bfloat16)
                return model.decode_step(params, cache, token, pos, enc_out=enc)
            return model.decode_step(params, cache, token, pos)

        tok_spec = sanitize_pspec(mesh, P(dp, None), specs["token"].shape)
        tok_sh = NamedSharding(mesh, tok_spec)
        pos_sh = NamedSharding(mesh, P())
        logits_sh = NamedSharding(mesh, sanitize_pspec(
            mesh, P(dp, "model"),
            (specs["token"].shape[0], cfg.padded_vocab)))
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                decode, donate_argnums=(1,),
                in_shardings=(param_sh, cache_sh, tok_sh, pos_sh),
                out_shardings=(logits_sh, cache_sh),
            ).lower(abstract_params, cache_abs, specs["token"], specs["pos"])
            compiled = lowered.compile()

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    # CPU-backend artifact accounting (decode cells): XLA's bf16-dot
    # emulation hoists f32 converts of the KV cache out of the layer scan
    # and carries full f32 cache copies in the while tuple. Native-bf16 TPUs
    # never materialize these; we detect f32 buffers exactly matching the
    # per-device bf16 cache shapes and report a TPU-corrected fit.
    cpu_artifact_bytes = 0
    if shape.kind == "decode":
        dp_size = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp_size *= mesh.shape[ax]
        tp = mesh.shape["model"]
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache_abs):
            if path[-1].key in ("k", "v") and leaf.dtype == jnp.bfloat16:
                d = list(leaf.shape)
                if d[1] % dp_size == 0:
                    d[1] //= dp_size
                if d[2] % tp == 0:
                    d[2] //= tp
                sig = "f32[" + ",".join(map(str, d)) + "]"
                if sig in hlo:
                    n_els = 1
                    for dd in d:
                        n_els *= dd
                    cpu_artifact_bytes += n_els * 4  # one live f32 copy/leaf
    chips = mesh.size
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_total = float(sum(coll.values()))
    terms = roofline_terms(flops_dev * chips, bytes_dev * chips,
                           coll_total * chips, chips)
    mf = model_flops(cfg, shape)
    # --- trip-count correction -------------------------------------------
    # XLA cost_analysis counts while-loop bodies ONCE (verified:
    # useful_flops_ratio >> 1). The layer scan runs n_layers times and the
    # grad-accumulation scan `accum` times, so HLO-counted terms are scaled
    # by M = n_layers × accum (kind-dependent). Inner scans (chunked
    # attention, SSM time scans) make corrected terms for hybrid/ssm cells
    # LOWER BOUNDS — noted per cell. The analytic compute term (6·N·D
    # MFU accounting) is exact and reported alongside.
    n_l = (cfg.enc_layers + cfg.dec_layers) if cfg.family == "enc_dec" \
        else cfg.n_layers
    m_trips = n_l * (accum if shape.kind == "train" else 1)
    terms_corr = roofline_terms(flops_dev * chips * m_trips,
                                bytes_dev * chips * m_trips,
                                coll_total * chips * m_trips, chips)
    from repro.roofline.analysis import PEAK_FLOPS
    compute_analytic_s = mf / (chips * PEAK_FLOPS)
    lower_bound = cfg.family in ("hybrid", "ssm")
    result = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
        "chips": chips, "ok": True, "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
            "fit_bytes": int(getattr(mem, "argument_size_in_bytes", 0))
            + int(getattr(mem, "temp_size_in_bytes", 0)),
            "cpu_artifact_bytes": int(cpu_artifact_bytes),
            "fit_bytes_tpu": int(getattr(mem, "argument_size_in_bytes", 0))
            + int(getattr(mem, "temp_size_in_bytes", 0))
            - int(cpu_artifact_bytes),
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev},
        "collectives_bytes_per_device": coll,
        "roofline": terms,
        "roofline_corrected": {**terms_corr, "m_trips": m_trips,
                               "compute_analytic_s": compute_analytic_s,
                               "inner_scan_lower_bound": lower_bound},
        "model_flops": mf,
        "useful_flops_ratio": (mf / (flops_dev * chips * m_trips)
                               if flops_dev > 0 else None),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{_mesh_tag(multi_pod)}{suffix}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch in REGISTRY:
            for shape in SHAPES:
                if not cell_is_live(arch, shape):
                    continue
                for mp in (False, True):
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape]
                    if mp:
                        cmd.append("--multi-pod")
                    t0 = time.time()
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    tag = f"{arch} × {shape} × {_mesh_tag(mp)}"
                    if r.returncode == 0:
                        print(f"PASS {tag} ({time.time()-t0:.0f}s)")
                    else:
                        print(f"FAIL {tag}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
                        failures.append(tag)
        if failures:
            print(f"\n{len(failures)} FAILURES:", *failures, sep="\n  ")
            sys.exit(1)
        print("\nALL DRY-RUN CELLS PASS")
        return

    assert args.arch and args.shape
    try:
        res = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    mem_gb = res["memory"]["fit_bytes_tpu"] / 2 ** 30
    raw_gb = res["memory"]["fit_bytes"] / 2 ** 30
    print(f"{res['arch']} {res['shape']} {res['mesh']}: compile={res['compile_s']}s "
          f"mem={mem_gb:.2f}GiB (raw_cpu={raw_gb:.2f}) "
          f"flops/dev={res['cost']['flops_per_device']:.3g} "
          f"dominant={res['roofline']['dominant']}")
    if mem_gb > 16.0:
        key = (res["arch"], res["shape"], res["mesh"])
        if key in EXPECTED_OVER_HBM:
            print(f"NOTE: exceeds single-pod HBM as expected "
                  f"({mem_gb:.1f} GiB) — multi-pod mesh required; "
                  f"compile + analysis succeeded.")
        else:
            print(f"WARNING: exceeds 16 GiB/chip HBM ({mem_gb:.1f})")
            sys.exit(2)


if __name__ == "__main__":
    main()
