import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count at first
#   initialization. 512 host devices let jax.make_mesh build the production
#   (16,16) single-pod and (2,16,16) multi-pod meshes with no TPU attached.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh and logical sharding rules,
  2. constructs ShapeDtypeStruct stand-ins for params / optimizer state /
     inputs (zero allocation — 132B-param configs lower on a laptop),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile()``,
  4. records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs / bytes for the roofline) and the collective schedule parsed
     from the optimized HLO,
  5. writes one JSON artifact per cell under ``benchmarks/artifacts/dryrun``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both [--force] [--out benchmarks/artifacts/dryrun]
"""
# NOTE: no `from __future__ import annotations` here — the XLA_FLAGS lines
# above must be the first statements in the file, which Python forbids for
# __future__ imports. This module therefore uses runtime-valid annotations.

import argparse
import json
import time
import traceback
from pathlib import Path

import dataclasses as _dc

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import hlo_analysis, roofline, sharding
from repro.configs import SHAPES, get_config, list_archs, shape
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import AdamConfig, adam_init
from repro.train.step import TrainState, make_train_step
from repro.utils import human_bytes, logger


# ---------------------------------------------------------------------------
# sharding-tree helpers
# ---------------------------------------------------------------------------

# moved to repro.sharding so the serving Engine can build the same trees
# without importing this module (whose XLA_FLAGS line must never run inside
# a live engine process); kept as an alias for existing callers/tests.
shardings_for = sharding.tree_shardings


def replicated(tree, mesh):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


def batch_shardings(batch_specs, mesh, rules):
    """tokens/labels (B, S) -> batch-sharded; embeds (B, S, D) likewise."""
    def f(x):
        names = ["batch"] + [None] * (x.ndim - 1)
        return NamedSharding(mesh,
                             sharding.resolve_spec(names, x.shape, mesh, rules))
    return jax.tree_util.tree_map(f, batch_specs)


# ---------------------------------------------------------------------------
# perf-iteration variants (EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------
# name -> dict(cfg=..., rules=..., quantized=bits, compress_grads=bool)
VARIANTS = {
    "baseline": {},
    # hillclimb A (gemma-2b train_4k): pin attention score shardings
    "pin_attn": {"cfg": {"attn_sharding": "batch"}},
    "seq_attn": {"cfg": {"attn_sharding": "seq"}},
    "seq_attn_flash": {"cfg": {"attn_sharding": "seq",
                               "attn_chunk_threshold": 2048}},
    # hillclimb B (mamba2 train_4k)
    "ssd_bf16": {"cfg": {"ssd_bf16_intra": True}},
    "chunk128": {"cfg": {"ssm_chunk": 128}},
    "ssd_bf16_chunk128": {"cfg": {"ssd_bf16_intra": True, "ssm_chunk": 128}},
    "mb4": {"cfg": {"microbatches": 4}},
    "ssd_best_mb4": {"cfg": {"ssd_bf16_intra": True, "ssm_chunk": 128,
                             "microbatches": 4}},
    # hillclimb C (dbrx-132b decode_32k)
    "serve_tp": {"rules": {"fsdp_embed": None}},          # bf16 TP-only
    "w4_packed": {"quantized": 4, "rules": {"fsdp_embed": None}},
    "w4_packed_kv8": {"quantized": 4, "rules": {"fsdp_embed": None},
                      "cfg": {"dtype": "bfloat16"}, "kv_bits": 8},
    # weight-activation serving (paper Table 3 deployment point)
    "w4a8_packed": {"quantized": 4, "a_bits": 8,
                    "rules": {"fsdp_embed": None}},
    "w4a4_packed_kv8": {"quantized": 4, "a_bits": 4, "kv_bits": 8,
                        "rules": {"fsdp_embed": None},
                        "cfg": {"dtype": "bfloat16"}},
}


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    """Returns (lowered, compiled, meta) for one cell."""
    vspec = VARIANTS[variant]
    cfg = get_config(arch)
    if vspec.get("cfg"):
        cfg = _dc.replace(cfg, **vspec["cfg"])
    sc = shape(shape_name)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = sharding.make_rules(multi_pod=multi_pod)
    if vspec.get("rules"):
        rules.update(vspec["rules"])
    chips = mesh.size

    if vspec.get("quantized"):
        return _lower_quantized_decode(cfg, sc, mesh, rules, chips, variant,
                                       bits=vspec["quantized"],
                                       a_bits=vspec.get("a_bits", 16),
                                       kv_bits=vspec.get("kv_bits", 16))

    with sharding.use_mesh(mesh, rules):
        param_axes = model.param_logical_axes()
        params_shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        params_sh = shardings_for(param_axes, params_shapes, mesh, rules)

        if sc.mode == "train":
            adam_cfg = AdamConfig(state_dtype=cfg.opt_state_dtype)
            train_step = make_train_step(model, adam_cfg)
            opt_shapes = jax.eval_shape(
                lambda: adam_init(params_shapes, adam_cfg))
            opt_sh = {
                "mu": shardings_for(param_axes, opt_shapes["mu"], mesh, rules),
                "nu": shardings_for(param_axes, opt_shapes["nu"], mesh, rules),
                "count": NamedSharding(mesh, P()),
            }
            state_specs = TrainState(params=params_shapes, opt=opt_shapes,
                                     step=jax.ShapeDtypeStruct((), jnp.int32))
            state_sh = TrainState(params=params_sh, opt=opt_sh,
                                  step=NamedSharding(mesh, P()))
            batch_specs = model.input_specs(sc)
            batch_sh = batch_shardings(batch_specs, mesh, rules)
            jitted = jax.jit(train_step,
                             in_shardings=(state_sh, batch_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_specs, batch_specs)

        elif sc.mode == "prefill":
            batch_specs = model.input_specs(sc)
            batch_sh = batch_shardings(batch_specs, mesh, rules)
            if cfg.family == "audio":
                def prefill_fn(params, batch):
                    return model.forward(params, batch)
            else:
                def prefill_fn(params, batch):
                    return model.prefill(params, batch, max_len=sc.seq_len)
            jitted = jax.jit(prefill_fn, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_shapes, batch_specs)

        else:  # decode
            specs = model.input_specs(sc)  # noqa: F841 (shared below)
            token_specs, cache_specs = specs["token"], specs["cache"]
            cache_axes = model.cache_logical_axes(cache_specs)
            cache_sh = shardings_for(cache_axes, cache_specs, mesh, rules)
            token_sh = NamedSharding(
                mesh, sharding.resolve_spec(["batch", None],
                                            token_specs.shape, mesh, rules))

            def serve_step(params, token, cache):
                return model.decode_step(params, token, cache)

            jitted = jax.jit(serve_step,
                             in_shardings=(params_sh, token_sh, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_shapes, token_specs, cache_specs)

        t0 = time.monotonic()
        compiled = lowered.compile()
        compile_s = time.monotonic() - t0

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "chips": chips, "mode": sc.mode, "compile_s": compile_s,
            "variant": variant}
    return lowered, compiled, meta


def _lower_quantized_decode(cfg, sc, mesh, rules, chips, variant, *,
                            bits: int, a_bits: int = 16, kv_bits: int = 16):
    """AffineQuant deployment cell: packed int weights, TP-only resident
    (no FSDP gathers), reference dequant math (lowerable on CPU; the Pallas
    kernel replaces it 1:1 on TPU). ``a_bits < 16`` lowers the fused
    weight-activation path; ``kv_bits < 16`` the int8-coded KV cache —
    both native ``QuantizedModel`` features, no spec stubbing needed.

    ``kernel_mode="ref"`` makes decode attention lower the page-structured
    flash-decode reference (``ops.flash_decode`` mode ref): 2048 pages of
    16 slots for the 32k shapes, cache read as stored, no (B, S, Hkv, D)
    fp intermediate in the step — the same per-page math the Pallas kernel
    runs for every head of a sequence on TPU."""
    from repro.core.quantizer import QuantConfig
    from repro.serve.quantized import QuantizedModel, quantize_lm_packed

    qcfg = QuantConfig(w_bits=bits, a_bits=a_bits, group_size=128,
                       kv_bits=kv_bits)
    qmodel = QuantizedModel(cfg, qcfg, kernel_mode="ref")
    base = build_model(cfg)

    with sharding.use_mesh(mesh, rules):
        params_shapes = jax.eval_shape(
            lambda: quantize_lm_packed(base.init(jax.random.PRNGKey(0)),
                                       cfg, qcfg))
        params_sh = shardings_for(qmodel.param_logical_axes(), params_shapes,
                                  mesh, rules)
        cache_specs = qmodel.cache_specs(sc.global_batch, sc.seq_len)
        cache_axes = qmodel.cache_logical_axes(cache_specs)
        cache_sh = shardings_for(cache_axes, cache_specs, mesh, rules)
        token_specs = jax.ShapeDtypeStruct((sc.global_batch, 1), jnp.int32)
        token_sh = NamedSharding(
            mesh, sharding.resolve_spec(["batch", None], token_specs.shape,
                                        mesh, rules))
        serve_step = qmodel.decode_step

        jitted = jax.jit(serve_step,
                         in_shardings=(params_sh, token_sh, cache_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_shapes, token_specs, cache_specs)
        t0 = time.monotonic()
        compiled = lowered.compile()
        compile_s = time.monotonic() - t0

    meta = {"arch": cfg.name, "shape": sc.name,
            "mesh": "multi" if "pod" in mesh.axis_names else "single",
            "chips": chips, "mode": sc.mode, "compile_s": compile_s,
            "variant": variant}
    return lowered, compiled, meta


def analyze(lowered, compiled, meta, cfg, sc) -> dict:
    out = dict(meta)
    # --- memory ---
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": repr(e)}
    # --- cost ---
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        out["cost"] = {k: v for k, v in cost.items()
                       if k in ("flops", "bytes accessed", "transcendentals",
                                "optimal_seconds")
                       or k.startswith("bytes accessed")}
    except Exception as e:  # pragma: no cover
        out["cost"] = {"error": repr(e)}
    # --- static HLO analysis (trip-count-corrected; see repro.hlo_analysis:
    # XLA's cost_analysis counts while bodies once, undercounting scan-over-
    # layers models by ~num_layers x microbatches) ---
    try:
        hlo = compiled.as_text()
        stats = hlo_analysis.analyze_hlo(hlo)
        out["hlo_analysis"] = {
            "dot_flops": stats["dot_flops"],
            "memory_bytes": stats["memory_bytes"],
        }
        out["collectives"] = stats["collectives"]
        out["hlo_bytes_len"] = len(hlo)
    except Exception as e:  # pragma: no cover
        out["collectives"] = {"error": repr(e)}
        out["hlo_analysis"] = {"error": repr(e)}

    flops = out.get("hlo_analysis", {}).get("dot_flops", 0.0) or 0.0
    bytes_acc = out.get("hlo_analysis", {}).get("memory_bytes", 0.0) or 0.0
    coll = out.get("collectives", {}).get("total_bytes", 0.0) or 0.0
    terms = roofline.RooflineTerms(
        arch=meta["arch"], shape=meta["shape"], mesh=meta["mesh"],
        chips=meta["chips"], hlo_flops=flops, hlo_bytes=bytes_acc,
        collective_bytes=coll,
        model_flops=roofline.model_flops(cfg, sc, sc.mode)).finalize()
    out["roofline"] = terms.to_dict()
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             force: bool = False, keep_hlo: bool = False,
             variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    sc = shape(shape_name)
    suffix = "" if variant == "baseline" else f"__{variant}"
    path = out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
    if path.exists() and not force:
        return json.loads(path.read_text())

    if not cfg.supports(shape_name):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skip",
                  "reason": cfg.skip_reason(shape_name) or "unsupported"}
        path.write_text(json.dumps(result, indent=2))
        return result

    logger.info("dry-run %s x %s x %s [%s] ...", arch, shape_name,
                mesh_kind, variant)
    try:
        lowered, compiled, meta = lower_cell(arch, shape_name,
                                             mesh_kind == "multi", variant)
        result = analyze(lowered, compiled, meta, cfg, sc)
        result["status"] = "ok"
        if keep_hlo:
            (out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.hlo.txt"
             ).write_text(compiled.as_text())
        logger.info("  ok: compile=%.1fs flops/dev=%.3e coll=%s dominant=%s",
                    meta["compile_s"], result["roofline"]["hlo_flops"],
                    human_bytes(result["roofline"]["collective_bytes"]),
                    result["roofline"]["dominant"])
    except Exception as e:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "error", "error": repr(e),
                  "traceback": traceback.format_exc()}
        logger.error("  FAILED %s x %s x %s: %r", arch, shape_name, mesh_kind, e)
    path.write_text(json.dumps(result, indent=2))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list_archs(assigned_only=True) if args.arch == "all" \
        else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                r = run_cell(arch, shape_name, mesh_kind, out_dir,
                             force=args.force, keep_hlo=args.keep_hlo,
                             variant=args.variant)
                st = r.get("status")
                n_ok += st == "ok"
                n_skip += st == "skip"
                n_err += st == "error"
    logger.info("dry-run complete: %d ok, %d skipped, %d errors",
                n_ok, n_skip, n_err)
    return 1 if n_err else 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
