import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  REPRO_DRYRUN_DEVICES overrides for fast dev runs.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on the production mesh, with zero real allocation
(ShapeDtypeStruct inputs), and record memory / cost / roofline data.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import (model_flops, params_count,
                                     roofline_terms)
from repro.configs import get_config, list_archs, long_variant
from repro.launch.mesh import HBM_PER_CHIP, make_mesh, make_production_mesh
from repro.launch.specs import (INPUT_SHAPES, batch_pspecs, batch_specs,
                                cache_pspecs, cache_specs, make_ctx, named)
from repro.launch.stepfns import (make_prefill_step, make_serve_step,
                                  make_train_step)
from repro.models.api import build_model, param_pspecs
from repro.launch.specs import fsdp_pspecs
from repro.optim import adamw_init

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _with_layer_specs(ctx, cfg, pspecs):
    """Derive per-layer param specs (stacked dim dropped) for remat-friendly
    weight regathering; dense-family models only (layers subtree)."""
    import dataclasses as _dc
    layers = pspecs.get("layers") if isinstance(pspecs, dict) else None
    if layers is None:
        return ctx
    drop = jax.tree.map(
        lambda s: P(*tuple(s)[1:]) if isinstance(s, P) and len(tuple(s))
        else P(),
        layers, is_leaf=lambda x: isinstance(x, P))
    return _dc.replace(ctx, layer_param_specs=drop)

SERVE_FSDP_THRESHOLD = 6 * 1024 ** 3  # bytes/chip of model-sharded params

# Gradient-accumulation factors for the train_4k shape (global batch 256 is
# preserved; microbatches shrink activation memory to fit 16 GiB/chip —
# standard production practice, applied per architecture).
# bf16 optimizer moments for configs whose f32 moments alone bust the
# 16 GiB budget (documented tradeoff; everything else keeps f32).
BF16_MOMENT_ARCHS = {"qwen3-moe-235b-a22b"}

TRAIN_GRAD_ACCUM = {
    "zamba2-1.2b": 4,
    "xlstm-1.3b": 2,
    "qwen3-moe-235b-a22b": 8,
    "yi-34b": 2,
    "qwen2.5-32b": 2,
    "minicpm3-4b": 2,
    "qwen3-moe-30b-a3b": 2,
}


def _mesh_for(tag: str):
    n = len(jax.devices())
    if n >= 512:
        return make_production_mesh(multi_pod=(tag == "multipod"))
    # scaled-down dev meshes keep both axes >1
    if tag == "multipod":
        return make_mesh((2, max(n // 8, 1), 4),
                                ("pod", "data", "model"))
    return make_mesh((max(n // 4, 1), 4), ("data", "model"))


def dryrun_one(arch: str, shape_name: str, mesh_tag: str,
               verbose: bool = True) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if shape_name == "long_500k":
        cfg = long_variant(cfg)
        if cfg is None:
            return {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                    "status": "skipped",
                    "reason": "full-attention enc-dec; see DESIGN.md"}
    api = build_model(cfg)
    mesh = _mesh_for(mesh_tag)
    n_chips = mesh.size
    ctx = make_ctx(mesh, shape)

    t0 = time.time()
    params_shape = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    counts = params_count(cfg, params_shape)
    base_specs = param_pspecs(params_shape, mesh)

    param_bytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                      for l in jax.tree.leaves(params_shape))
    msize = mesh.shape["model"]
    per_chip_model_sharded = param_bytes / msize

    bspecs = batch_specs(cfg, shape)
    b_pspecs = batch_pspecs(cfg, shape, ctx)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            pspecs = fsdp_pspecs(params_shape, mesh, base_specs)
            ctx = _with_layer_specs(ctx, cfg, pspecs)
            moment_dt = (jnp.bfloat16 if cfg.name in BF16_MOMENT_ARCHS
                         else jnp.float32)
            opt_shape = jax.eval_shape(
                lambda p: adamw_init(p, moment_dtype=moment_dt),
                params_shape)
            opt_pspecs = {"mu": pspecs, "nu": pspecs,
                          "step": P()}
            accum = TRAIN_GRAD_ACCUM.get(cfg.name, 1)
            step = make_train_step(api, ctx, grad_accum=accum)
            in_sh = (named(mesh, pspecs), named(mesh, opt_pspecs),
                     named(mesh, b_pspecs))
            out_sh = (named(mesh, pspecs), named(mesh, opt_pspecs), None)
            args = (params_shape, opt_shape, bspecs)
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(0, 1))
        elif shape.kind == "prefill":
            pspecs = (fsdp_pspecs(params_shape, mesh, base_specs)
                      if per_chip_model_sharded > SERVE_FSDP_THRESHOLD
                      else base_specs)
            c_pspecs = cache_pspecs(cfg, shape, ctx)
            step = make_prefill_step(api, ctx)
            in_sh = (named(mesh, pspecs), named(mesh, b_pspecs))
            out_sh = (None, named(mesh, c_pspecs))
            args = (params_shape, bspecs)
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        else:  # decode
            pspecs = (fsdp_pspecs(params_shape, mesh, base_specs)
                      if per_chip_model_sharded > SERVE_FSDP_THRESHOLD
                      else base_specs)
            c_shape = cache_specs(api, cfg, shape)
            c_pspecs = cache_pspecs(cfg, shape, ctx)
            step = make_serve_step(api, ctx)
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            in_sh = (named(mesh, pspecs),
                     NamedSharding(mesh, P(ctx.batch_spec, None)),
                     named(mesh, c_pspecs), NamedSharding(mesh, P()))
            out_sh = (None, named(mesh, c_pspecs))
            args = (params_shape, tok, c_shape, pos)
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(2,))

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    try:
        cost = compiled.cost_analysis()
        cost_flops = float(cost.get("flops", -1.0))
        cost_bytes = float(cost.get("bytes accessed", -1.0))
    except Exception:   # pragma: no cover
        cost_flops = cost_bytes = -1.0
    hlo = analyze_hlo(compiled.as_text(), n_chips)
    terms = roofline_terms(hlo, n_chips=n_chips)
    mflops = model_flops(cfg, counts, shape.kind, shape.global_batch,
                         shape.seq_len)
    mflops_per_chip = mflops / n_chips
    useful_ratio = (mflops_per_chip / hlo["dot_flops"]
                    if hlo["dot_flops"] else 0.0)

    per_chip_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                      + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "status": "ok",
        "n_chips": n_chips,
        "mesh_shape": dict(mesh.shape),
        "family": cfg.family,
        "param_count": counts["total"],
        "param_bytes": param_bytes,
        "fsdp": bool(pspecs is not base_specs),
        "grad_accum": (TRAIN_GRAD_ACCUM.get(cfg.name, 1)
                       if shape.kind == "train" else None),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_chip_bytes": per_chip_bytes,
            "fits_hbm": bool(per_chip_bytes <= HBM_PER_CHIP),
        },
        "cost_analysis": {"flops_per_device_uncorrected": cost_flops,
                          "bytes_accessed_uncorrected": cost_bytes},
        "hlo": {
            "dot_flops_per_chip": hlo["dot_flops"],
            "hbm_bytes_per_chip": hlo["hbm_bytes"],
            "collective_wire_bytes_per_chip": hlo["collective_wire_bytes"],
            "collective_count": hlo["collective_count"],
            "collectives": hlo["collectives"],
        },
        "roofline": terms,
        "model_flops_global": mflops,
        "useful_flops_ratio": useful_ratio,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_tag}: "
              f"compile={t_compile:.1f}s "
              f"mem/chip={per_chip_bytes/2**30:.2f}GiB "
              f"fits={result['memory']['fits_hbm']} "
              f"dominant={terms['dominant']} "
              f"(c={terms['compute_s']*1e3:.2f}ms m={terms['memory_s']*1e3:.2f}ms "
              f"coll={terms['collective_s']*1e3:.2f}ms) "
              f"useful={useful_ratio:.2f}")
    return result


def save_result(res: dict, out_dir: Path = RESULTS_DIR):
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{res['arch']}__{res['shape']}__{res['mesh']}.json"
    (out_dir / name).write_text(json.dumps(res, indent=1, default=float))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_tag in meshes:
                fname = out_dir / f"{arch}__{shape}__{mesh_tag}.json"
                if args.skip_existing and fname.exists():
                    print(f"[dryrun] skip existing {fname.name}")
                    continue
                try:
                    res = dryrun_one(arch, shape, mesh_tag)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                           "status": "error", "error": str(e)[-2000:]}
                    failures.append((arch, shape, mesh_tag, str(e)[:200]))
                save_result(res, out_dir)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-runs OK")


if __name__ == "__main__":
    main()
