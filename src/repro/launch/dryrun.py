import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init. Tests may shrink the pool via REPRO_DRYRUN_DEVICES.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
resolves, collectives legal, memory fits) and extracts the roofline terms:

    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...).lower(**input_specs)
        compiled = lowered.compile()
        compiled.memory_analysis()   # fits?
        compiled.cost_analysis()     # FLOPs / bytes for the roofline

Results are written incrementally as JSON under --out (default
experiments/dryrun/<mesh>/<arch>__<shape>.json).

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--remat full]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..analysis import hlo_costs
from ..analysis import roofline as rl
from ..configs import (SHAPES, applicable, get_config, input_specs,
                       list_archs, n_active_params, reduced)
from ..distributed.sharding import MeshRules, replicated
from ..models.model import Model
from ..optim.adamw import AdamW, AdamWState
from ..train import steps as steps_lib
from .mesh import make_production_mesh, make_mesh


def _memstats_dict(ma) -> dict:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def lower_cell(cfg, shape, mesh, *, remat: str = "full",
               batch_override: int = 0, extra_rules=None, zero: bool = False):
    """Build + lower + compile one cell; returns (compiled, report_dict)."""
    rules = MeshRules(mesh)
    if extra_rules:
        rules.rules.update(extra_rules)
    model = Model(cfg, constrain=rules.constrain, remat=remat, mesh=mesh)
    specs = input_specs(cfg, shape, batch_override)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    params_shapes = jax.eval_shape(lambda: model.init(key))
    param_sh = rules.tree_shardings(model.param_specs(), params_shapes)
    batch_sh = rules.batch_shardings(specs)

    if shape.kind == "train":
        opt = AdamW()
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        if zero:
            # ZeRO: master params + moments additionally sharded over data
            param_sh = rules.tree_shardings_zero(model.param_specs(),
                                                 params_shapes)
            zsh = param_sh
        else:
            zsh = param_sh
        opt_sh = AdamWState(count=replicated(mesh), mu=zsh, nu=zsh)
        step = steps_lib.make_train_step(model, opt)
        jf = jax.jit(step,
                     in_shardings=(param_sh, opt_sh, batch_sh),
                     out_shardings=(param_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        lowered = jf.lower(params_shapes, opt_shapes, specs)
    elif shape.kind == "prefill":
        step = steps_lib.make_prefill_step(model)
        jf = jax.jit(step, in_shardings=(param_sh, batch_sh),
                     out_shardings=None)
        lowered = jf.lower(params_shapes, specs)
    else:  # decode
        b = batch_override or shape.global_batch
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(b, shape.seq_len))
        cache_sh = rules.tree_shardings(model.cache_specs(), cache_shapes)
        step = steps_lib.make_serve_step(model)
        jf = jax.jit(step,
                     in_shardings=(param_sh, cache_sh,
                                   batch_sh["tokens"], batch_sh["pos"]),
                     out_shardings=(None, cache_sh),
                     donate_argnums=(1,))
        lowered = jf.lower(params_shapes, cache_shapes,
                           specs["tokens"], specs["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    raw_cost = dict(compiled.cost_analysis())
    ma = compiled.memory_analysis()
    memstats = _memstats_dict(ma)
    # trip-count-corrected per-device costs from the optimized HLO
    # (cost_analysis counts while bodies once — see analysis/hlo_costs.py)
    parsed = hlo_costs.module_costs(compiled.as_text())
    cost = {"flops": parsed["flops"], "bytes accessed": parsed["hbm_bytes"]}
    coll = parsed["coll"]
    chips = mesh.devices.size
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    roof = rl.analyze(
        arch=cfg.name, shape=shape.name, mesh_name=mesh_name, chips=chips,
        cost=cost,
        coll=coll, model_flops=rl.model_flops_for(cfg, shape, n_active_params(cfg)),
        device_kind=rl.V5E, memstats=memstats)
    op_mix = dict(sorted(parsed["op_mix"].items(),
                         key=lambda kv: -kv[1])[:24])
    report = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "chips": chips, "remat": remat,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": memstats,
        "bytes_per_device_resident": memstats["argument_size_in_bytes"]
        + memstats["temp_size_in_bytes"],
        "cost_analysis_raw": {k: float(v) for k, v in raw_cost.items()
                              if k in ("flops", "bytes accessed",
                                       "transcendentals", "optimal_seconds")},
        "hlo_costs": {"flops": parsed["flops"],
                      "hbm_bytes": parsed["hbm_bytes"]},
        "collective_bytes": coll,
        "op_mix": op_mix,
        "roofline": roof.asdict(),
        "status": "ok",
    }
    return compiled, report


def run_cell(arch: str, shape_name: str, mesh, out_dir: Path, *,
             remat: str = "full", use_reduced: bool = False,
             extra_rules=None, cfg_overrides=None, zero: bool = False) -> dict:
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    if cfg_overrides:
        import dataclasses as _dc
        moe_over = cfg_overrides.pop("capacity_factor", None)
        cfg = _dc.replace(cfg, **cfg_overrides)
        if moe_over is not None and cfg.moe is not None:
            cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe,
                                                   capacity_factor=moe_over))
    shape = SHAPES[shape_name]
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    out_path = out_dir / mesh_name / f"{cfg.name}__{shape_name}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    if not applicable(cfg, shape):
        report = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped",
                  "reason": "long_500k requires sub-quadratic attention "
                            "(DESIGN.md SS Arch-applicability)"}
        out_path.write_text(json.dumps(report, indent=2))
        return report
    try:
        compiled, report = lower_cell(cfg, shape, mesh, remat=remat,
                                      extra_rules=extra_rules, zero=zero)
        del compiled
    except Exception as e:  # a failing cell is a bug; record it loudly
        report = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
    out_path.write_text(json.dumps(report, indent=2))
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=("full", "dots", "dots_no_batch", "none"))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke mode: reduced configs (CI)")
    ap.add_argument("--mesh-shape", default=None,
                    help="override, e.g. 2,4 with axes data,model")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-shard master params/moments over data")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual streams (seq -> model)")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--rwkv-chunk", type=int, default=None)
    ap.add_argument("--kv-chunk", type=int, default=None)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    meshes = []
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        meshes.append(make_mesh(shape, axes))
    elif args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    n_ok = n_skip = n_err = 0
    for mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                t0 = time.time()
                extra_rules = {"seq": "model"} if args.sp else None
                overrides = {}
                if args.capacity_factor is not None:
                    overrides["capacity_factor"] = args.capacity_factor
                if args.rwkv_chunk is not None:
                    overrides["rwkv_chunk"] = args.rwkv_chunk
                rep = run_cell(arch, shape_name, mesh, out_dir,
                               remat=args.remat, use_reduced=args.reduced,
                               extra_rules=extra_rules,
                               cfg_overrides=overrides or None,
                               zero=args.zero)
                dt = time.time() - t0
                status = rep["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                line = (f"[{status:7s}] {rep['mesh']:9s} {arch:22s} "
                        f"{shape_name:12s} {dt:7.1f}s")
                if status == "ok":
                    r = rep["roofline"]
                    line += (f"  flops/dev={r['flops_per_device']:.3e}"
                             f" Tc={r['t_compute']:.4f}s Tm={r['t_memory']:.4f}s"
                             f" Tx={r['t_collective']:.4f}s -> {r['bottleneck']}")
                elif status == "error":
                    line += "  " + rep["error"][:160]
                print(line, flush=True)
    print(f"DONE ok={n_ok} skipped={n_skip} errors={n_err}", flush=True)
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
