import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholders only: never take a chip

"""Multi-pod dry-run: lower + compile every (architecture x input shape x mesh)
cell and derive the roofline terms from the compiled artifact.

The lines above MUST run before any other import (jax locks the device
count at first backend init); 512 placeholder host devices back both the
single-pod 16x16 mesh and the 2x16x16 multi-pod mesh. The dry run stays on
the CPU even on a TPU host, so it and the child per-cell processes it
spawns never hold the chip another process needs.

Usage:
    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--jobs-from N]
    python -m repro.launch.dryrun --list
"""
import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import roofline  # noqa: E402
from repro.config import (SHAPES, TrainConfig, cell_supported, get_arch,  # noqa: E402
                          list_archs)
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import describe, make_production_mesh  # noqa: E402
from repro.models.model import Runtime  # noqa: E402
from repro.serve.engine import make_decode_step, make_prefill  # noqa: E402
from repro.train.step import make_optimizer_for, make_train_step  # noqa: E402

BIG_ARCHS = {"deepseek-v2-236b", "arctic-480b"}  # adafactor + fsdp


def runtime_for(cfg, mesh, shape, overrides: Optional[Dict] = None) -> Runtime:
    kw: Dict[str, Any] = dict(
        mesh=mesh,
        compute_dtype=jnp.bfloat16,
        remat="full" if shape.kind == "train" else "none",
        fsdp=cfg.name in BIG_ARCHS,
        attn_seq_shard=False,  # baseline; hillclimb enables via overrides
    )
    kw.update({k: v for k, v in (overrides or {}).items()
               if k != "microbatches"})
    return Runtime(**kw)


def train_config_for(cfg) -> TrainConfig:
    return TrainConfig(optimizer="adafactor" if cfg.name in BIG_ARCHS
                       else "adamw")


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               rt_overrides: Optional[Dict] = None):
    """Returns (lowered_fn_args (jitted, args), mesh, cfg, shape, rt, notes)."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        raise ValueError(f"unsupported cell: {why}")
    mesh = make_production_mesh(multi_pod=multi_pod)
    rt = runtime_for(cfg, mesh, shape, rt_overrides)
    notes = []

    if shape.kind == "train":
        tcfg = train_config_for(cfg)
        notes.append(f"optimizer={tcfg.optimizer} fsdp={rt.fsdp} remat={rt.remat}")
        opt = make_optimizer_for(tcfg)
        mb = int((rt_overrides or {}).get("microbatches", 1))
        notes.append(f"microbatches={mb}")
        state_shapes, state_specs = S.train_state_specs(cfg, rt, tcfg)
        step = make_train_step(cfg, rt, opt, microbatches=mb,
                               param_specs=state_specs.params)
        batch = S.input_specs(cfg, shape)
        bspecs = S.batch_pspecs(cfg, shape, rt)
        metrics_shape = jax.eval_shape(step, state_shapes, batch)[1]
        mspecs = jax.tree.map(lambda _: P(), metrics_shape)
        jitted = jax.jit(step,
                         in_shardings=(S.named(mesh, state_specs),
                                       S.named(mesh, bspecs)),
                         out_shardings=(S.named(mesh, state_specs),
                                        S.named(mesh, mspecs)),
                         donate_argnums=(0,))
        return jitted, (state_shapes, batch), mesh, cfg, shape, rt, notes

    if shape.kind == "prefill":
        fn = make_prefill(cfg, rt)
        params_shapes, pspecs = S.param_specs_only(cfg, rt)
        batch = S.input_specs(cfg, shape)
        bspecs = S.batch_pspecs(cfg, shape, rt)
        out_shape = jax.eval_shape(fn, params_shapes, batch)
        ospec = P(rt.batch_spec(shape.global_batch), None,
                  rt.model_axis if rt.model_divides(out_shape.shape[-1]) else None)
        jitted = jax.jit(fn,
                         in_shardings=(S.named(mesh, pspecs),
                                       S.named(mesh, bspecs)),
                         out_shardings=S.named(mesh, ospec))
        return jitted, (params_shapes, batch), mesh, cfg, shape, rt, notes

    # decode
    fn = make_decode_step(cfg, rt)
    params_shapes, pspecs = S.param_specs_only(cfg, rt)
    caches, cspecs = S.decode_cache_specs(cfg, shape, rt)
    batch = S.input_specs(cfg, shape)
    bspecs = S.batch_pspecs(cfg, shape, rt)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    out_shapes = jax.eval_shape(fn, params_shapes, batch, caches, index)
    lspec = P(rt.batch_spec(shape.global_batch), None,
              rt.model_axis if rt.model_divides(out_shapes[0].shape[-1]) else None)
    jitted = jax.jit(fn,
                     in_shardings=(S.named(mesh, pspecs),
                                   S.named(mesh, bspecs),
                                   S.named(mesh, cspecs), S.named(mesh, P())),
                     out_shardings=(S.named(mesh, lspec),
                                    S.named(mesh, cspecs)),
                     donate_argnums=(2,))
    return jitted, (params_shapes, batch, caches, index), mesh, cfg, shape, rt, notes


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rt_overrides: Optional[Dict] = None,
             print_analysis: bool = True) -> Dict[str, Any]:
    rt_overrides = rt_overrides or {}
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    mesh_desc = "pod=2xdata=16xmodel=16" if multi_pod else "data=16xmodel=16"
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_desc,
        "multi_pod": multi_pod, "status": "skip", "reason": why,
    }
    if not ok:
        return result
    t0 = time.time()
    jitted, args, mesh, cfg, shape, rt, notes = build_cell(
        arch, shape_name, multi_pod, rt_overrides)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis()
    mem = roofline.memory_analysis_dict(compiled)
    if print_analysis:
        print(f"[{arch} x {shape_name} x {mesh_desc}] "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print("  memory_analysis:", json.dumps(mem))
        print("  cost_analysis: flops=%.3e bytes=%.3e"
              % (cost.get("flops", 0), cost.get("bytes accessed", 0)))
    hlo = compiled.as_text()
    report = roofline.analyze(
        arch=arch, shape_name=shape_name, mesh_desc=mesh_desc,
        n_devices=mesh.size, cost=cost, hlo_text=hlo, memory_analysis=mem,
        cfg=cfg, shape=shape, notes="; ".join(notes))
    result.update(status="ok", lower_s=t_lower, compile_s=t_compile,
                  roofline=report.to_json(), step_time_s=report.step_time_s,
                  mfu=report.mfu)
    return result


def cell_list():
    cells = []
    for arch in sorted(set(list_archs()) - {"gpt2"}):
        for shape_name in SHAPES:
            cells.append((arch, shape_name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--fresh", action="store_true",
                    help="recompute cells that already have results")
    # hillclimb knobs (recorded in the result JSON)
    ap.add_argument("--strategy", default="")
    ap.add_argument("--remat", default="")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--mixed-precision", action="store_true")
    ap.add_argument("--scores-bf16", action="store_true")
    ap.add_argument("--seq-shard-attn", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    if args.strategy:
        overrides["strategy"] = args.strategy
    if args.remat:
        overrides["remat"] = args.remat
    if args.microbatches:
        overrides["microbatches"] = args.microbatches
    if args.mixed_precision:
        overrides["mixed_precision"] = True
    if args.scores_bf16:
        overrides["attn_scores_bf16"] = True
    if args.seq_shard_attn:
        overrides["attn_seq_shard"] = True

    if args.list:
        for arch, shape in cell_list():
            cfg = get_arch(arch)
            ok, why = cell_supported(cfg, SHAPES[shape])
            print(f"{arch:20s} {shape:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return 0

    os.makedirs(args.out, exist_ok=True)

    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        failures = 0
        for arch, shape in cell_list():
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.fresh:
                    print(f"cached {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                print(f"=== {tag} ===", flush=True)
                rc = subprocess.call(cmd)
                if rc != 0:
                    failures += 1
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "multi_pod": mp, "status": "fail",
                                   "rc": rc}, f)
        print(f"done; failures={failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    tag = f"{args.arch}__{args.shape}__{'multi' if args.multi_pod else 'single'}"
    if args.tag:
        tag += "__" + args.tag
    path = os.path.join(args.out, tag + ".json")
    try:
        result = run_cell(args.arch, args.shape, args.multi_pod, overrides)
        result["overrides"] = {k: str(v) for k, v in overrides.items()}
    except Exception as e:
        traceback.print_exc()
        result = {"arch": args.arch, "shape": args.shape,
                  "multi_pod": args.multi_pod, "status": "fail",
                  "error": f"{type(e).__name__}: {e}"}
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        return 1
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    if result["status"] == "ok":
        r = result["roofline"]
        print(f"  terms: compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
              f"collective={r['collective_s']:.4f}s -> {r['bottleneck']}-bound; "
              f"useful={r['useful_ratio']:.3f} mfu={result['mfu']:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
