import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh),
extract memory/cost analysis and collective schedule, write one JSON per
combo (resumable).

The three lines above MUST stay first: jax locks the device count at first
initialisation, and the production meshes need 512 placeholder CPU devices
(on a TPU host jax would otherwise pick the chip).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch all|<id>] [--shape all|<name>] [--mesh single|multi|both]
      [--variant dense|m2] [--out results/dryrun] [--fsdp/--no-fsdp]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_case
from repro.roofline.analysis import model_flops_for, roofline


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            variant: str = "dense", fsdp: bool = True,
            pod_fsdp: bool = False, shard_kv_seq=None,
            expert_data_shard: bool = False, kv_quant: bool = False,
            verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()
    case = build_case(arch, shape_name, mesh, variant=variant, fsdp=fsdp,
                      pod_fsdp=pod_fsdp, shard_kv_seq=shard_kv_seq,
                      expert_data_shard=expert_data_shard,
                      kv_quant=kv_quant)
    with mesh:
        jitted = jax.jit(case.fn, in_shardings=case.in_shardings,
                         out_shardings=case.out_shardings,
                         donate_argnums=case.donate_argnums)
        lowered = jitted.lower(*case.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    terms = roofline(cost, hlo, chips=int(mesh.devices.size),
                     model_flops=model_flops_for(cfg, shape))

    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(mesh.devices.size),
        "fsdp": fsdp, "pod_fsdp": pod_fsdp,
        "expert_data_shard": expert_data_shard,
        "kv_quant": kv_quant,
        "meta": case.meta,
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2**30,
            "output_gb": mem.output_size_in_bytes / 2**30,
            "temp_gb": mem.temp_size_in_bytes / 2**30,
            "alias_gb": mem.alias_size_in_bytes / 2**30,
            "per_device_gb": (mem.argument_size_in_bytes
                              + mem.output_size_in_bytes
                              + mem.temp_size_in_bytes
                              - mem.alias_size_in_bytes) / 2**30,
        } if mem else None,
        "cost": {k: cost[k] for k in ("flops", "bytes accessed")
                 if k in cost},
        "roofline": terms.to_json(),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    if verbose:
        m = rec["memory"] or {}
        print(f"[ok] {arch} × {shape_name} × {rec['mesh']} ({variant}) "
              f"compile={t_compile:.1f}s mem/dev={m.get('per_device_gb', -1):.2f}GiB "
              f"bottleneck={terms.bottleneck} "
              f"(c={terms.compute_s*1e3:.1f}ms m={terms.memory_s*1e3:.1f}ms "
              f"coll={terms.collective_s*1e3:.1f}ms)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="dense", choices=["dense", "m2"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--pod-fsdp", action="store_true")
    ap.add_argument("--expert-data-shard", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_tag = "multi" if multi else "single"
                fname = os.path.join(
                    args.out,
                    f"{arch}__{shape}__{mesh_tag}__{args.variant}"
                    f"{args.tag}.json")
                if os.path.exists(fname) and not args.force:
                    print(f"[skip] {fname}", flush=True)
                    continue
                try:
                    rec = run_one(arch, shape, multi_pod=multi,
                                  variant=args.variant,
                                  fsdp=not args.no_fsdp,
                                  pod_fsdp=args.pod_fsdp,
                                  expert_data_shard=args.expert_data_shard,
                                  kv_quant=args.kv_quant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi else "16x16",
                           "variant": args.variant, "status": "fail",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[FAIL] {arch} × {shape} × {mesh_tag}: "
                          f"{type(e).__name__}: {str(e)[:300]}", flush=True)
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
