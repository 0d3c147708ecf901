import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any jax-importing module: jax locks
# the device count at first backend initialization. Everything below is a
# normal import block.
import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.registry import (ARCH_NAMES, config_for_shape)  # noqa: E402
from repro.core import CompressionConfig, Granularity, make_compressor  # noqa: E402
from repro.launch.analysis import analyze_compiled, save_roofline  # noqa: E402
from repro.launch.engine import Engine  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import INPUT_SHAPES  # noqa: E402
from repro.optim import OptConfig  # noqa: E402

"""Multi-pod dry-run: .lower().compile() for every (arch x shape x mesh).

No arrays are allocated — inputs are ShapeDtypeStructs; the compiled
artifact yields memory_analysis (fits-in-HBM proof), cost_analysis
(FLOPs/bytes) and the per-device HLO whose collective ops feed the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).
"""


def build_compression(args) -> CompressionConfig:
    if args.compressor == "none":
        return CompressionConfig(strategy="dense")
    kw = {}
    if args.compressor in ("randomk", "topk"):
        kw["ratio"] = args.ratio
    if args.compressor == "qsgd":
        kw["levels"] = args.levels
    return CompressionConfig(
        qw=make_compressor(args.compressor, **kw),
        qm=(make_compressor(args.qm) if args.qm != "identity"
            else make_compressor("identity")),
        granularity=Granularity(args.granularity, args.block_size),
        strategy=args.strategy,
        wire_dtype=args.wire_dtype)


def run_one(arch: str, shape_name: str, multi_pod: bool, comp, opt,
            out_dir: str, remat: bool = True, save_hlo: bool = False,
            microbatch: int = 0, tag_suffix: str = "",
            capacity_factor: float = 0.0, mesh_shape=None,
            kv_int8: bool = False):
    shape = INPUT_SHAPES[shape_name]
    cfg, note = config_for_shape(arch, shape_name)
    if cfg is not None and microbatch:
        cfg = dataclasses.replace(cfg, train_microbatch=microbatch)
    if cfg is not None and capacity_factor:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=capacity_factor)
    if cfg is not None and kv_int8 and cfg.attention == "gqa":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}{tag_suffix}"
    if cfg is None:
        print(f"[skip] {tag}: {note}")
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "note": note}
    t0 = time.time()
    if mesh_shape:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(mesh_shape, ("data", "model"))
        mesh_name = "x".join(str(s) for s in mesh_shape)
        tag = f"{arch}__{shape_name}__{mesh_name}{tag_suffix}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    eng = Engine(cfg, mesh, comp=comp, opt=opt, remat=remat)
    with jax.sharding.use_mesh(mesh):
        if shape.kind == "train":
            step = eng.build_train_step()
            args_sds, _ = eng.train_input_specs(shape)
            lowered = step.lower(*args_sds)
        elif shape.kind == "prefill":
            step = eng.build_prefill(shape)
            params = eng._sharded_sds(eng.model.param_shapes(),
                                      eng.model.param_pspecs())
            (batch,), _ = eng.input_specs(shape)
            lowered = step.lower(params, batch)
        else:
            step = eng.build_serve_step(shape)
            params = eng._sharded_sds(eng.model.param_shapes(),
                                      eng.model.param_pspecs())
            (batch, cache), _ = eng.input_specs(shape)
            lowered = step.lower(params, batch, cache)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    roof = analyze_compiled(compiled, arch=arch, shape=shape,
                            mesh_name=mesh_name, chips=chips, cfg=cfg)
    est = eng.memory_estimate(shape)
    roof.memory_per_device["tpu_estimate_total"] = est["total"]
    roof.memory_per_device["tpu_estimate_fits_16g"] = float(est["fits_16g"])
    print(compiled.memory_analysis())
    print("tpu_estimate:", {k: (round(v / 1e9, 3) if isinstance(v, float)
                                else v) for k, v in est.items()})
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    print({k: v for k, v in ca.items()
           if k in ("flops", "bytes accessed")})
    os.makedirs(out_dir, exist_ok=True)
    save_roofline(roof, os.path.join(out_dir, f"{tag}.json"))
    if save_hlo:
        with open(os.path.join(out_dir, f"{tag}.hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    d = roof.to_dict()
    d.update(status="ok", note=note, lower_s=round(t_lower, 1),
             compile_s=round(t_compile, 1))
    print(f"[ok] {tag}: bottleneck={roof.bottleneck} "
          f"t=({roof.t_compute:.4f},{roof.t_memory:.4f},"
          f"{roof.t_collective:.4f})s useful={roof.useful_flops_ratio:.3f} "
          f"lower={t_lower:.0f}s compile={t_compile:.0f}s")
    return d


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help=f"one of {ARCH_NAMES} or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {tuple(INPUT_SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--compressor", default="topk",
                    help="none|randomk|topk|threshold_v|adaptive_threshold|"
                         "terngrad|qsgd|signsgd|natural")
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--levels", type=int, default=16)
    ap.add_argument("--qm", default="identity")
    ap.add_argument("--granularity", default="layerwise",
                    choices=["layerwise", "entire_model", "blockwise"])
    ap.add_argument("--block-size", type=int, default=65536)
    ap.add_argument("--strategy", default="simulated")
    ap.add_argument("--wire-dtype", default="float32")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--mesh-shape", default="",
                    help="override: 'data,model' e.g. '64,4' (analysis runs)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache (GQA archs)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args(argv)

    comp = build_compression(args)
    opt = OptConfig(name=args.optimizer)
    archs = ARCH_NAMES if args.arch == "all" else (args.arch,)
    shapes = tuple(INPUT_SHAPES) if args.shape == "all" else (args.shape,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    results, failures = [], 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(arch, shape, mp, comp, opt,
                                           args.out,
                                           remat=not args.no_remat,
                                           save_hlo=args.save_hlo,
                                           microbatch=args.microbatch,
                                           tag_suffix=args.tag,
                                           capacity_factor=args.capacity_factor,
                                           mesh_shape=tuple(
                                               int(x) for x in
                                               args.mesh_shape.split(","))
                                           if args.mesh_shape else None,
                                           kv_int8=args.kv_int8))
                except Exception:
                    failures += 1
                    tagm = "2x16x16" if mp else "16x16"
                    print(f"[FAIL] {arch}__{shape}__{tagm}")
                    traceback.print_exc()
                    if args.fail_fast:
                        raise
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "summary.json"), "a") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    print(f"\n{len(results)} ok / {failures} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
