#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU v5e, without the chip,
and print its memory analysis: the check to make before a cell's first
run on the chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py <cell> [<cell> ...]

A one-chip cell compiles for one device of a described v5e:2x2, a
four-chip cell for all four. The step is the launcher's (parse_args, the
engine, build_train_step) on the cell's settings, with its arguments as
shapes; nothing runs. The last line of standard output is a JSON object
{cell: {seconds, argument/output/temp/generated bytes, kernels}}.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))


def compile_cell(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from bench import cells, harness
    from repro.kernels import ops
    from repro.launch import train as launcher
    from repro.launch.engine import Engine
    from repro.models.config import InputShape
    from repro.optim import OptConfig, piecewise_linear

    ops.interpret_mode = lambda: False     # the target is the TPU
    cell = cells.load_cell(name)
    t = cell.traffic
    args = launcher.parse_args(harness.launcher_argv(cell, 0))
    cfg = launcher.get_config(args.arch)
    mesh = Mesh(np.array(topo.devices[:cell.chips]).reshape(cell.chips, 1),
                ("data", "model"))
    eng = Engine(cfg, mesh, comp=launcher.build_compression(args),
                 opt=OptConfig(name=args.optimizer, lr=args.lr))
    sched = piecewise_linear(args.lr, args.steps, max(1, args.steps // 10))
    step = eng.build_train_step(sched, wire=args.wire,
                                collective=args.collective)
    shape = InputShape("train", int(t["seq"]), int(t["batch"]), "train")
    (params, opt, batch, idx), _ = eng.train_input_specs(shape)
    if "frames" in batch:   # the benchmark feeds float32 frames
        f = batch["frames"]
        batch["frames"] = jax.ShapeDtypeStruct(f.shape, jnp.float32,
                                               sharding=f.sharding)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, batch, idx).compile()
    ma = compiled.memory_analysis()
    return {"seconds": time.perf_counter() - t0,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "pallas_kernels": compiled.as_text().count("tpu_custom_call")}


def main(argv=None) -> int:
    names = argv if argv is not None else sys.argv[1:]
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    out = {}
    for name in names:
        out[name] = compile_cell(name, topo)
        print(name, out[name], file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
