"""The whisper ring on four virtual CPU devices, in a process of its own
(the device count is set before jax starts). Run by
bench/tests/test_bench_whisper.py; prints one JSON line.

  cell    the smoke whisper (float32) through the benchmark's timed path
          on four workers, QSGD-16 per layer over the streaming ring
          (--wire --collective ring), against the plain reference's
          four-worker readings: the numbers bench/compare.py compares
  hops    one engine train step of the same job under an enabled
          TraceRecorder: per wire message, the ring's hop spans (category
          `collective`) and the bytes each carries, beside what the
          schedule states; and whether the step's compiled program names
          the encoder stack and the cross-attention
"""
import json
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bench_smoke_cells as smoke  # noqa: E402
import whisper_smoke_cell as wcell  # noqa: E402
from bench import cells, harness, run  # noqa: E402

SEED = 2 ** 31 + 4242
#: every number the comparison reads, each with a limit no reading
#: reaches, so that all three are printed
LIMITS = {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}


def cell_numbers():
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        root = smoke.copy_bench(tmp)
        wcell.write(root, LIMITS)
        cell = cells.load_cell(wcell.NAME, bench=root)
        harness.enable_compile_cache(persistent=False)
        out = run.run_cell(cell, SEED, 0.2, 0, require_tpu=False)
    nums = {k: c["value"] for k, c in out["checks"].items()}
    return {"numbers": nums, "attempted": out["attempted"],
            "failed": out["failed"], "devices": out["device"]["count"]}


def hop_spans():
    from repro.configs.registry import get_smoke
    from repro.core import CompressionConfig, Granularity, make_compressor
    from repro.core.schedule import build_schedule
    from repro.core.wire import message_layouts, wire_codec
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh
    from repro.models.config import InputShape
    from repro.obs import TraceRecorder

    cfg = get_smoke("whisper-base")
    comp = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                             granularity=Granularity("layerwise"))
    eng = Engine(cfg, make_host_mesh(4, 1), comp=comp)
    rec = TraceRecorder()
    step = eng.build_train_step(wire=True, collective="ring", tracer=rec)
    params, opt = eng.init_state(0)
    shape = InputShape("train", 16, 8, "train")
    batch = {k: jnp.zeros(s.shape, s.dtype) + (3 if k != "frames" else 0)
             for k, s in eng.batch_shapes(shape).items()}
    text = step.lower(params, opt, batch, jnp.int32(0)).as_text(
        debug_info=True)
    out = step(params, opt, batch, jnp.int32(0))
    jax.block_until_ready(out)
    rec.finalize_step(0, dedupe=True)
    rest_plan, _ = eng.comm_plans()
    layouts = message_layouts(build_schedule(rest_plan, 0.0),
                              wire_codec(comp.qw))
    hops = {}
    for e in rec.span_events(step=0):
        if e["args"]["stage"] == "hop":
            hops.setdefault(e["args"]["message"], []).append(
                [e["cat"], e["args"]["nbytes"]])
    return {"hops": {str(k): v for k, v in sorted(hops.items())},
            "message_bytes": [int(l.total_nbytes) for l in layouts],
            "scopes": {s: s in text for s in ("repro/encoder",
                                              "repro/cross_attention")}}


if __name__ == "__main__":
    print(json.dumps({"cell": cell_numbers(), "trace": hop_spans()}),
          flush=True)
