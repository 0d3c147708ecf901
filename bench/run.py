#!/usr/bin/env python3
"""Run one benchmark cell on the TPU chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. Set-up builds the job through the program's
launcher, gives it the seed's weights and batches, and drives the first
steps, which the plain reference then follows (they are also the warm-up;
the first run in a checkout compiles). The window runs the train step back
to back for --seconds. With --trace 0 the result reports the cell's
end-to-end metrics; with --trace 1 the window runs under the profiler and
the result reports the per-layer metrics that bench/metrics/ reads from
its trace. After the window the program's state is freed and the
reference decides `correct`.

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cells, compare, trace_reduce, wire_bytes  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default="",
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    return ap.parse_args(argv)


def per_layer(cell, run_info, trace_dir, peaks) -> tuple:
    devs = trace_reduce.load(trace_dir)
    summ = trace_reduce.summary(devs)
    codec = cell.traffic.get("codec")
    shapes = {p: tuple(s[0]) for p, s in
              cell.reference().param_specs(cell.config).items()}
    ctx = {"summary": summ, "devices": devs, "steps": run_info["steps"],
           "chips": cell.chips, "peaks": peaks, "cell": cell,
           "flops_per_step": cell.flops_per_step(),
           "wire_bytes_per_step": (wire_bytes.step_bytes(
               codec, shapes, cell.chips) if codec else None)}
    metrics = {}
    for name, reader in cells.metric_readers(cell.bench).items():
        v = reader.read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": reader.UNIT}
    breakdown = {"device_ops": trace_reduce.top_ops(devs),
                 "idle_gaps": idle_gaps(devs)}
    return metrics, summ, breakdown


def idle_gaps(devs, k: int = 10):
    """The longest idle gaps of the first device, each named by the
    operation that ended last before it."""
    if not devs:
        return []
    d = devs[0]
    by_end = sorted(d.ops, key=lambda o: o.end)
    ends = [o.end for o in by_end]
    longest = sorted(trace_reduce.gaps(d.intervals(), d.window),
                     key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in longest:
        i = bisect.bisect_right(ends, s) - 1
        label = trace_reduce.short_name(by_end[i].name) if i >= 0 \
            else "window start"
        out.append([f"after {label}", (e - s) / 1e9])
    return out


def run_cell(cell, seed: int, seconds: float, trace: int, *,
             require_tpu: bool = True, break_step=None, job=None,
             trace_dir: str = "") -> dict:
    import jax
    from bench import harness
    devices = harness.device_check(cell.chips, require_tpu)
    kind = devices[0].device_kind
    peaks = cells.peaks(kind, cell.bench) if require_tpu else None
    harness.enable_compile_cache(persistent=require_tpu)

    run = harness.build(cell, seed, break_step=break_step, job=job)
    prog = harness.first_steps(run)
    setup_s = time.perf_counter() - T_START

    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
    try:
        info = harness.window(run, seconds,
                              int(cell.workload["trace_steps"])
                              if trace else None, annotate=bool(trace))
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak = harness.peak_bytes(devices)
    harness.log(f"memory {devices[0].memory_stats()}")
    host_batches = run.host_batches
    harness.free(run)

    t_ref = time.perf_counter()
    ref = cell.reference()
    from bench.reference._common import train_readings
    ref_r = train_readings(ref, cell.config, cell.traffic, cell.chips,
                           host_batches, seed,
                           int(cell.traffic["first_step"]),
                           steps=int(cell.traffic["reference_steps"]))
    print(f"bench: setup {setup_s:.3f} s, window {info['steps']} steps in "
          f"{info['seconds']:.3f} s, reference "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    print(f"bench: losses program {prog['losses']} reference "
          f"{ref_r['losses']}", file=sys.stderr)
    for p in sorted(prog["grad_norms"]):
        harness.log(f"leaf {p}: grad {prog['grad_norms'][p]!r} ref "
                    f"{ref_r['grad_norms'][p]!r} change "
                    f"{prog['update_norms'][p]!r} ref "
                    f"{ref_r['update_norms'][p]!r} true grad "
                    f"{ref_r['true_grad_norms'][p]!r}")
    nums = compare.numbers(prog, ref_r)
    harness.log(f"numbers {nums}")
    chk = compare.checks(nums, cell.workload["limits"])
    failed = sum(1 for x in info["losses"] if not math.isfinite(x))
    correct = (compare.passed(chk) and failed == 0 and info["steps"] > 0)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": info["steps"],
           "failed": failed}
    if trace:
        t_tr = time.perf_counter()
        metrics, summ, breakdown = per_layer(cell, info, tmp, peaks)
        harness.log(f"trace read in {time.perf_counter() - t_tr:.3f} s")
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        if not trace_dir:
            shutil.rmtree(tmp, ignore_errors=True)
        out.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        tokens = info["steps"] * cell.tokens_per_step()
        out.update(metrics={
            "tokens_per_s": {"value": tokens / info["seconds"],
                             "unit": "tokens/s"},
            "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"}}, device=device)
    out["checks"] = chk
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cells.load_cell(args.workload)
    out = run_cell(cell, args.seed, args.seconds, args.trace,
                   trace_dir=args.trace_dir)
    for name, c in out["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
