#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set: a script, not
part of a run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange] \
        [--fault-seeds 1,2,3] [--witness-seeds 1,2] [--out raw.json]

On the cell's chips, in one process (one compile of the step): the
program's first steps on every seed; then, with the program's state
freed, the float32 reference on every seed, the control (the reference in
the program's place with its contractions one precision below the
configuration's) on the control seeds, each planted fault (in the
reference put in the program's place) on the fault seeds, and the
witness (the reference computed at the configuration's own precision,
bf16 for bf16: what rounding alone does to the numbers on a seed) on the
witness seeds. Prints one JSON line per reading: the numbers
bench/compare.py compares, for the program (lower readings), the control
and each fault (upper readings), and the witness.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cells, compare  # noqa: E402


def seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--witness-seeds", type=seeds, default=[])
    ap.add_argument("--out", default="",
                    help="also write every raw reading (per leaf) here, "
                         "as JSON, so that numbers can be read anew")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.reference._common import (control_precision,
                                         own_precision, train_readings)
    cell = cells.load_cell(args.workload)
    harness.device_check(cell.chips)
    harness.enable_compile_cache()
    from repro.launch import train as launcher
    with contextlib.redirect_stdout(sys.stderr):
        job = launcher.prepare(launcher.parse_args(
            harness.launcher_argv(cell, 0)))
    prog, hosts = {}, {}
    for s in args.seeds:
        t0 = time.perf_counter()
        run = harness.build(cell, s, job=job)
        prog[s] = harness.first_steps(run)
        hosts[s] = run.host_batches
        run.params = run.opt_state = run.batches = None
        print(f"program seed {s}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    job.step_fn = None
    del job, run
    harness.free_all()

    ref = cell.reference()
    t, n = cell.traffic, int(cell.traffic["reference_steps"])

    def readings(s, precision="f32", fault=None):
        t0 = time.perf_counter()
        r = train_readings(ref, cell.config, t, cell.chips, hosts[s], s,
                           int(t["first_step"]), precision=precision,
                           steps=n, fault=fault)
        print(f"reference {precision} {fault} seed {s}: "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        return r

    raw = {}

    def dump():
        if args.out:
            with open(args.out, "w") as f:
                json.dump(raw, f)

    for s in args.seeds:
        r = readings(s)
        raw[s] = {"program": prog[s], "reference": r}
        line = {"cell": cell.name, "seed": s, "what": "program",
                **compare.numbers(prog[s], r)}
        print(json.dumps(line), flush=True)
        if s in args.control_seeds:
            prec = control_precision(cell.config["dtype"])
            c = readings(s, prec)
            raw[s]["control_" + prec] = c
            print(json.dumps({"cell": cell.name, "seed": s,
                              "what": "control_" + prec,
                              **compare.numbers(c, r)}), flush=True)
        if s in args.witness_seeds:
            prec = own_precision(cell.config["dtype"])
            c = readings(s, prec)
            raw[s]["witness_" + prec] = c
            print(json.dumps({"cell": cell.name, "seed": s,
                              "what": "witness_" + prec,
                              **compare.numbers(c, r)}), flush=True)
        if s in args.fault_seeds:
            for f in [x for x in args.faults.split(",") if x]:
                fr = readings(s, fault=f)
                raw[s]["fault_" + f] = fr
                print(json.dumps({"cell": cell.name, "seed": s,
                                  "what": "fault_" + f,
                                  **compare.numbers(fr, r)}), flush=True)
        dump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
