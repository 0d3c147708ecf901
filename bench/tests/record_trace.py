#!/usr/bin/env python3
"""Record the small chip trace that test_bench_trace_reduce.py reduces:
two traced steps of the smoke mamba2 configuration with QSGD-16 wire
messages, on one chip (the fused Pallas pack and unpack kernels appear).

    python3 bench/tests/record_trace.py <out dir>

Writes <out dir>/.../*.xplane.pb and prints the summary the test holds.
"""
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import bench_smoke_cells as smoke  # noqa: E402
from bench import cells, trace_reduce  # noqa: E402

NAME = "mamba2-smoke.qsgd16-layerwise-wire"


def main(out: str) -> int:
    from bench import run
    tmp = tempfile.mkdtemp(prefix="bench_smoke_")
    root = smoke.copy_bench(tmp)
    smoke.write(root, NAME)
    cell = cells.load_cell(NAME, bench=root)
    cell.workload["trace_steps"] = 2
    res = run.run_cell(cell, 7, 1.0, 1, trace_dir=out)
    shutil.rmtree(tmp, ignore_errors=True)
    devs = trace_reduce.load(out)
    print(json.dumps({"summary": trace_reduce.summary(devs),
                      "metrics": res["metrics"],
                      "xplane": trace_reduce.find_xplane(out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
