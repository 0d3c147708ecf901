"""On-chip benchmark of the compressed data-parallel train step.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of BENCHMARK.json on the TPU chips of this host. Every cell,
configuration, traffic mix and per-layer metric is a file of its own under
this directory, found by its name.
"""
