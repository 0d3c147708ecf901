"""Find a cell's files by name: its workload, configuration, traffic mix,
plain reference, FLOP function and the per-layer metric readers.

    bench/workloads/<cell>.json       chips, trace steps, correctness limits
    bench/configs/<config>.json       published sizes of the model
    bench/configs/<config>.flops.py   model FLOPs of one train step
    bench/reference/<config>.py       plain float32 reference
    bench/traffic/<traffic>.json      batch stream and training job
    bench/metrics/<metric>.py         one per-layer metric reader each

Adding a cell, a configuration or a metric adds files here and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import importlib.util
import json
import os
import re
from typing import Any, Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """Import a file by path (names such as mamba2-1.3b.py are not valid
    module names), once per process."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload: a configuration under a traffic mix on 1 or 4 chips."""
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    bench: str = BENCH

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def reference(self):
        """bench/reference/<config>.py; a configuration that is a
        cut-down size of another names that one's under "reference"."""
        name = self.config.get("reference", self.workload["config"])
        return load_module(os.path.join(self.bench, "reference",
                                        name + ".py"))

    def flops_per_step(self) -> float:
        name = self.config.get("reference", self.workload["config"])
        mod = load_module(os.path.join(self.bench, "configs",
                                       name + ".flops.py"))
        return float(mod.flops_per_step(self.config, self.traffic))

    def tokens_per_step(self) -> int:
        return int(self.traffic["batch"]) * int(self.traffic["seq"])


def load_cell(name: str, bench: str = BENCH) -> Cell:
    path = os.path.join(bench, "workloads", name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"no workload {name!r}: {path} does not exist")
    wl = _read_json(path)
    cfg = _read_json(os.path.join(bench, "configs", wl["config"] + ".json"))
    traffic = _read_json(os.path.join(bench, "traffic",
                                      wl["traffic"] + ".json"))
    return Cell(name, wl, cfg, traffic, bench)


def metric_readers(bench: str = BENCH) -> Dict[str, Any]:
    """{metric name: reader module} for every bench/metrics/<name>.py."""
    out = {}
    for path in sorted(glob.glob(os.path.join(bench, "metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        if not name.startswith("_"):
            out[name] = load_module(path)
    return out


def peaks(device_kind: str, bench: str = BENCH) -> Dict[str, float]:
    """Published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = _read_json(os.path.join(bench, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
