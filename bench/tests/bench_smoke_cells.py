"""Smoke-size cells for the benchmark's CPU tests: the program's own
--smoke configuration of mamba2, written as benchmark files into a copy
of bench/ (so the tests also show that a cell, a
configuration and a traffic mix are files the harness finds by name)."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAMBA2 = {
    "name": "mamba2-smoke", "arch": "mamba2-1.3b", "smoke": True,
    "reference": "mamba2-1.3b", "d_model": 128, "n_layer": 2,
    "vocab_size": 512, "vocab_padded": 512, "d_state": 16, "d_conv": 4,
    "expand": 2, "headdim": 32, "ngroups": 1, "chunk_size": 8,
    "norm_eps": 1e-05, "dtype": "float32"}


def traffic(launcher, codec, batch, seq):
    return {"batch": batch, "seq": seq, "ring": 3, "optimizer": "sgd",
            "lr": 0.5, "steps": 1000, "first_step": 100,
            "reference_steps": 3, "launcher": launcher, "codec": codec}


QSGD = {"name": "qsgd", "levels": 16, "granularity": "layerwise"}
WIRE = ["--wire"]

CELLS = {
    "mamba2-smoke.dense": (MAMBA2, traffic([], None, 4, 32)),
    "mamba2-smoke.qsgd16-layerwise-wire": (MAMBA2, traffic(WIRE, QSGD, 4,
                                                           32)),
}


def write(root: str, name: str, limits=None, chips: int = 1) -> None:
    cfg, tr = CELLS[name]
    tname = name + ".traffic"
    for sub, fname, obj in (
            ("configs", cfg["name"], cfg), ("traffic", tname, tr),
            ("workloads", name, {"config": cfg["name"], "traffic": tname,
                                 "chips": chips, "trace_steps": 2,
                                 "limits": limits or {}})):
        with open(os.path.join(root, sub, fname + ".json"), "w") as f:
            json.dump(obj, f)


def copy_bench(dst: str) -> str:
    """A copy of bench/ without its tests, at dst/bench."""
    root = os.path.join(dst, "bench")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    return root
