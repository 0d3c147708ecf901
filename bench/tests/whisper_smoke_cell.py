"""The smoke-size whisper cell for the benchmark's CPU tests: the
program's --smoke whisper (src/repro/configs/whisper_base.py SMOKE, float32)
on four workers, QSGD-16 per layer over the streaming ring, written as
benchmark files into a copy of bench/ beside bench_smoke_cells.py's."""
from __future__ import annotations

import json
import os

NAME = "whisper-smoke.qsgd16-layerwise-wire-ring"
WORKERS = 4

CONFIG = {
    "name": "whisper-smoke", "arch": "whisper-base", "smoke": True,
    "reference": "whisper-base", "d_model": 96, "encoder_layers": 2,
    "decoder_layers": 2, "encoder_attention_heads": 4,
    "decoder_attention_heads": 4, "encoder_ffn_dim": 192,
    "decoder_ffn_dim": 192, "vocab_size": 512, "vocab_padded": 512,
    "max_source_positions": 24, "max_target_positions": 448,
    "norm_eps": 1e-05, "dtype": "float32"}

TRAFFIC = {"batch": 8, "seq": 16, "frames": 24, "ring": 3,
           "optimizer": "sgd", "lr": 0.5, "steps": 1000, "first_step": 100,
           "reference_steps": 3,
           "launcher": ["--wire", "--collective", "ring"],
           "codec": {"name": "qsgd", "levels": 16,
                     "granularity": "layerwise"}}


def write(root: str, limits: dict) -> None:
    """The cell's files under root (a copy of bench/)."""
    tname = NAME + ".traffic"
    for sub, fname, obj in (
            ("configs", CONFIG["name"], CONFIG), ("traffic", tname, TRAFFIC),
            ("workloads", NAME, {"config": CONFIG["name"], "traffic": tname,
                                 "chips": WORKERS, "trace_steps": 2,
                                 "limits": limits})):
        with open(os.path.join(root, sub, fname + ".json"), "w") as f:
            json.dump(obj, f)
