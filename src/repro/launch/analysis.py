"""Compiled-artifact analysis: collective-byte parsing and the three-term
roofline (compute / memory / collective) from the dry-run.

Hardware model (TPU v5e target, per assignment):
  peak bf16        197 TFLOP/s per chip
  HBM bandwidth    819 GB/s per chip
  ICI link         ~50 GB/s per link
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional, Tuple

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every 'dtype[dims]' group in an HLO result type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind result bytes of every collective in (per-device) HLO.

    We take the RESULT shape as the wire proxy: for all-reduce it equals the
    payload; for all-gather it is the received total; for reduce-scatter the
    sent total is result x n (we report result — conservative).
    'xxx-start' variants (async) are counted; '-done' are not.
    """
    out = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "fusion" in s.split("=")[0]:
            continue
        m = re.match(r"%?[\w.\-]+ = (.+?) (" + "|".join(COLLECTIVE_OPS) +
                     r")(-start)?\(", s)
        if m:
            out[m.group(2)] += _shape_bytes(m.group(1))
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    collective_bytes_per_device: float
    collective_breakdown: Dict[str, float]
    model_flops_global: float
    memory_per_device: Dict[str, float]
    raw_cost_analysis: Optional[Dict[str, float]] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops_per_device / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops_per_device * self.chips
        return self.model_flops_global / total if total else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio)
        return d


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS per step: 6·N·D train, 2·N·D forward (N = active params,
    D = tokens processed globally)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def analyze_compiled(compiled, *, arch: str, shape, mesh_name: str,
                     chips: int, cfg) -> Roofline:
    from repro.launch.hlo_cost import scan_scaled_costs
    cost = compiled.cost_analysis()
    raw = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = float(v)
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)
    text = compiled.as_text()
    # scan-scaled per-device costs (XLA's cost_analysis counts while-loop
    # bodies ONCE — useless for scan-over-layers models; see hlo_cost.py)
    sc = scan_scaled_costs(text, default_group=chips)
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops_per_device=sc["flops"], hlo_bytes_per_device=sc["bytes"],
        collective_bytes_per_device=sum(sc["collectives"].values()),
        collective_breakdown=sc["collectives"],
        model_flops_global=model_flops(cfg, shape),
        memory_per_device=mem,
        raw_cost_analysis=raw)


def save_roofline(r: Roofline, path: str):
    with open(path, "w") as f:
        json.dump(r.to_dict(), f, indent=2)
