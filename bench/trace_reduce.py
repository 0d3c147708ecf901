"""Reduce a profiler trace (.xplane.pb) to device intervals.

For each TPU device plane, the "XLA Ops" line holds one event per
operation run on the chip, named by its HLO text, and the "XLA Modules"
line one per program run. Control-flow operations (while, conditional,
call) span the operations of their bodies, which have events of their
own; they are left out, so that the gaps inside a loop count as idle.
Asynchronous copies and collectives in flight ("Async XLA Ops") are not
operations of the core; their "-done" halves, which wait, are. Each
operation is classed as

  pallas      a Pallas kernel: a custom call to tpu_custom_call
  collective  all-reduce, all-gather, reduce-scatter, collective-permute,
              all-to-all, send/recv (start and done halves alike)
  other       everything else

The traced window of a device runs from the start of its first program
to the end of its last. Busy time is the union of its operations'
intervals inside that window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[int, int]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|"
                         r"\bsend\b|\brecv\b|send-done|recv-done")


@dataclasses.dataclass
class Op:
    start: int          # ns
    end: int            # ns
    name: str           # the HLO text of an operation
    kind: str           # pallas | collective | other (module: a program)


@dataclasses.dataclass
class DeviceTrace:
    device: int
    ops: List[Op]
    modules: List[Op]

    @property
    def window(self) -> Interval:
        spans = self.modules or self.ops
        return (min(o.start for o in spans), max(o.end for o in spans))

    def intervals(self, kind: str = None) -> List[Interval]:
        return [(o.start, o.end) for o in self.ops
                if kind is None or o.kind == kind]


def short_name(text: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def classify(text: str) -> str:
    """pallas | collective | other, from an operation's HLO text."""
    low = text.lower()
    if "tpu_custom_call" in low:
        return "pallas"
    if _COLLECTIVE.search(short_name(low)):
        return "collective"
    return "other"


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str) -> List[DeviceTrace]:
    """Every TPU device's operations and programs in the trace at `path`
    (an .xplane.pb, or a directory that holds one)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if not m:
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            is_ops = line.name == "XLA Ops"
            for ev in line.events:
                name = ev.name
                if is_ops and _CONTAINER.match(short_name(name)):
                    continue
                start = int(ev.start_ns)
                op = Op(start, start + int(ev.duration_ns), name,
                        classify(name) if is_ops else "module")
                (ops if is_ops else modules).append(op)
        if ops:
            out.append(DeviceTrace(int(m.group(1)), ops, modules))
    return sorted(out, key=lambda d: d.device)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(ivs: List[Interval]) -> int:
    return sum(e - s for s, e in union(ivs))


def clip(ivs: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def minus(a: List[Interval], b: List[Interval]) -> int:
    """Length of the union of `a` outside the union of `b`."""
    b = union(b)
    total, j = 0, 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                total += bs - cur
            cur = max(cur, be)
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(ivs: List[Interval], window: Interval) -> List[Interval]:
    """Idle intervals of the window: where no interval runs."""
    out, cur = [], window[0]
    for s, e in union(clip(ivs, window)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def summary(devices: List[DeviceTrace]) -> Dict[str, float]:
    """Seconds, averaged over the devices: traced window, busy union,
    Pallas kernel time, collective time, and collective time during which
    nothing else ran (the worst device)."""
    n = max(1, len(devices))
    win = busy = pallas = coll = 0.0
    exposed = 0.0
    for d in devices:
        w = d.window
        win += (w[1] - w[0]) / 1e9
        busy += length(clip(d.intervals(), w)) / 1e9
        pallas += length(clip(d.intervals("pallas"), w)) / 1e9
        coll += length(clip(d.intervals("collective"), w)) / 1e9
        other = d.intervals("other") + d.intervals("pallas")
        exposed = max(exposed, minus(clip(d.intervals("collective"), w),
                                     clip(other, w)) / 1e9)
    return {"window_s": win / n, "busy_s": busy / n,
            "pallas_s": pallas / n, "collective_s": coll / n,
            "collective_exposed_s": exposed, "devices": len(devices)}


def top_ops(devices: List[DeviceTrace], k: int = 10) -> List[List]:
    """The k operation names that took the most device time, in seconds
    averaged over the devices (numbered suffixes merged: fusion.12 ->
    fusion)."""
    tot: Dict[str, float] = {}
    for d in devices:
        for o in d.ops:
            key = re.sub(r"\.\d+$", "", short_name(o.name))
            if o.kind == "pallas":
                key = "pallas:" + key
            tot[key] = tot.get(key, 0.0) + (o.end - o.start) / 1e9
    n = max(1, len(devices))
    return [[name, s / n] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
