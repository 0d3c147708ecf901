"""Share of the ring's link roofline: the least time for the bytes one
chip sends over the ring in a step (bench/ring_bytes.py, from the cell's
unit sizes and codec width) at the chip's whole interchip bandwidth
(bench/peaks.json `ici_bits_per_s`), over the time a step has a
collective in flight, averaged over the chips.

A collective in flight is the span from an asynchronous collective's
start operation to the end of the done operation that waits for it (a
synchronous one: its own span). The bytes cannot cross the links faster
than the peak while they are in flight, so the share cannot pass 100%.
`collective_ms` counts only the start and done operations themselves,
which is less wherever the core computes while the bytes move. None
where the cell runs no ring or no collective ran."""
import re

from bench import ring_bytes
from bench import trace_reduce as tr

UNIT = "%"

#: the operand of a done operation: the start it waits for
_DONE_OF = re.compile(r"-done\([^%]*%([\w.\-]+)")


def in_flight_s(dev) -> float:
    """Seconds of the device's window with a collective in flight."""
    coll = [o for o in dev.ops if o.kind == "collective"]
    starts = {tr.short_name(o.name): o for o in coll}
    ivs = []
    for o in coll:
        m = _DONE_OF.search(o.name)
        s = starts.get(m.group(1)) if m else None
        ivs.append((min(o.start, s.start) if s else o.start, o.end))
    return tr.length(tr.clip(ivs, dev.window)) / 1e9


def read(ctx):
    s, cell, devs = ctx["summary"], ctx.get("cell"), ctx["devices"]
    if (cell is None or "ring" not in cell.traffic.get("launcher", ())
            or not ctx["steps"] or s["collective_s"] <= 0):
        return None
    flight = sum(in_flight_s(d) for d in devs) / len(devs) / ctx["steps"]
    shapes = {p: tuple(sp[0]) for p, sp in
              cell.reference().param_specs(cell.config).items()}
    sent = ring_bytes.step_bytes(cell.traffic["codec"], shapes, ctx["chips"])
    least = 8.0 * sent / ctx["peaks"]["ici_bits_per_s"]
    return 100.0 * least / flight
