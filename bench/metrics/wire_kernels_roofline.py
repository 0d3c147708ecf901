"""Share of the wire kernels' roofline: the least time for the HBM bytes
they must move (bench/wire_bytes.py, from the cell's unit sizes and codec
width; no FLOP bound applies to a pack) over their measured device time."""

UNIT = "%"


def read(ctx):
    s = ctx["summary"]
    need = ctx.get("wire_bytes_per_step")
    if not need or not ctx["steps"] or s["pallas_s"] <= 0:
        return None
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (s["pallas_s"] / ctx["steps"])
