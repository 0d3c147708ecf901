"""Share of the traced window in which no operation ran on the device:
100 x (1 - busy union / window), averaged over the cell's chips."""

UNIT = "%"


def read(ctx):
    s = ctx["summary"]
    if not s["devices"] or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
