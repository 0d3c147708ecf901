"""Device time per step of the collective operations during which no
other operation ran, on the chip where it is largest:
bench/trace_reduce.py's `collective_exposed_s`. None where no collective
ran."""

UNIT = "ms"


def read(ctx):
    s = ctx["summary"]
    if not ctx["steps"] or s["collective_s"] <= 0:
        return None
    return 1e3 * s["collective_exposed_s"] / ctx["steps"]
