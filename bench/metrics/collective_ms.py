"""Device time per step of the collective operations (the ring's
collective-permutes, and any all-reduce, all-gather or reduce-scatter),
averaged over the chips: bench/trace_reduce.py's `collective_s`. None on
one chip, where nothing is exchanged."""

UNIT = "ms"


def read(ctx):
    s = ctx["summary"]
    if not ctx["steps"] or s["collective_s"] <= 0:
        return None
    return 1e3 * s["collective_s"] / ctx["steps"]
