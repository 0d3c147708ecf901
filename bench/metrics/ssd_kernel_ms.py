"""Device time per step of the SSD kernel pair: the Pallas kernels
(tpu_custom_call) whose operation name starts with `ssd_` (the forward,
the forward that keeps the chunk states, the backward; `kernels/ssd.py`),
averaged over the chips. None where no such kernel ran, as in a program
that runs the SSD as XLA operations."""
from bench import trace_reduce

UNIT = "ms"


def read(ctx):
    devs = ctx["devices"]
    if not devs or not ctx["steps"]:
        return None
    total = 0.0
    for d in devs:
        ivs = [(o.start, o.end) for o in d.ops if o.kind == "pallas"
               and trace_reduce.short_name(o.name).startswith("ssd_")]
        total += trace_reduce.length(trace_reduce.clip(ivs, d.window)) / 1e9
    if total <= 0:
        return None
    return 1e3 * total / len(devs) / ctx["steps"]
