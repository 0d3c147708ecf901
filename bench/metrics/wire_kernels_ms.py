"""Device time per step of the Pallas kernels (tpu_custom_call), averaged
over the chips. The model path runs no Pallas kernel, so each one is a
wire kernel: compress-and-pack or unpack-and-decode."""

UNIT = "ms"


def read(ctx):
    s = ctx["summary"]
    if not ctx["steps"] or s["pallas_s"] <= 0:
        return None
    return 1e3 * s["pallas_s"] / ctx["steps"]
