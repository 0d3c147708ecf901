"""Model FLOPs utilisation of the whole train step: the configuration's
model FLOPs per step (bench/configs/<config>.flops.py, from published
shapes) times the steps traced, over the traced window, over the chips'
bf16 peak (bench/peaks.json)."""

UNIT = "%"


def read(ctx):
    s = ctx["summary"]
    if not s["devices"] or s["window_s"] <= 0 or not ctx["steps"]:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_step"] * ctx["steps"] / s["window_s"] / peak
