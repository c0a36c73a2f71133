"""Share of the traced window in which no operation ran on the device:
1 − (union of program executions ÷ window), the mean over the chips."""
from chipbench import trace as tr


def read(ctx):
    devs = ctx.trace.devices
    if not devs or ctx.trace.window_ns <= 0:
        return None
    busy = [tr.busy_ns(ctx.trace, d) for d in devs]
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.trace.window_ns)
