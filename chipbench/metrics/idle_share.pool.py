"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses, in %."""

from chipbench import trace


def read(ctx):
    devs = ctx.devices()
    if not devs:
        return None
    busy = sum(trace.busy_ns(d, ctx.lo, ctx.hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ctx.window_ns)
