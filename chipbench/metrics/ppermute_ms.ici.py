"""Device time of the cross-chip copy programs (``fused_copy_ppermute``,
``repro.core.migrator``) per migration tick, averaged over the chips."""

from chipbench import trace


def read(ctx):
    devs, ticks = ctx.devices(), ctx.facts["ticks"]
    if not devs or not ticks:
        return None
    per_chip = [trace.module_time_ns(d, r"ppermute", ctx.lo, ctx.hi) for d in devs]
    if not any(n for _, n in per_chip):
        return None
    return sum(ns for ns, _ in per_chip) / len(devs) * 1e-6 / ticks
