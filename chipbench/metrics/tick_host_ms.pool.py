"""Host time of one migration tick: the benchmark's span around
``LeapSession.tick()``, summed over the window, over the ticks."""


def read(ctx):
    n = ctx.spans.count.get("tick", 0)
    return 1e3 * ctx.spans.total_s["tick"] / n if n else None
