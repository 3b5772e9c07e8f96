"""Device time of one decode step: the XLA module ``jit_paged_decode_step``
(``repro.serving.engine``) on the first chip, summed over the traced window,
over the number of them."""

from chipbench import trace


def read(ctx):
    if not ctx.devices():
        return None
    ns, n = trace.module_time_ns(ctx.devices()[0], r"paged_decode_step", ctx.lo, ctx.hi)
    return ns * 1e-6 / n if n else None
