"""Roofline share of the ``leap_copy_blocks`` DMA kernel: the least time
the chip needs to read and write every byte the engine copied in the window
(retries included), at the HBM peak, over the summed kernel time, in %."""

from chipbench import flops, peaks, trace


def read(ctx):
    if not ctx.devices():
        return None
    ns, n = trace.op_time_ns(ctx.devices()[0], r"leap_copy_blocks", ctx.lo, ctx.hi)
    if not n:
        return None
    return peaks.roofline_share(0.0, flops.copy_bytes(ctx.facts["bytes_copied"]), ns * 1e-9,
                                ctx.peaks)
