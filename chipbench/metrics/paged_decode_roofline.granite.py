"""Roofline share of the ``paged_decode`` Pallas kernel: the least time the
chip needs to read the KV it must read in the window (for each page a
sequence attends over, at every step, each layer's ``[2, 16, 512]`` bf16
slab: ``chipbench/serve_work.py``, from the program's counter of pages read)
at the HBM peak, over the kernel's summed time, in %."""

from chipbench import peaks, trace


def read(ctx):
    nbytes = ctx.facts.get("kv_bytes_read")
    if not ctx.devices() or not nbytes:
        return None
    ns, n = trace.op_time_ns(ctx.devices()[0], r"^paged_decode", ctx.lo, ctx.hi)
    if not n:
        return None
    return peaks.roofline_share(0.0, nbytes, ns * 1e-9, ctx.peaks)
