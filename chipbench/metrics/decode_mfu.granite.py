"""Model FLOP/s utilization of serving: the model operations of the tokens
decoded and prefilled in the window (``chipbench/serve_work.py``: 2 x the
parameters each token's matrix products touch, tied head included where its
logits are computed, plus its attention over the tokens before it), over
the window's host-clock seconds and the chip's bf16 peak, in %.

The operations are counted on the host and the time is the host's window,
so the number reads no device time; it is reported with the traced run's
metrics, and only where the trace shows a device with a known peak."""


def read(ctx):
    if not ctx.devices() or ctx.peaks is None:
        return None
    return 100.0 * ctx.facts["model_flops"] / (ctx.facts["window_s"] * ctx.peaks.bf16_flops)
