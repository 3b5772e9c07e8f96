"""Host time of one rebalance: the program's span ``leap.serve.rebalance``
around ``PagedEngine.rebalance()`` (the placement policy and the sequence's
leap request to the session), mean over the traced window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_span_ms(ctx, "leap.serve.rebalance")
