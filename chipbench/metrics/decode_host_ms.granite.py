"""Host time of one decode step of the batch: the program's span
``leap.serve.decode`` around ``PagedEngine.decode()`` (page allocation,
tables, the step's launch, the wait for its tokens), mean over the traced
window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_span_ms(ctx, "leap.serve.decode")
