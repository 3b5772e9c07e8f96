"""Host time of one admission: the program's span ``leap.serve.admit``
around ``PagedEngine.admit()`` (the prefill, its first token, and the page
install through ``MigrationDriver.write``), mean over the traced window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_span_ms(ctx, "leap.serve.admit")
