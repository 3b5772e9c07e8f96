"""Host time of one foreground write: the program's span ``leap.write``
around ``MigrationDriver.write()`` (dirty-tracking bookkeeping, the id
transfer and the ``leap_write`` enqueue), mean over the traced window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_span_ms(ctx, "leap.write")
