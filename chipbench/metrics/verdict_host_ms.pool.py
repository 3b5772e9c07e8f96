"""Host time of the verdict stage per migration tick: the program's span
``leap.verdict.harvest`` (``core/pipeline/verdict.py``), summed over the
traced window, over the ``leap.tick`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_tick_ms(ctx, "leap.verdict.harvest")
