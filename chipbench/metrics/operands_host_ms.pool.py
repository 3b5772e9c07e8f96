"""Host time spent building the megastep's operands per migration tick:
the program's span ``leap.dispatch.operands`` (``core/pipeline/dispatch.py``:
concatenation, sentinel padding, host-to-device transfers), summed over the
traced window, over the ``leap.tick`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_tick_ms(ctx, "leap.dispatch.operands")
