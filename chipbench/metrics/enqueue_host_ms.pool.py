"""Host time of launching the megastep per migration tick: the program's
span ``leap.dispatch.enqueue`` around the ``migrator.megastep`` call until
it returns, summed over the traced window, over the ``leap.tick`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_tick_ms(ctx, "leap.dispatch.enqueue")
