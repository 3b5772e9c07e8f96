"""Host time of budgeting and scheduling per migration tick: the program's
spans ``leap.dispatch.commit_ready``, ``leap.budget.open_tick`` and
``leap.dispatch.plan`` (``core/pipeline/budget.py``, ``dispatch.py``),
summed over the traced window, over the ``leap.tick`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per_tick_ms(ctx, "leap.dispatch.commit_ready",
                                     "leap.budget.open_tick", "leap.dispatch.plan")
