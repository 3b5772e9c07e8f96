"""The program's own host spans in a traced window.

The migration pipeline marks each of its stages with a profiler annotation
named ``leap.<stage>`` (``repro.obs.recorder``): ``leap.tick`` around
``MigrationDriver.tick()``, its stages inside it, and ``leap.write`` around
``MigrationDriver.write()``.  The per-layer metrics of the host pipeline sum
them here.  A span counts when it starts in the window, on any host thread,
and only under its exact name.  A program without these spans gives no
number: every function here returns None.
"""

from __future__ import annotations

TICK = "leap.tick"


def durations_ns(ctx, name: str) -> list:
    """Durations of the host spans named exactly ``name`` that start in
    ``[ctx.lo, ctx.hi)``."""
    return [d for n, s, d in ctx.trace.host if n == name and ctx.lo <= s < ctx.hi]


def per_tick_ms(ctx, *names: str) -> float | None:
    """Summed duration of the spans ``names`` over the number of
    ``leap.tick`` spans, in ms."""
    ticks = len(durations_ns(ctx, TICK))
    durs = [d for name in names for d in durations_ns(ctx, name)]
    if not ticks or not durs:
        return None
    return sum(durs) * 1e-6 / ticks


def per_span_ms(ctx, name: str) -> float | None:
    """Mean duration of the spans ``name``, in ms."""
    durs = durations_ns(ctx, name)
    return sum(durs) * 1e-6 / len(durs) if durs else None
