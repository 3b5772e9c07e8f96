"""The per-layer metrics that read the program's own spans: the shared sums
on a host event list built by hand, and a traced run of the tiny cell."""

import pytest

from chipbench import program_spans, run
from chipbench.run import Context
from chipbench.trace import Trace

MS = 1_000_000  # ns
TICK_METRICS = ("verdict_host_ms.pool", "schedule_host_ms.pool", "operands_host_ms.pool",
                "enqueue_host_ms.pool")
SPAN_METRICS = TICK_METRICS + ("write_host_ms.pool",)


def _ctx(host, lo=10 * MS, hi=100 * MS):
    return Context({}, None, Trace([], host), lo, hi, None)


HOST = [
    ("window", 10 * MS, 90 * MS),
    ("tick", 19 * MS, 12 * MS),  # the benchmark's span, not the program's
    ("leap.tick", 5 * MS, 4 * MS),  # starts before the window
    ("leap.tick", 20 * MS, 10 * MS),
    ("leap.tick", 40 * MS, 10 * MS),
    ("leap.tick", 99 * MS, 10 * MS),  # starts inside, ends after
    ("leap.tick", 100 * MS, 10 * MS),  # starts at the window's end
    ("leap.verdict.harvest", 6 * MS, 1 * MS),
    ("leap.verdict.harvest", 21 * MS, 2 * MS),
    ("leap.verdict.harvest", 41 * MS, 4 * MS),
    ("leap.budget.open_tick", 24 * MS, 1 * MS),
    ("leap.dispatch.plan", 25 * MS, 2 * MS),
    ("leap.verdict.harvest#blocking=1#", 42 * MS, 1 * MS),
    ("leap.write", 15 * MS, 3 * MS),
    ("leap.write", 35 * MS, 1 * MS),
    ("write", 14 * MS, 5 * MS),
]


def test_sums_take_exact_names_that_start_in_the_window_over_the_ticks():
    ctx = _ctx(HOST)
    assert program_spans.durations_ns(ctx, "leap.tick") == [10 * MS, 10 * MS, 10 * MS]
    # (2 + 4) ms of harvest over 3 ticks; the one before the window and the
    # name carrying metadata do not count
    assert program_spans.per_tick_ms(ctx, "leap.verdict.harvest") == pytest.approx(2.0)
    assert program_spans.per_tick_ms(
        ctx, "leap.dispatch.commit_ready", "leap.budget.open_tick", "leap.dispatch.plan"
    ) == pytest.approx(1.0)
    assert program_spans.per_span_ms(ctx, "leap.write") == pytest.approx(2.0)
    assert run.load_reader(run.ROOT, "verdict_host_ms.pool")(ctx) == pytest.approx(2.0)
    assert run.load_reader(run.ROOT, "write_host_ms.pool")(ctx) == pytest.approx(2.0)


def test_no_program_span_or_no_tick_gives_no_number():
    bench_only = [e for e in HOST if not e[0].startswith("leap.")]
    for name in SPAN_METRICS:
        assert run.load_reader(run.ROOT, name)(_ctx(bench_only)) is None, name
    no_ticks = [e for e in HOST if e[0] != "leap.tick"]
    assert program_spans.per_tick_ms(_ctx(no_ticks), "leap.verdict.harvest") is None
    assert program_spans.per_tick_ms(_ctx(HOST), "leap.dispatch.enqueue") is None
    assert program_spans.per_span_ms(_ctx(HOST, lo=50 * MS), "leap.write") is None


def test_traced_tiny_cell_reports_every_program_span_metric(tiny_root):
    out = run.run_cell(tiny_root, "pool.tiny", 2**31 + 11, 0.5, True, require_tpu=False)
    assert out["correct"]
    values = {name: out["metrics"][name]["value"] for name in SPAN_METRICS}
    assert all(v > 0 for v in values.values()), values
    # the four tick stages are siblings inside leap.tick, so they sum to
    # less than the benchmark's own span around the whole tick
    assert sum(values[n] for n in TICK_METRICS) < out["metrics"]["tick_host_ms.pool"]["value"]
