"""Tier-1 tests for the telemetry subsystem (``repro.obs``).

Covers the recorder contract (bounded ring, exact counter totals, request
lifecycle spans, latency attribution), the overhead guard (a disabled
pipeline emits no ring event and shares the NULL_RECORDER; an enabled ring
stays bounded across a long drain), the stages' ``leap.<stage>`` spans on
the profiler clock, the Chrome-trace exporter
and its Perfetto schema validator, the metrics registry renderings, the
public accessors (session / sealed facade / handle latency), stats snapshot
independence, and the ``benchmarks.run --trace`` acceptance path end to end.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import LeapSession
from repro.core import LeapConfig, MigrationDriver, PoolConfig, init_state, leap_write
from repro.core.stats import MigrationStats
from repro.obs import (
    NULL_RECORDER,
    Histogram,
    TelemetryRecorder,
    TelemetryView,
    chrome_trace,
    make_recorder,
    summarize,
    validate_chrome_trace,
)

#: Counters mirrored through ``PipelineContext.count`` — the event log and
#: MigrationStats must agree on these exactly (drift-proof single write path).
MIRRORED = (
    "blocks_requested",
    "blocks_migrated",
    "blocks_forced",
    "blocks_cancelled",
    "bytes_copied",
    "dispatches",
)


def make(n_blocks=16, slots=24, n_regions=2, telemetry=True, **leap_kw):
    cfg = PoolConfig(n_regions, slots, (4,))
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    data = np.arange(n_blocks * 4, dtype=np.float32).reshape(n_blocks, 4)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    kw = dict(
        initial_area_blocks=4, chunk_blocks=2, budget_blocks_per_tick=4,
        telemetry=telemetry,
    )
    kw.update(leap_kw)
    drv = MigrationDriver(state, cfg, LeapConfig(**kw))
    return cfg, drv, LeapSession(drv)


def _fake_clock():
    """Deterministic microsecond-stepping clock for recorder units."""
    t = [0.0]

    def clock():
        t[0] += 1e-6
        return t[0]

    return clock


# ---------------------------------------------------------------------------
# Recorder contract
# ---------------------------------------------------------------------------


def test_recorder_stage_counter_and_event_families():
    rec = TelemetryRecorder(capacity=16, clock=_fake_clock())
    rec.begin_tick(3)
    with rec.stage("dispatch.run_tick", opened=2):
        pass
    rec.count("dispatches", 1, program="copy_chunk")
    rec.count("dispatches", 2)
    rec.event("jit", "jit_miss", n=1)
    kinds = [(e["kind"], e["name"]) for e in rec.events()]
    assert kinds == [
        ("stage", "dispatch.run_tick"),
        ("counter", "dispatches"),
        ("counter", "dispatches"),
        ("jit", "jit_miss"),
    ]
    stage = rec.events()[0]
    assert stage["tick"] == 3 and stage["dur"] > 0 and stage["args"] == {"opened": 2}
    assert rec.events()[2]["total"] == 3  # running total rides on the event
    assert rec.counter_totals() == {"dispatches": 3}


def test_recorder_request_span_lifecycle_and_outcomes():
    rec = TelemetryRecorder(clock=_fake_clock())
    rec.begin_tick(1)
    rec.request_submitted(7, dst_region=1, priority=2)
    rec.request_phase(7, "ADMITTED", n=8)
    rec.request_phase(7, "ROUTED", n=2)
    rec.begin_tick(2)
    rec.request_phase(7, "EPOCH_OPEN", n=4)
    rec.request_phase(7, "RETRY", n=1)
    rec.begin_tick(5)
    rec.request_resolved(7, committed=8, forced=0, cancelled=0, requested=8)
    (span,) = rec.request_spans()
    assert span.outcome == "COMMITTED" and span.requested == 8
    assert span.areas == 2 and span.epochs == 1 and span.retries == 1
    lat = rec.latency(7)
    assert lat.ticks_total == 4 and lat.queue_ticks == 1 and lat.copy_ticks == 3
    assert lat.queue_wall_s + lat.copy_wall_s == pytest.approx(lat.wall_s)
    # outcome classification on the other terminal shapes
    for committed, forced, cancelled, want in (
        (0, 0, 4, "CANCELLED"),
        (2, 0, 2, "PARTIAL"),
        (0, 4, 0, "FORCED"),
    ):
        rec.request_submitted(99, 0, 0)
        rec.request_resolved(99, committed, forced, cancelled, requested=4)
        assert rec.latency(99).outcome == want
    # unknown rids are ignored, not an error (span may have been evicted)
    rec.request_phase(12345, "EPOCH_OPEN", n=1)
    rec.request_resolved(12345, 0, 0, 0, 0)
    assert rec.latency(12345) is None


def test_recorder_ring_is_bounded_but_totals_are_exact():
    rec = TelemetryRecorder(capacity=32, clock=_fake_clock())
    for i in range(500):
        rec.count("dispatches", 1)
    assert len(rec.events()) == 32
    assert rec.dropped == 500 - 32
    assert rec.counter_totals() == {"dispatches": 500}  # eviction-proof


def test_done_span_store_is_bounded_lru():
    rec = TelemetryRecorder(request_capacity=4, clock=_fake_clock())
    for rid in range(10):
        rec.request_submitted(rid, 0, 0)
        rec.request_resolved(rid, 1, 0, 0, 1)
    assert len(rec.request_spans()) == 4
    assert rec.latency(0) is None and rec.latency(9) is not None


# ---------------------------------------------------------------------------
# Overhead guard: disabled == strictly silent
# ---------------------------------------------------------------------------


def test_disabled_config_yields_the_shared_null_recorder():
    assert make_recorder(LeapConfig()) is NULL_RECORDER
    assert make_recorder(LeapConfig(telemetry=True)) is not NULL_RECORDER
    assert not NULL_RECORDER.enabled


def test_disabled_pipeline_emits_nothing_but_stats_still_count():
    _, drv, sess = make(telemetry=False)
    h = sess.leap(np.arange(16), 1)
    assert sess.drain() and h.done
    assert drv.telemetry is NULL_RECORDER
    assert drv.telemetry.events() == []
    assert drv.telemetry.counter_totals() == {}
    assert drv.telemetry.request_spans() == []
    assert h.latency() is None
    assert drv.stats.blocks_migrated + drv.stats.blocks_forced == 16
    view = sess.telemetry()
    assert not view.enabled and view.events() == []


def test_enabled_ring_stays_bounded_across_long_drain():
    # A long churny run with a tiny ring: the buffer must never exceed its
    # capacity, evictions must be counted, and the exact totals must still
    # agree with MigrationStats at the end.
    _, drv, sess = make(telemetry=True, telemetry_events=64)
    rng = np.random.default_rng(0)
    for _ in range(12):
        ids = rng.choice(16, size=8, replace=False)
        sess.leap(ids, int(rng.integers(0, 2)))
        sess.drain()
    rec = drv.telemetry
    assert len(rec.events()) <= 64
    assert rec.dropped > 0
    for key in MIRRORED:
        assert rec.counter_totals().get(key, 0) == getattr(drv.stats, key), key


# ---------------------------------------------------------------------------
# Live pipeline: counters, spans, jit attribution
# ---------------------------------------------------------------------------


def test_pipeline_counters_match_stats_and_span_completes():
    _, drv, sess = make()
    h = sess.leap(np.arange(16), 1)
    assert sess.drain()
    rec = drv.telemetry
    for key in MIRRORED:
        assert rec.counter_totals().get(key, 0) == getattr(drv.stats, key), key
    lat = h.latency()
    assert lat is not None and lat.outcome == "COMMITTED"
    assert lat.requested == lat.committed == 16
    assert lat.epochs >= 1 and lat.ticks_total >= 1
    names = {e["name"] for e in rec.events() if e["kind"] == "stage"}
    assert {"tick", "dispatch.run_tick", "verdict.harvest"} <= names


def test_jit_misses_land_as_events():
    # A fresh driver compiles its migration programs on first use — those
    # cache misses must surface as "jit" events carrying the per-tick delta.
    # An unusual block shape keeps this from being satisfied for free by
    # compiles other tests in the process already paid for.
    cfg = PoolConfig(2, 24, (6,))
    state = init_state(cfg, 16, np.zeros(16, np.int32))
    drv = MigrationDriver(
        state, cfg,
        LeapConfig(initial_area_blocks=4, chunk_blocks=3,
                   budget_blocks_per_tick=6, telemetry=True),
    )
    sess = LeapSession(drv)
    sess.leap(np.arange(16), 1)
    sess.drain()
    misses = [e for e in drv.telemetry.events() if e["kind"] == "jit"]
    assert drv.stats.jit_cache_misses > 0
    assert sum(e["args"]["n"] for e in misses) == drv.stats.jit_cache_misses


# ---------------------------------------------------------------------------
# Profiler spans: every stage on the profiler clock as leap.<stage>
# ---------------------------------------------------------------------------

#: Each span the pipeline puts on the profiler clock, with its parent.
SPAN_PARENTS = {
    "leap.tick": None,
    "leap.verdict.harvest": "leap.tick",
    "leap.verdict.sync": "leap.verdict.harvest",
    "leap.dispatch.commit_ready": "leap.tick",
    "leap.budget.open_tick": "leap.tick",
    "leap.dispatch.run_tick": "leap.tick",
    "leap.dispatch.plan": "leap.dispatch.run_tick",
    "leap.dispatch.device": "leap.dispatch.run_tick",
    "leap.dispatch.operands": "leap.dispatch.device",
    "leap.dispatch.enqueue": "leap.dispatch.device",
    "leap.write": None,
}


def _host_spans(logdir):
    """``[(name, start_ns, end_ns)]`` of every host event named ``leap.*``."""
    from jax.profiler import ProfileData

    (path,) = logdir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("leap.")
    ]


def _traced_ticks(tmp_path, telemetry):
    import jax

    _, drv, sess = make(telemetry=telemetry)
    sess.leap(np.arange(16), 1)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(6):
            drv.write(np.arange(2), jnp.ones((2, 4), jnp.float32))
            drv.write_rows(np.arange(2, 4), np.zeros(2, np.int32), jnp.ones(2))
            sess.tick()
    assert sess.drain()  # drain's blocking harvests run outside any tick: untraced
    return drv, _host_spans(tmp_path)


@pytest.mark.parametrize("telemetry", [False, True], ids=["off", "on"])
def test_every_stage_span_reaches_the_profiler_inside_its_parent(tmp_path, telemetry):
    drv, spans = _traced_ticks(tmp_path, telemetry)
    assert {name for name, _, _ in spans} == set(SPAN_PARENTS)
    assert not [name for name, _, _ in spans if "#" in name]  # no TraceMe metadata
    for name, s, e in spans:
        parent = SPAN_PARENTS[name]
        if parent is None:
            continue
        assert any(p == parent and ps <= s and e <= pe for p, ps, pe in spans), name
        assert any(p == "leap.tick" and ps <= s and e <= pe for p, ps, pe in spans), name
    ticks = [(s, e) for name, s, e in spans if name == "leap.tick"]
    for name, s, e in spans:
        if name == "leap.write":
            assert not any(ts <= s < te for ts, te in ticks)
    assert len(ticks) == 6
    assert sum(name == "leap.write" for name, _, _ in spans) == 12
    if telemetry:  # ring events keep their unprefixed names
        stages = {e["name"] for e in drv.telemetry.events() if e["kind"] == "stage"}
        assert {n.removeprefix("leap.") for n in SPAN_PARENTS} <= stages


def test_disabled_pipeline_under_the_profiler_records_no_ring_events(tmp_path):
    drv, spans = _traced_ticks(tmp_path, telemetry=False)
    assert drv.telemetry is NULL_RECORDER
    assert drv.telemetry.events() == [] and drv.telemetry.counter_totals() == {}
    assert spans  # the annotations alone reached the trace


def test_stage_arguments_stay_out_of_the_profiler_span_name(tmp_path):
    import jax

    rec = TelemetryRecorder(clock=_fake_clock())
    with jax.profiler.trace(str(tmp_path)):
        with rec.stage("admission.cancel", rid=3):
            pass
        with NULL_RECORDER.stage("admission.cancel", rid=4):
            pass
    assert [name for name, _, _ in _host_spans(tmp_path)] == ["leap.admission.cancel"] * 2
    (ev,) = rec.events()
    assert ev["name"] == "admission.cancel" and ev["args"] == {"rid": 3}


# ---------------------------------------------------------------------------
# Views: session / sealed facade / metrics renderings
# ---------------------------------------------------------------------------


def test_session_and_facade_hand_out_views_over_one_recorder():
    _, drv, sess = make()
    sess.leap(np.arange(16), 1)
    sess.drain()
    view = sess.telemetry()
    sealed = sess.facade.telemetry()
    assert isinstance(view, TelemetryView) and isinstance(sealed, TelemetryView)
    assert view.enabled and sealed.enabled
    assert view.counters() == sealed.counters()
    # counters() returns a copy — mutating it cannot touch the recorder
    view.counters()["blocks_migrated"] = -1
    assert view.counters()["blocks_migrated"] == drv.stats.blocks_migrated


def test_metrics_json_and_prometheus_text():
    _, drv, sess = make()
    sess.leap(np.arange(16), 1)
    sess.drain()
    doc = sess.telemetry().metrics_json()
    assert doc["counters"]["leap_blocks_migrated_total"] == drv.stats.blocks_migrated
    assert doc["gauges"]["leap_ticks"] == drv.stats.ticks
    text = sess.telemetry().metrics_text()
    assert "# TYPE leap_blocks_migrated_total counter" in text
    assert f"leap_blocks_migrated_total {drv.stats.blocks_migrated}" in text
    assert 'le="+Inf"' in text and "leap_request_latency_ticks_count 1" in text
    assert "leap_link_bytes_total{" in text  # per-link counters with labels


def test_histogram_quantiles():
    h = Histogram((1, 2, 4, 8))
    for v in (0, 1, 3, 3, 100):
        h.observe(v)
    assert h.count == 5
    assert h.quantile(0.5) <= 4 and h.quantile(1.0) > 8
    assert len(h.counts) == 5  # len(buckets) + overflow


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_from_live_run_is_valid_and_complete():
    _, drv, sess = make()
    h = sess.leap(np.arange(16), 1)
    sess.drain()
    trace = sess.telemetry().chrome_trace(label="unit")
    validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    assert {e["name"] for e in evs if e["ph"] == "M"} == {
        "process_name", "thread_name",
    }
    assert any(e["ph"] == "X" and e["name"] == "tick" for e in evs)
    # at least one complete request lifecycle async span (begin AND end)
    begins = [e for e in evs if e["ph"] == "b" and e["cat"] == "request"]
    ends = [e for e in evs if e["ph"] == "e" and e["cat"] == "request"]
    assert begins and {e["id"] for e in begins} == {e["id"] for e in ends}
    assert all(e["args"]["phase"] != "OPEN_AT_EXPORT" for e in ends)
    assert any(e["id"] == h.request_id for e in begins)
    json.dumps(trace)  # serializable end to end


def test_chrome_trace_closes_spans_cut_mid_run():
    rec = TelemetryRecorder(clock=_fake_clock())
    rec.request_submitted(5, 0, 0)
    rec.request_phase(5, "EPOCH_OPEN", n=2)  # never resolved
    trace = chrome_trace(rec)  # bare-recorder form
    validate_chrome_trace(trace)
    (end,) = [e for e in trace["traceEvents"] if e["ph"] == "e"]
    assert end["id"] == 5 and end["args"]["phase"] == "OPEN_AT_EXPORT"


def test_validator_rejects_malformed_traces():
    ok = {"traceEvents": [], "displayTimeUnit": "ms"}
    validate_chrome_trace(ok)
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(
            {"traceEvents": [
                {"ph": "X", "name": "t", "ts": 0.0, "pid": 0, "tid": 0}
            ]}
        )
    with pytest.raises(ValueError, match="without begin"):
        validate_chrome_trace(
            {"traceEvents": [
                {"ph": "e", "name": "r", "cat": "request", "id": 1,
                 "ts": 0.0, "pid": 0, "tid": 0}
            ]}
        )
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace(
            {"traceEvents": [
                {"ph": "b", "name": "r", "cat": "request", "id": 1,
                 "ts": 0.0, "pid": 0, "tid": 0}
            ]}
        )


def test_summarize_aggregates_across_pools():
    recs = []
    for _ in range(2):
        rec = TelemetryRecorder(clock=_fake_clock())
        rec.begin_tick(1)
        with rec.stage("tick"):
            pass
        rec.count("dispatches", 3)
        recs.append(rec)
    doc = summarize((f"p{i}", r) for i, r in enumerate(recs))  # generator ok
    assert doc["pools"] == 2 and doc["counters"]["dispatches"] == 6
    assert doc["stage_totals_us"]["tick"] > 0


# ---------------------------------------------------------------------------
# Stats snapshot independence (the facade's observer contract)
# ---------------------------------------------------------------------------


def test_stats_snapshot_is_fully_independent():
    live = MigrationStats(blocks_migrated=4)
    live.bytes_per_link[(0, 1)] = 100
    snap = live.snapshot()
    # mutate the live object, container field included
    live.blocks_migrated = 99
    live.bytes_per_link[(0, 1)] = 999
    live.bytes_per_link[(1, 0)] = 7
    assert snap.blocks_migrated == 4
    assert snap.bytes_per_link == {(0, 1): 100}
    # and the other direction: a held snapshot cannot corrupt live accounting
    snap.bytes_per_link[(2, 3)] = 1
    assert (2, 3) not in live.bytes_per_link


def test_facade_snapshot_does_not_alias_live_stats():
    _, drv, sess = make()
    sess.leap(np.arange(16), 1)
    sess.drain()
    snap = sess.facade.snapshot_stats()
    snap.bytes_per_link[(9, 9)] = 1
    snap.blocks_migrated = -5
    assert (9, 9) not in drv.stats.bytes_per_link
    assert drv.stats.blocks_migrated >= 0


# ---------------------------------------------------------------------------
# Acceptance: benchmarks.run --trace produces a Perfetto-loadable trace
# ---------------------------------------------------------------------------


def test_bench_trace_flag_produces_valid_trace_and_summary(tmp_path):
    from benchmarks import common
    from benchmarks.run import main

    rc = main(["--only", "table2_overhead", "--outdir", str(tmp_path), "--trace"])
    assert rc == 0
    trace_path = tmp_path / "TRACE_table2_overhead.json"
    assert trace_path.exists()
    trace = json.loads(trace_path.read_text())
    validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    assert any(e["ph"] == "X" and e["name"] == "tick" for e in evs)
    # >= 1 complete request lifecycle span survived into the export
    assert any(e["ph"] == "b" and e["cat"] == "request" for e in evs)
    assert any(
        e["ph"] == "e" and e["args"].get("phase") in
        ("COMMITTED", "FORCED", "PARTIAL", "CANCELLED")
        for e in evs
    )
    doc = json.loads((tmp_path / "BENCH_table2_overhead.json").read_text())
    tel = doc["telemetry"]
    assert tel["pools"] >= 1 and tel["events"] > 0
    assert tel["counters"]["blocks_migrated"] > 0
    assert "tick" in tel["stage_totals_us"]
    assert tel["trace_file"] == str(trace_path)
    # the harness restored the module flags on exit (no leakage into later
    # non-traced runs in the same process)
    assert common.TRACING is False and common.TRACE_SESSIONS == []


def test_bench_without_trace_embeds_no_telemetry(tmp_path):
    from benchmarks.run import main

    rc = main(["--only", "table2_overhead", "--outdir", str(tmp_path)])
    assert rc == 0
    doc = json.loads((tmp_path / "BENCH_table2_overhead.json").read_text())
    assert "telemetry" not in doc
    assert not (tmp_path / "TRACE_table2_overhead.json").exists()
