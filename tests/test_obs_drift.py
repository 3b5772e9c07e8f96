"""Counter-drift property: telemetry event log vs ``MigrationStats``.

Every mirrored counter flows through the single write path
(``PipelineContext.count``), so after any scenario — faults, cancels,
forces, huge tiers, relays included — the recorder's exact totals must
equal the stats fields, and (when the bounded ring never evicted) replaying
the raw event log must reproduce those totals increment by increment.  A
drifting pair means some code path bumped one side directly; this is the
regression net over that invariant.

Deterministic seeded sweep runs in tier-1; the Hypothesis exploration at
the bottom is importorskip'd like the rest of the generative chaos suite.
"""

import pytest

from repro.chaos import ChaosDriver, sample_spec

#: Stats fields mirrored 1:1 into the telemetry counter log.
MIRRORED = (
    "blocks_requested",
    "blocks_migrated",
    "blocks_forced",
    "blocks_cancelled",
    "bytes_copied",
    "dispatches",
    "h2d_transfers",
)
#: Mirrored too, but only nonzero on some scenario shapes (tiered pools,
#: topologies with congestion/relays) — same equality, asserted when present.
MIRRORED_EXTRA = (
    "dirty_rejections",
    "splits",
    "huge_areas_committed",
    "demotions",
    "promotions",
    "bytes_copied_huge",
    "deferred_congested",
    "multi_hop_areas",
)


def _replay_totals(events):
    """Aggregate counter events exactly as a log consumer would."""
    totals: dict[str, int] = {}
    for ev in events:
        if ev["kind"] == "counter":
            totals[ev["name"]] = totals.get(ev["name"], 0) + ev["n"]
    return totals


def _assert_no_drift(driver):
    rec = driver.telemetry
    assert rec.enabled  # chaos always records (trace-on-failure contract)
    totals = rec.counter_totals()
    stats = driver.stats
    for key in MIRRORED + MIRRORED_EXTRA:
        assert totals.get(key, 0) == getattr(stats, key), (
            f"counter {key!r} drifted: event log says {totals.get(key, 0)}, "
            f"MigrationStats says {getattr(stats, key)}"
        )
    # the running totals stamped on the ring events must be internally
    # consistent with the increments (log replay), when nothing was evicted
    if rec.dropped == 0:
        assert _replay_totals(rec.events()) == totals


@pytest.mark.parametrize("seed", range(6))
def test_seeded_chaos_scenarios_never_drift(seed):
    chaos = ChaosDriver(sample_spec(seed))
    report = chaos.run()
    assert report.completed
    assert chaos.driver.stats.blocks_requested > 0  # scenario actually moved
    _assert_no_drift(chaos.driver)


def test_megastep_counts_one_dispatch_per_device_sync():
    """The megastep is ONE dispatch, counted once — both in MigrationStats
    and in the telemetry counter log — however many phases it fuses; ticks
    never see more than one `dispatches` increment under megastep."""
    chaos = ChaosDriver(sample_spec(3))
    report = chaos.run()
    assert report.completed
    driver = chaos.driver
    assert driver.stats.dispatches <= driver.stats.ticks
    per_program = [
        ev for ev in driver.telemetry.events()
        if ev["kind"] == "counter" and ev["name"] == "dispatches"
    ]
    assert per_program, "scenario must dispatch"
    assert all(ev["n"] == 1 for ev in per_program)
    assert {ev["args"]["program"] for ev in per_program} == {"megastep"}
    _assert_no_drift(driver)


def test_drift_check_survives_ring_eviction():
    # A tiny event ring forces evictions mid-scenario; the exact totals
    # (never dropped) must still match, proving aggregates don't live in
    # the bounded buffer.
    chaos = ChaosDriver(sample_spec(1))
    rec = chaos.driver.telemetry
    rec._events = type(rec._events)(maxlen=32)
    rec.capacity = 32
    chaos.run()
    assert rec.dropped > 0
    _assert_no_drift(chaos.driver)


try:
    from hypothesis import given, settings

    from repro.chaos import scenario_specs

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dep
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(spec=scenario_specs())
    def test_generated_chaos_scenarios_never_drift(spec):
        chaos = ChaosDriver(spec)
        chaos.run()
        _assert_no_drift(chaos.driver)
