"""Megastep dispatch: the single-dispatch tick (DESIGN.md §12).

Covers the acceptance criteria of the device-resident tick loop:
  * the single-dispatch invariant — a warm fused-megastep drain issues at
    most one device program per tick (``dispatches_per_tick`` ~ 1.0),
  * verdict-carry correctness — under interleaved writes the megastep
    reads back the expected payload, with per-request accounting closure
    (the batched generation is checked against it on the ppermute backend
    in test_multidevice.py),
  * jit-cache stability — a retry storm's fragmented batch lengths all
    round up to the shared floored bucket, so megastep compiles a bounded
    number of variants after warmup,
  * the dispatch generation follows the copy backend (megastep on xla,
    batched on ppermute),
  * verdict prefetch — every commit verdict's host copy starts at dispatch,
    one per harvested batch, and changes no outcome.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommitBatch,
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
    migrator,
)
from repro.core.pipeline import PipelineContext
from repro.kernels.heat_scan import padded_heat_len


def make(n_regions=2, slots=64, n_blocks=32, block_shape=(4,), seed=0):
    cfg = PoolConfig(n_regions, slots, block_shape)
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n_blocks,) + block_shape).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    return cfg, state, data


def _run_interleaved(seed=3, n_blocks=32):
    """A request + interleaved write schedule with escalation."""
    cfg, state, data = make(n_blocks=n_blocks, slots=n_blocks * 2, seed=seed)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(
            initial_area_blocks=8,
            budget_blocks_per_tick=8,
            max_attempts_before_force=3,
        ),
    )
    session = drv.default_session()
    session.leap(np.arange(n_blocks), 1)
    rng = np.random.default_rng(seed)
    expected = data.copy()
    steps = 0
    while not drv.done and steps < 1000:
        drv.tick()
        ids = rng.choice(n_blocks, size=2, replace=False)
        vals = rng.normal(size=(2, 4)).astype(np.float32)
        drv.write(jnp.asarray(ids), jnp.asarray(vals))
        expected[ids] = vals
        steps += 1
    assert session.drain()
    return drv, expected


# ---------------------------------------------------------------------------
# One dispatch generation per copy backend
# ---------------------------------------------------------------------------


def test_dispatch_generation_follows_backend():
    """The copy backend alone picks the generation: no option selects it."""
    cfg, state, _ = make(n_blocks=8, slots=16)
    drv = MigrationDriver(state, cfg, LeapConfig())
    assert not drv._dispatch._batched
    drv.default_session().leap(np.arange(8), 1)
    assert drv.drain()
    assert drv.stats.dispatches == drv.stats.h2d_transfers > 0  # megastep only


def test_megastep_falls_back_to_batched_on_ppermute():
    """shard_map programs have static (src, dst) endpoints: they cannot fuse
    into one variant-stable program, so the ppermute backend runs batched."""
    cfg, state, _ = make(n_blocks=8, slots=16)
    drv = MigrationDriver(state, cfg, LeapConfig(backend="ppermute", axis_name="data"))
    assert drv._dispatch._batched


# ---------------------------------------------------------------------------
# The single-dispatch invariant
# ---------------------------------------------------------------------------


def test_single_dispatch_per_tick_on_drain():
    """fig9-style drain under megastep: at most ONE device program per tick
    (idle/harvest-only ticks dispatch nothing, so the ratio sits at or just
    under 1.0 — never above)."""
    cfg, state, _ = make(n_blocks=128, slots=256)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(initial_area_blocks=64, budget_blocks_per_tick=64),
    )
    drv.default_session().leap(np.arange(128), 1)
    assert drv.drain()
    assert drv.stats.ticks > 0
    assert drv.stats.dispatches <= drv.stats.ticks
    assert 0.0 < drv.stats.dispatches_per_tick <= 1.0
    assert drv.verify_mirror()


def test_idle_ticks_dispatch_nothing():
    cfg, state, _ = make(n_blocks=8, slots=16)
    drv = MigrationDriver(state, cfg, LeapConfig())
    for _ in range(5):
        drv.tick()
    assert drv.stats.ticks == 5 and drv.stats.dispatches == 0


# ---------------------------------------------------------------------------
# Verdict-carry correctness across dispatch generations
# ---------------------------------------------------------------------------


def test_megastep_matches_batched_and_legacy_under_writes():
    drv, expected = _run_interleaved()
    assert (drv.host_placement() == 1).all()
    assert drv.verify_mirror()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(32))), expected)
    # at most one device program per tick, whatever the retries and forces
    assert drv.stats.dirty_rejections > 0
    assert 0 < drv.stats.dispatches <= drv.stats.ticks


def test_accounting_closure_every_mode():
    """committed + forced + cancelled == requested at termination, and the
    retry traffic the stats report covers the re-copied bytes."""
    drv, _ = _run_interleaved(seed=7)
    for req in drv.requests.values():
        assert req.done
        assert req.committed + req.forced + req.cancelled == req.requested
    s = drv.stats
    assert s.blocks_migrated + s.blocks_forced + s.blocks_cancelled == s.blocks_requested


def test_megastep_huge_tier_drain():
    """Two-tier pool under megastep: grouped commits and contiguous-run
    copies ride the same single dispatch."""
    G = 4
    cfg = PoolConfig(2, 32, (4,), huge_factor=G)
    n_blocks = 16
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    rng = np.random.default_rng(5)
    data = rng.normal(size=(n_blocks, 4)).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    drv = MigrationDriver(state, cfg, LeapConfig(initial_area_blocks=8))
    drv.adopt_huge(np.arange(n_blocks // G))
    drv.default_session().leap(np.arange(n_blocks), 1)
    assert drv.drain()
    assert (drv.host_placement() == 1).all()
    assert drv.verify_mirror()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(n_blocks))), data)
    assert drv.stats.huge_areas_committed > 0
    assert 0.0 < drv.stats.dispatches_per_tick <= 1.0


# ---------------------------------------------------------------------------
# Jit-cache stability under a retry storm
# ---------------------------------------------------------------------------


def test_megastep_cache_stable_under_retry_storm():
    """However the splitter fragments the work, every megastep operand pads
    to the budget-floored shared bucket: the storm compiles a handful of
    variants, not one per batch-length combination."""
    before = migrator.program_cache_sizes()["megastep"]
    for seed in (21, 22):
        cfg, state, data = make(n_blocks=64, slots=128, seed=seed)
        drv = MigrationDriver(
            state,
            cfg,
            LeapConfig(
                initial_area_blocks=16,
                budget_blocks_per_tick=64,
                max_attempts_before_force=4,
            ),
        )
        drv.default_session().leap(np.arange(64), 1)
        rng = np.random.default_rng(seed)
        steps = 0
        while not drv.done and steps < 2000:
            drv.tick()
            ids = rng.choice(64, size=4, replace=False)
            drv.write(jnp.asarray(ids), jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32)))
            steps += 1
        assert drv.drain()
        assert drv.verify_mirror()
        assert drv.stats.dirty_rejections > 0, "workload must exercise splitting"
    after = migrator.program_cache_sizes()["megastep"]
    # the floored bucket admits the steady-state shape plus at most the
    # force-overflow shape (forces are budget-exempt, so a force batch can
    # exceed the budget floor and round up one bucket)
    assert after - before <= 3, (before, after)
    # driver-level stat agrees: bounded compiles despite the length storm
    assert drv.stats.jit_cache_misses <= 6


def test_megastep_warm_ticks_do_not_recompile():
    """Second drain on an identically shaped pool: zero new megastep
    variants (the warm path the fig9 bench gates)."""
    cfg, state, _ = make(n_blocks=32, slots=64, seed=31)
    drv = MigrationDriver(state, cfg, LeapConfig(budget_blocks_per_tick=16))
    drv.default_session().leap(np.arange(32), 1)
    assert drv.drain()
    before = migrator.program_cache_sizes()["megastep"]
    cfg2, state2, _ = make(n_blocks=32, slots=64, seed=32)
    drv2 = MigrationDriver(state2, cfg2, LeapConfig(budget_blocks_per_tick=16))
    drv2.default_session().leap(np.arange(32), 0)  # opposite direction, same shapes
    drv2.default_session().leap(np.arange(32), 1)
    assert drv2.drain()
    assert migrator.program_cache_sizes()["megastep"] == before
    assert drv2.stats.jit_cache_misses == 0


# ---------------------------------------------------------------------------
# Packed operands: one host-to-device transfer per megastep
# ---------------------------------------------------------------------------

#: Every signature ``_warm_megastep`` compiles on a tiered (G = 2) pool with
#: tiering on — the drain loop, the heat phase, the huge-group shapes.
WARM_SIGNATURES = [
    ("commit",),
    ("begin", "copy"),
    ("commit", "begin", "copy"),
    ("heat",),
    ("commit", "heat"),
    ("begin", "copy", "heat"),
    ("commit", "begin", "copy", "heat"),
    ("groups",),
    ("begin", "runs"),
    ("groups", "begin", "runs"),
    ("groups", "begin", "copy"),
]


@pytest.fixture(scope="module")
def warm_operands():
    cfg = PoolConfig(2, 64, (4,), huge_factor=2)
    state = init_state(cfg, 32, np.zeros(32, np.int32))
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True, budget_blocks_per_tick=16))
    return cfg, drv._dispatch._warm_operands()


def test_warm_signatures_are_the_packed_ones(warm_operands):
    _, ops_by_sig = warm_operands
    assert list(ops_by_sig) == WARM_SIGNATURES


@pytest.mark.parametrize("sig", WARM_SIGNATURES, ids="+".join)
def test_packed_operands_slice_back_to_each_phase(warm_operands, sig):
    """Packing on the host and slicing inside a jitted program give every
    segment exactly the separate vector, sentinels included; an absent
    phase is a zero-length segment."""
    cfg, ops_by_sig = warm_operands
    segments, heat_w = ops_by_sig[sig]
    packed, layout = migrator.pack_operands(segments)
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert sum(layout) == len(packed) == sum(len(v) for v in segments.values())
    sliced = jax.jit(migrator.unpack_operands, static_argnums=1)(packed, layout)
    for name, got in zip(migrator.MEGASTEP_SEGMENTS, sliced):
        want = segments.get(name, np.zeros(0, np.int32))
        assert got.dtype == jnp.int32, name
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)
    # the warm operands are the out-of-bounds sentinels the program drops
    if "commit" in sig:
        assert (segments["commit_ids"] == 32).all()
        assert (segments["commit_regions"] == cfg.n_regions).all()
        assert (segments["commit_slots"] == cfg.slots_per_region).all()
    if "groups" in sig:
        assert (segments["grp_members"] == 32).all()
    if "heat" in sig:
        assert (segments["heat_ids"] == padded_heat_len(32)).all()
        assert heat_w is not None and len(heat_w) == len(segments["heat_ids"])
    else:
        assert heat_w is None


@pytest.mark.parametrize(
    "huge,tiering",
    [(1, False), (1, True), (2, False)],
    ids=["megastep-False", "megastep-True", "huge-False"],
)
def test_h2d_transfers_per_dispatch(huge, tiering):
    """A tiering-off megastep drain makes exactly one operand transfer per
    dispatch, on a two-tier pool too; a tick with a heat phase adds the
    weights."""
    cfg = PoolConfig(2, 64, (4,), huge_factor=huge)
    state = init_state(cfg, 32, np.zeros(32, np.int32))
    drv = MigrationDriver(state, cfg, LeapConfig(budget_blocks_per_tick=8, tiering=tiering))
    if huge > 1:
        assert drv.adopt_huge(np.arange(32 // huge)) == 32 // huge
    drv.default_session().leap(np.arange(32), 1)
    rng = np.random.default_rng(41)
    while not drv.done:
        drv.tick()
        ids = rng.choice(32, size=2, replace=False)
        drv.write(jnp.asarray(ids), jnp.asarray(rng.normal(size=(2, 4)).astype(np.float32)))
    assert drv.default_session().drain()
    s = drv.stats
    assert s.dispatches > 0
    if huge > 1:
        assert s.huge_areas_committed > 0
    if tiering:
        # writes feed the heat plane on every tick that dispatches after one
        assert s.dispatches < s.h2d_transfers <= 2 * s.dispatches
    else:
        assert s.h2d_transfers == s.dispatches


# ---------------------------------------------------------------------------
# Verdict prefetch: the host copy starts at dispatch
# ---------------------------------------------------------------------------


def _leaps_with_writes(huge, seed=17, n=32):
    """Two seeded leaps (out and back) under overlapping writes.  Each tick's
    programs finish before the next one, so every run harvests each verdict
    on the same tick and the whole schedule is reproducible."""
    cfg = PoolConfig(2, 2 * n, (4,), huge_factor=huge)
    state = init_state(cfg, n, np.zeros(n, np.int32))
    leap = LeapConfig(
        initial_area_blocks=8,
        budget_blocks_per_tick=8,
        max_attempts_before_force=3,
        telemetry=True,
    )
    drv = MigrationDriver(state, cfg, leap)
    if huge > 1:
        assert drv.adopt_huge(np.arange(n // huge)) == n // huge
    rng = np.random.default_rng(seed)
    expected = rng.normal(size=(n, 4)).astype(np.float32)
    drv.write(jnp.arange(n), jnp.asarray(expected))
    session = drv.default_session()
    for dst in (1, 0):
        session.leap(np.arange(n), dst)
        steps = 0
        while not drv.done and steps < 1000:
            drv.tick()
            jax.block_until_ready(drv.state)
            ids = rng.choice(n, size=8, replace=False)
            vals = rng.normal(size=(8, 4)).astype(np.float32)
            drv.write(jnp.asarray(ids), jnp.asarray(vals))
            expected[ids] = vals
            steps += 1
        assert drv.done and session.drain()
    return drv, expected


def _assert_drained_and_mirrored(drv, expected):
    assert not drv.ctx.pending
    np.testing.assert_array_equal(drv.host_table(), np.asarray(drv.state.table))
    assert drv.verify_mirror()
    assert (drv.host_placement() == 0).all()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(len(expected)))), expected)


@pytest.mark.parametrize("huge", [1, 2], ids=["small", "huge"])
def test_verdict_prefetched_per_harvested_batch(huge):
    """Every batch the verdict stage harvests had its host copy started at
    dispatch: one ``verdict.sync`` read per prefetch."""
    drv, expected = _leaps_with_writes(huge)
    rec = drv.telemetry
    assert rec.dropped == 0
    harvested = sum(
        1 for ev in rec.events() if ev["kind"] == "stage" and ev["name"] == "verdict.sync"
    )
    assert drv.stats.verdict_prefetches == harvested > 0
    assert rec.counter_totals()["verdict_prefetches"] == harvested
    _assert_drained_and_mirrored(drv, expected)


@pytest.mark.parametrize("huge", [1, 2], ids=["small", "huge"])
def test_verdict_prefetch_changes_no_outcome(huge, monkeypatch):
    """The prefetch moves when the verdict's bytes cross, nothing else: with
    the copy left to the harvest, the same schedule commits, rejects and
    splits the same blocks and ends in the same pool."""
    drv, expected = _leaps_with_writes(huge)

    def without_prefetch(ctx, areas, offsets, verdict):
        ctx.pending.append(CommitBatch(areas, offsets, verdict))

    monkeypatch.setattr(PipelineContext, "await_verdict", without_prefetch)
    plain, plain_expected = _leaps_with_writes(huge)
    assert plain.stats.verdict_prefetches == 0 < drv.stats.verdict_prefetches
    np.testing.assert_array_equal(plain_expected, expected)
    for key in (
        "blocks_migrated",
        "blocks_forced",
        "dirty_rejections",
        "splits",
        "huge_areas_committed",
        "demotions",
        "dispatches",
        "ticks",
    ):
        assert getattr(plain.stats, key) == getattr(drv.stats, key), key
    assert drv.stats.dirty_rejections > 0 and drv.stats.splits > 0
    if huge > 1:
        assert drv.stats.huge_areas_committed > 0
    np.testing.assert_array_equal(np.asarray(plain.state.table), np.asarray(drv.state.table))
    np.testing.assert_array_equal(np.asarray(plain.state.pool), np.asarray(drv.state.pool))
    _assert_drained_and_mirrored(plain, expected)
    _assert_drained_and_mirrored(drv, expected)
