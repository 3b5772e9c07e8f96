"""Closed-loop hot/cold tiering (DESIGN.md §13).

Covers the tiering acceptance criteria:
  * heat-kernel correctness — the Pallas accumulate (interpret mode)
    matches the numpy decay oracle under random access traces, including
    out-of-range sentinel lanes and duplicate ids;
  * the single-dispatch invariant survives the heat phase — folding read
    samples into the megastep adds ZERO device programs per tick, and the
    warm path stays compile-free at a steady read rate;
  * ``tiering=False`` is bit-identical to the pre-tiering engine (the heat
    phase is trace-time guarded, not masked), and ``tiering=True`` without
    a policy perturbs nothing;
  * the :class:`TieringPolicy` loop — watermark promotion/demotion,
    cooldown hysteresis, G-aligned demotion runs, ping-pong metering —
    and the tier-residency telemetry.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
    migrator,
)
from repro.kernels import ops, ref
from repro.kernels.heat_scan import heat_scan_pallas, padded_heat_len
from repro.tiering import TieringConfig, TieringPolicy, residency_extra, split_tiers
from repro.topology import NumaTopology


def make(n_regions=2, slots=64, n_blocks=32, block_shape=(4,), seed=0, **pool_kw):
    cfg = PoolConfig(n_regions, slots, block_shape, **pool_kw)
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n_blocks,) + tuple(block_shape)).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    return cfg, state, data


def cxl_pool(n_blocks=48, slots=64, far_share=1.0, seed=0):
    """3-region cxl_pooled pool (near = {0, 1}, far = {2}), blocks start far."""
    topo = NumaTopology.cxl_pooled(2, 1)
    cfg = PoolConfig(3, slots, (4,), topology=topo)
    init_regions = np.full(n_blocks, 2, np.int32)
    init_regions[: int(n_blocks * (1.0 - far_share))] = 0
    state = init_state(cfg, n_blocks, init_regions)
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n_blocks, 4)).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    return cfg, state, data


# ---------------------------------------------------------------------------
# Heat kernel vs. numpy oracle
# ---------------------------------------------------------------------------


def heat_oracle(heat, ids, w, decay):
    out = np.asarray(heat, np.float32) * np.float32(decay)
    for i, ww in zip(np.asarray(ids), np.asarray(w)):
        if 0 <= i < len(out):
            out[i] += np.float32(ww)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("decay", [1.0, 0.9, 0.5])
def test_heat_scan_matches_oracle_random_traces(seed, decay):
    """Interpret-mode Pallas accumulate == numpy decay oracle on random
    traces: duplicate ids sum, sentinel (>= L) lanes are inert, and chained
    steps compose (exponential decay across ticks)."""
    rng = np.random.default_rng(seed)
    L = padded_heat_len(100)
    heat = rng.gamma(1.0, 1.0, size=L).astype(np.float32)
    expect = heat.copy()
    got = jnp.asarray(heat)
    for _ in range(4):
        k = int(rng.integers(1, 70))
        ids = rng.integers(0, 100, size=k).astype(np.int32)
        ids[rng.random(k) < 0.15] = L  # OOB sentinel: must drop, not wrap
        w = rng.uniform(0.25, 2.0, size=k).astype(np.float32)
        expect = heat_oracle(expect, ids, w, decay)
        got = heat_scan_pallas(
            got, jnp.asarray(ids), jnp.asarray(w), decay, interpret=True
        )
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5)


def test_heat_scan_ref_matches_oracle():
    rng = np.random.default_rng(7)
    L = padded_heat_len(40)
    heat = rng.gamma(1.0, 1.0, size=L).astype(np.float32)
    ids = rng.integers(0, 45, size=33).astype(np.int32)
    ids[:5] = L
    w = rng.uniform(0.0, 2.0, size=33).astype(np.float32)
    got = ref.heat_scan_ref(jnp.asarray(heat), jnp.asarray(ids), jnp.asarray(w), 0.8)
    np.testing.assert_allclose(np.asarray(got), heat_oracle(heat, ids, w, 0.8), rtol=1e-5)


def test_heat_scan_dispatcher_empty_is_identity():
    heat = jnp.arange(padded_heat_len(8), dtype=jnp.float32)
    out = ops.heat_scan_impl(
        heat, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32), 0.5
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(heat))


def test_padded_heat_len_tile_aligned():
    for n in (1, 7, 1024, 1025, 5000):
        L = padded_heat_len(n)
        assert L >= n and L % 1024 == 0


# ---------------------------------------------------------------------------
# Megastep integration: dispatch count and bit-identity
# ---------------------------------------------------------------------------


def drain_with_reads(tiering, seed=11, n_blocks=32, reads_per_tick=4):
    cfg, state, _ = make(n_blocks=n_blocks, slots=n_blocks * 2, seed=seed)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(budget_blocks_per_tick=16, tiering=tiering),
    )
    drv.default_session().leap(np.arange(n_blocks), 1)
    rng = np.random.default_rng(seed)
    steps = 0
    while not drv.done and steps < 500:
        drv.read(rng.choice(n_blocks, size=reads_per_tick, replace=False))
        drv.tick()
        steps += 1
    assert drv.default_session().drain()
    return drv


def test_single_dispatch_with_heat_phase():
    """Reads every tick with tiering on: the heat fold rides the megastep —
    dispatches/tick stays at (or under) 1.0, same as with tiering off."""
    drv = drain_with_reads(tiering=True)
    assert 0.0 < drv.stats.dispatches_per_tick <= 1.0
    assert drv.verify_mirror()
    heat = drv.heat_snapshot()
    assert heat.shape == (32,) and (heat > 0).any()


def test_tiering_off_bit_identical_and_on_logically_inert():
    """tiering=False must equal the pre-tiering engine bit-for-bit (the heat
    phase is a trace-time skip, not a masked no-op); tiering=True with no
    policy observes reads without perturbing placement or data."""
    off = drain_with_reads(tiering=False, seed=13)
    on = drain_with_reads(tiering=True, seed=13)
    np.testing.assert_array_equal(np.asarray(off.state.pool), np.asarray(on.state.pool))
    np.testing.assert_array_equal(np.asarray(off.state.table), np.asarray(on.state.table))
    np.testing.assert_array_equal(off.host_table(), on.host_table())
    # tiering off => heat plane absent and snapshot reads zero
    assert (off.heat_snapshot() == 0).all()


def test_heat_warm_path_does_not_recompile():
    """Steady read rate (batches <= the budget floor) after a drain: no new
    megastep variants, zero jit misses — the heat operands pad to the same
    geometric buckets as the migration operands."""
    cfg, state, _ = make(n_blocks=32, slots=64, seed=41)
    drv = MigrationDriver(state, cfg, LeapConfig(budget_blocks_per_tick=16, tiering=True))
    sess = drv.default_session()
    rng = np.random.default_rng(41)
    sess.leap(np.arange(32), 1)
    while not drv.done:
        drv.read(rng.choice(32, size=8, replace=False))
        drv.tick()
    assert sess.drain()
    before = migrator.program_cache_sizes()["megastep"]
    misses = drv.stats.jit_cache_misses
    sess.leap(np.arange(32), 0)
    steps = 0
    while not drv.done and steps < 500:
        drv.read(rng.choice(32, size=8, replace=False))
        drv.tick()
        steps += 1
    assert sess.drain()
    assert migrator.program_cache_sizes()["megastep"] == before
    assert drv.stats.jit_cache_misses == misses


def test_heat_decay_orders_recency():
    """Blocks read longer ago decay below recently read ones."""
    cfg, state, _ = make(n_blocks=16, slots=32, seed=6)
    drv = MigrationDriver(
        state, cfg, LeapConfig(tiering=True, tier_heat_decay=0.5)
    )
    drv.read(np.array([1]))
    drv.tick()
    for _ in range(4):
        drv.read(np.array([2]))
        drv.tick()
    heat = drv.heat_snapshot()
    assert heat[2] > heat[1] > 0


# ---------------------------------------------------------------------------
# split_tiers
# ---------------------------------------------------------------------------


def test_split_tiers_cxl_and_uniform():
    near, far = split_tiers(NumaTopology.cxl_pooled(2, 1))
    assert near == (0, 1) and far == (2,)
    near, far = split_tiers(NumaTopology.cxl_pooled(2, 2))
    assert near == (0, 1) and far == (2, 3)
    # uniform mesh: no region is beyond the fastest link => no far tier
    near, far = split_tiers(NumaTopology.symmetric(4))
    assert near == (0, 1, 2, 3) and far == ()
    # explicit override wins and completes the complement
    near, far = split_tiers(NumaTopology.symmetric(4), far=(3,))
    assert near == (0, 1, 2) and far == (3,)


# ---------------------------------------------------------------------------
# TieringPolicy: watermarks, hysteresis, G-aligned demotion
# ---------------------------------------------------------------------------


def run_policy(drv, pol, hot_ids, ticks, reads_per_tick=None):
    sess = drv.default_session()
    for _ in range(ticks):
        if len(hot_ids):
            drv.read(hot_ids)
        pol.maybe_apply(sess)
        drv.tick()
    sess.drain()
    return drv.host_placement()


def test_policy_promotes_hot_and_demotes_cold():
    cfg, state, data = cxl_pool()
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True, budget_blocks_per_tick=16))
    pol = TieringPolicy(
        drv,
        TieringConfig(hot_watermark=2.0, cold_watermark=0.1, epoch_ticks=4, cooldown_ticks=8),
    )
    hot = np.array([20, 21, 22, 23], np.int32)
    placement = run_policy(drv, pol, hot, 40)
    assert set(placement[hot].tolist()) <= {0, 1}, placement[hot]
    assert drv.stats.tier_promotions >= len(hot)
    # data survives the round trips
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(48))), data)
    assert drv.verify_mirror()


def test_policy_cooldown_pins_recent_movers():
    """A block the policy just moved is ineligible until cooldown expires —
    even if its heat immediately crosses the opposite watermark."""
    cfg, state, _ = cxl_pool()
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True))
    pol = TieringPolicy(
        drv,
        TieringConfig(
            hot_watermark=1.5, cold_watermark=1.0, epoch_ticks=1, cooldown_ticks=10_000
        ),
    )
    sess = drv.default_session()
    # heat block 30 over the promote watermark, then go silent: its heat
    # decays below cold_watermark, but the cooldown must pin it near.
    for _ in range(4):
        drv.read(np.array([30]))
        drv.tick()
    pol.maybe_apply(sess)
    for _ in range(10):
        drv.tick()
    assert sess.drain()
    assert drv.host_placement()[30] in (0, 1)
    for _ in range(30):  # heat now ~0 — decisively cold
        pol.maybe_apply(sess)
        drv.tick()
    assert sess.drain()
    assert drv.host_placement()[30] in (0, 1), "cooldown must prevent demotion"
    assert drv.stats.tier_demotions == 0


def test_policy_demotes_whole_aligned_runs_on_tiered_pool():
    """huge_factor G > 1: demotion only moves G-aligned runs whose every
    member is cold; a half-hot run keeps all members near."""
    G = 4
    topo = NumaTopology.cxl_pooled(2, 1)
    cfg = PoolConfig(3, 32, (4,), huge_factor=G, topology=topo)
    n = 16
    state = init_state(cfg, n, np.zeros(n, np.int32))  # all near
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True))
    pol = TieringPolicy(
        drv,
        TieringConfig(hot_watermark=2.0, cold_watermark=0.5, epoch_ticks=2, cooldown_ticks=4),
    )
    hot = np.array([4], np.int32)  # group 1 is half-hot; groups 0, 2, 3 all-cold
    for _ in range(6):  # build block 4's heat before the first epoch fires
        drv.read(hot)
        drv.tick()
    placement = run_policy(drv, pol, hot, 30)
    assert (placement[4:8] != 2).all(), "half-hot run must stay near"
    demoted = [g for g in (0, 2, 3) if (placement[g * G : (g + 1) * G] == 2).all()]
    assert demoted, placement
    assert drv.stats.tier_demotions % G == 0


def test_policy_noop_without_topology_or_far_tier():
    cfg, state, _ = make(n_blocks=8, slots=16)
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True))
    pol = TieringPolicy(drv)
    assert pol.decide(drv.default_session().facade) == []


# ---------------------------------------------------------------------------
# Ping-pong metering
# ---------------------------------------------------------------------------


def test_ping_pong_counter_meters_rapid_remigration():
    """Back-and-forth moves within the window count; slow oscillation does
    not — and the meter runs with tiering off (every baseline pays it)."""
    cfg, state, _ = make(n_blocks=8, slots=32, seed=9)
    drv = MigrationDriver(state, cfg, LeapConfig(tier_pingpong_window=16))
    sess = drv.default_session()
    ids = np.array([0, 1], np.int32)
    for dst in (1, 0, 1):  # three rapid moves: 2nd and 3rd are ping-pongs
        sess.leap(ids, dst)
        assert sess.drain()
    assert drv.stats.ping_pong_migrations == 2 * len(ids)
    before = drv.stats.ping_pong_migrations
    for _ in range(20):  # let the window expire
        drv.tick()
    sess.leap(ids, 0)
    assert sess.drain()
    assert drv.stats.ping_pong_migrations == before


# ---------------------------------------------------------------------------
# Telemetry: residency gauges, counters, extra stacking
# ---------------------------------------------------------------------------


def test_tier_residency_gauges_and_counters_in_prometheus():
    cfg, state, _ = cxl_pool(n_blocks=24, far_share=0.5)
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True))
    sess = drv.default_session()
    txt = sess.telemetry().metrics_text()
    bb = cfg.block_bytes
    assert f'tier_resident_bytes{{tier="far"}} {12 * bb}' in txt
    assert f'tier_resident_bytes{{tier="near"}} {12 * bb}' in txt
    assert "leap_tier_promotions_total 0" in txt
    assert "leap_tier_demotions_total 0" in txt
    assert "leap_ping_pong_migrations_total 0" in txt
    # gauges track placement: move every block far
    sess.leap(np.arange(24), 2)
    assert sess.drain()
    txt = sess.telemetry().metrics_text()
    assert f'tier_resident_bytes{{tier="far"}} {24 * bb}' in txt
    assert f'tier_resident_bytes{{tier="near"}} 0' in txt


def test_with_extra_stacks_not_replaces():
    cfg, state, _ = cxl_pool(n_blocks=8)
    drv = MigrationDriver(state, cfg, LeapConfig())
    view = drv.default_session().telemetry()  # carries residency extra
    view = view.with_extra(lambda reg: reg.gauge("custom_extra", 7))
    txt = view.metrics_text()
    assert "custom_extra 7" in txt
    assert 'tier_resident_bytes{tier="near"}' in txt, "stacking dropped prior extra"


def test_facade_heat_accessor():
    cfg, state, _ = make(n_blocks=8, slots=16)
    drv = MigrationDriver(state, cfg, LeapConfig(tiering=True))
    drv.read(np.array([2, 2, 5]))
    drv.tick()
    heat = drv.default_session().facade.heat()
    assert heat.shape == (8,)
    assert heat[2] > heat[5] > 0 and heat[0] == 0
