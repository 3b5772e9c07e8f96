"""Migration engine tests: epochs, dirty protocol, adaptive split, driver loop.

Hypothesis property tests over arbitrary write/migration interleavings live in
test_property_migrator.py (guarded by ``pytest.importorskip("hypothesis")`` so
the suite collects without the optional dev dependency)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_read,
    leap_write,
)
from repro.core.adaptive import Area, split_area
from repro.core.migrator import begin_areas, commit_areas, force_areas, fused_copy
from repro.core.state import REGION, SLOT


def make(n_regions=2, slots=32, n_blocks=16, block_shape=(4,), seed=0):
    cfg = PoolConfig(n_regions, slots, block_shape)
    placement = np.zeros(n_blocks, np.int32)  # everything starts on region 0
    state = init_state(cfg, n_blocks, placement)
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n_blocks,) + block_shape).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    return cfg, state, data


def copy_to(cfg, state, ids, slots, dst_region):
    """``fused_copy`` of blocks ``ids`` into ``(dst_region, slots)``."""
    loc = np.asarray(state.table)[np.asarray(ids)]
    src = loc[:, REGION] * cfg.slots_per_region + loc[:, SLOT]
    dst = dst_region * cfg.slots_per_region + np.asarray(slots)
    return fused_copy(state, jnp.asarray(src), jnp.asarray(dst))


def commit_to(state, ids, slots, dst_region):
    return commit_areas(state, ids, jnp.full(len(ids), dst_region), slots)


# ---------------------------------------------------------------------------
# Low-level program semantics
# ---------------------------------------------------------------------------


def test_copy_then_commit_clean_flips_table():
    cfg, state, data = make()
    ids = jnp.asarray([0, 1, 2])
    slots = jnp.asarray([0, 1, 2])
    state = begin_areas(state, ids)
    state = copy_to(cfg, state, ids, slots, dst_region=1)
    # table still points at region 0 during the copy (readers see source)
    assert np.asarray(state.table)[:3, REGION].tolist() == [0, 0, 0]
    state, verdict = commit_to(state, ids, slots, dst_region=1)
    assert not np.asarray(verdict).any()
    assert np.asarray(state.table)[:3, REGION].tolist() == [1, 1, 1]
    np.testing.assert_array_equal(np.asarray(leap_read(state, ids)), data[:3])


def test_dirty_write_invalidates_commit():
    cfg, state, data = make()
    ids = jnp.asarray([0, 1])
    slots = jnp.asarray([0, 1])
    state = begin_areas(state, ids)
    state = copy_to(cfg, state, ids, slots, dst_region=1)
    # concurrent write to block 1 *after* its copy
    new = np.full((1, 4), 42.0, np.float32)
    state = leap_write(state, jnp.asarray([1]), jnp.asarray(new))
    state, verdict = commit_to(state, ids, slots, dst_region=1)
    v = np.asarray(verdict)
    assert v.tolist() == [False, True]
    table = np.asarray(state.table)
    assert table[0, REGION] == 1  # clean block migrated
    assert table[1, REGION] == 0  # dirty block kept its old mapping
    # and crucially the write is preserved (the paper's correctness property)
    np.testing.assert_array_equal(np.asarray(leap_read(state, jnp.asarray([1]))), new)


def test_write_before_copy_is_carried():
    cfg, state, data = make()
    ids = jnp.asarray([3])
    slots = jnp.asarray([5])
    state = begin_areas(state, ids)
    new = np.full((1, 4), 7.0, np.float32)
    state = leap_write(state, ids, jnp.asarray(new))  # write DURING epoch, before copy
    state = copy_to(cfg, state, ids, slots, dst_region=1)
    state, verdict = commit_to(state, ids, slots, dst_region=1)
    # footnote-1 semantics: conservatively dirty (unnecessary retry), but the
    # write is never lost.
    assert np.asarray(verdict)[0]
    np.testing.assert_array_equal(np.asarray(leap_read(state, ids)), new)


def test_force_areas_unconditional():
    cfg, state, data = make()
    ids = jnp.asarray([0])
    state = begin_areas(state, ids)
    state = leap_write(state, ids, jnp.full((1, 4), 9.0))
    state = force_areas(state, ids, jnp.asarray([1]), jnp.asarray([4]))
    t = np.asarray(state.table)
    assert t[0].tolist() == [1, 4]
    assert not np.asarray(state.dirty)[0] and not np.asarray(state.in_flight)[0]
    np.testing.assert_array_equal(
        np.asarray(leap_read(state, ids)), np.full((1, 4), 9.0, np.float32)
    )


# ---------------------------------------------------------------------------
# Adaptive splitting
# ---------------------------------------------------------------------------


def test_split_area_only_requeues_dirty():
    a = Area(block_ids=np.arange(8, dtype=np.int32), src_region=0, dst_region=1)
    dirty = np.zeros(8, bool)
    dirty[[2, 3, 6]] = True
    subs = split_area(a, dirty, reduction_factor=2, min_area_blocks=1)
    got = np.concatenate([s.block_ids for s in subs]).tolist()
    assert got == [2, 3, 6]
    assert all(len(s) <= 4 for s in subs)
    assert all(s.attempts == 1 for s in subs)


def test_split_respects_min_area():
    a = Area(block_ids=np.arange(2, dtype=np.int32), src_region=0, dst_region=1, attempts=3)
    subs = split_area(a, np.ones(2, bool), reduction_factor=2, min_area_blocks=2)
    assert len(subs) == 1 and len(subs[0]) == 2 and subs[0].attempts == 4


# ---------------------------------------------------------------------------
# Driver end-to-end
# ---------------------------------------------------------------------------


def test_driver_migrates_all_without_writes():
    cfg, state, data = make(n_blocks=16)
    drv = MigrationDriver(state, cfg, LeapConfig(initial_area_blocks=8))
    n = drv.request(np.arange(16), dst_region=1)
    assert n == 16
    assert drv.drain()
    assert (drv.host_placement() == 1).all()
    assert drv.verify_mirror()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), data)
    assert drv.stats.blocks_migrated == 16
    assert drv.stats.bytes_copied == 16 * cfg.block_bytes  # no retries => optimum


def test_driver_request_skips_resident_and_duplicate():
    cfg, state, data = make(n_blocks=8)
    drv = MigrationDriver(state, cfg)
    placement = np.zeros(8, np.int32)
    assert drv.request(np.arange(8), dst_region=0) == 0  # already resident
    assert drv.request(np.asarray([1, 2]), dst_region=1) == 2
    assert drv.request(np.asarray([2, 3]), dst_region=1) == 1  # 2 already queued
    assert drv.drain()


def test_driver_migration_under_interleaved_writes_preserves_data():
    cfg, state, data = make(n_blocks=32, slots=64)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(initial_area_blocks=16, budget_blocks_per_tick=8),
    )
    drv.request(np.arange(32), dst_region=1)
    rng = np.random.default_rng(1)
    expected = data.copy()
    steps = 0
    while not drv.done and steps < 500:
        drv.tick()
        # concurrent writer: mutate two random blocks between ticks
        ids = rng.choice(32, size=2, replace=False)
        vals = rng.normal(size=(2, 4)).astype(np.float32)
        drv.write(jnp.asarray(ids), jnp.asarray(vals))
        expected[ids] = vals
        steps += 1
    assert drv.drain()
    assert (drv.host_placement() == 1).all()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(32))), expected)
    assert drv.verify_mirror()


def test_driver_force_escalation_terminates_adversarial_writer():
    """A writer that dirties *every* block every tick would livelock the paper's
    protocol; write-through escalation must still terminate."""
    cfg, state, data = make(n_blocks=4, slots=16)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(
            initial_area_blocks=4,
            budget_blocks_per_tick=2,
            max_attempts_before_force=2,
        ),
    )
    drv.request(np.arange(4), dst_region=1)
    rng = np.random.default_rng(2)
    expected = data.copy()
    steps = 0
    while not drv.done and steps < 300:
        drv.tick()
        vals = rng.normal(size=(4, 4)).astype(np.float32)
        drv.write(jnp.arange(4), jnp.asarray(vals))
        expected[:] = vals
        steps += 1
    assert drv.done, "escalation failed to terminate"
    assert (drv.host_placement() == 1).all()
    assert drv.stats.blocks_forced > 0
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(4))), expected)


def test_driver_slot_accounting_no_leak():
    cfg, state, data = make(n_blocks=16, slots=24)
    drv = MigrationDriver(state, cfg, LeapConfig(initial_area_blocks=4))
    for dst in (1, 0, 1):
        drv.request(np.arange(16), dst_region=dst)
        assert drv.drain()
    # after ping-pong, exactly n_blocks slots used in total
    used = sum(
        cfg.slots_per_region - drv.free_slots(r) for r in range(cfg.n_regions)
    )
    assert used == 16
    # free lists contain no duplicates and no in-use slots
    table = drv.host_table()
    for r in range(cfg.n_regions):
        f = set(drv.debug_free_list(r))
        assert len(f) == drv.free_slots(r)
        in_use = set(int(s) for b, s in enumerate(table[:, 1]) if table[b, 0] == r)
        assert not (f & in_use)


# Property tests over arbitrary interleavings: see test_property_migrator.py.
