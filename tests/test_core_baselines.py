"""Tests for the move_pages()-analogue sync resharder and the auto-balancer.

Both baselines are scheduler-policy configurations of the shared migration
pipeline (no standalone migration loop): these tests drive them through a
:class:`MigrationDriver` and check the move_pages()/autonuma semantics —
synchronous completion, EBUSY skip with no retry, the fresh-allocation zero
pass, the defer-under-write-pressure gate — plus that the traffic really
went through the engine's force path (stats account it).
"""

import jax.numpy as jnp
import numpy as np

from repro.chaos import InvariantChecker
from repro.core import (
    AutoBalanceConfig,
    AutoBalancer,
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    SyncResharder,
    init_state,
    leap_write,
)
from repro.core.migrator import begin_areas


def make(n_blocks=8, n_regions=2, slots=16):
    cfg = PoolConfig(n_regions, slots, (4,))
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    data = np.arange(n_blocks * 4, dtype=np.float32).reshape(n_blocks, 4)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    drv = MigrationDriver(state, cfg, LeapConfig())
    return cfg, drv, data


def test_sync_reshard_moves_and_preserves():
    cfg, drv, data = make()
    rs = SyncResharder(cfg)
    res = rs.migrate_driver(drv, np.arange(8), dst_region=1)
    assert len(res.migrated) == 8 and len(res.failed) == 0
    assert (drv.host_placement() == 1).all()
    # mirror/slot/accounting/payload invariants via the shared checker
    InvariantChecker(drv).check_final(expected=data)
    # fresh allocation pays a zero pass on top of the copy
    assert res.bytes_touched == 2 * res.bytes_copied
    # the move went through the shared pipeline's force path, not a side loop
    assert drv.stats.blocks_forced == 8 and drv.stats.blocks_migrated == 0


def test_sync_reshard_skips_busy_blocks():
    cfg, drv, data = make()
    drv.state = begin_areas(drv.state, jnp.asarray([2, 5]))  # blocks 2,5 are "busy"
    rs = SyncResharder(cfg)
    res = rs.migrate_driver(drv, np.arange(8), dst_region=1)
    assert sorted(res.failed.tolist()) == [2, 5]  # no retry: unreliable
    placement = drv.host_placement()
    assert placement[2] == 0 and placement[5] == 0
    assert (placement[[0, 1, 3, 4, 6, 7]] == 1).all()


def test_sync_reshard_skips_blocks_claimed_by_live_leap_requests():
    cfg, drv, data = make()
    sess = drv.default_session()
    h = sess.leap(np.asarray([0, 1]), 1)  # queued, epoch not yet open
    rs = SyncResharder(cfg)
    res = rs.migrate_driver(drv, np.arange(8), dst_region=1)
    assert sorted(res.failed.tolist()) == [0, 1]
    assert sorted(res.migrated.tolist()) == [2, 3, 4, 5, 6, 7]
    assert h.wait()  # the leap request still completes on its own
    assert (drv.host_placement() == 1).all()
    InvariantChecker(drv).check_final(expected=data)


def test_sync_reshard_pooled_mode_no_zero_pass():
    cfg, drv, data = make()
    rs = SyncResharder(cfg, fresh_alloc=False)
    res = rs.migrate_driver(drv, np.arange(4), dst_region=1)
    assert res.bytes_touched == res.bytes_copied


def test_sync_reshard_out_of_slots_raises():
    cfg = PoolConfig(2, 8, (4,))
    state = init_state(cfg, 14, np.asarray([0] * 7 + [1] * 7, np.int32))
    drv = MigrationDriver(state, cfg)
    rs = SyncResharder(cfg)
    try:
        rs.migrate_driver(drv, np.arange(7), dst_region=1)
    except RuntimeError as e:
        assert "out of slots" in str(e)
    else:  # pragma: no cover
        raise AssertionError("expected RuntimeError")


def test_autobalancer_migrates_hot_blocks_when_idle():
    cfg, drv, data = make()
    ab = AutoBalancer(cfg, 8, AutoBalanceConfig(hot_threshold=3))
    for _ in range(4):
        ab.observe_driver(drv, np.asarray([0, 1]), reader_region=1)
    moved = ab.scan_driver(drv)
    assert moved == 2
    placement = drv.host_placement()
    assert placement[0] == 1 and placement[1] == 1
    InvariantChecker(drv).check_final(expected=data)
    assert ab.blocks_migrated == 2
    assert ab.bytes_copied == 2 * cfg.block_bytes
    # unconditional kernel-style moves ride the engine's force path
    assert drv.stats.blocks_forced == 2


def test_autobalancer_defers_under_write_pressure():
    cfg, drv, data = make()
    ab = AutoBalancer(cfg, 8, AutoBalanceConfig(hot_threshold=1, pressure_threshold=0.1))
    ab.observe_driver(drv, np.arange(8), reader_region=1)
    ab.observe_writes(100)  # heavy write burst
    assert ab.scan_driver(drv) == 0  # "waits for times of little load"
    assert ab.scan_driver(drv) > 0  # pressure cleared


def test_autobalancer_bidirectional_scan_preserves_payloads():
    # Regression: both directions move in ONE scan tick, so one move's
    # freshly-freed source slot is immediately reallocated as the other
    # direction's zero-filled destination.  The zero pass must never land
    # before the force program has read the slot (silent corruption:
    # verify_mirror stayed true while the payload read back as zeros).
    cfg = PoolConfig(2, 16, (4,))
    state = init_state(cfg, 8, np.asarray([0, 0, 0, 0, 1, 1, 1, 1], np.int32))
    data = np.arange(32, dtype=np.float32).reshape(8, 4) + 1.0
    state = leap_write(state, jnp.arange(8), jnp.asarray(data))
    drv = MigrationDriver(state, cfg)
    ab = AutoBalancer(cfg, 8, AutoBalanceConfig(hot_threshold=1))
    ab.observe_driver(drv, np.asarray([0]), reader_region=1)  # 0 -> region 1
    ab.observe_driver(drv, np.asarray([4]), reader_region=0)  # 4 -> region 0
    assert ab.scan_driver(drv) == 2
    # the shared payload-integrity check is exactly what this regression
    # needs: structural invariants stayed green while the data went to zero
    InvariantChecker(drv).check_final(expected=data)


def test_sync_reshard_on_tiered_pool_splits_huge_mappings():
    # move_pages()-style requests split huge mappings (THP split on
    # migration) and force the members as small blocks — tier invariants
    # hold and the request really goes through the force path.
    cfg = PoolConfig(2, 32, (4,), huge_factor=4)
    state = init_state(cfg, 16, np.zeros(16, np.int32))
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    state = leap_write(state, jnp.arange(16), jnp.asarray(data))
    drv = MigrationDriver(state, cfg)
    assert drv.adopt_huge(np.arange(4)) == 4
    rs = SyncResharder(cfg)
    res = rs.migrate_driver(drv, np.arange(16), dst_region=1)
    assert len(res.migrated) == 16 and len(res.failed) == 0
    assert (drv.host_placement() == 1).all()
    # tier consistency (buddy + two-level table) rides the shared checker
    InvariantChecker(drv).check_final(expected=data)
    assert drv.stats.demotions == 4 and drv.stats.blocks_forced == 16


def test_autobalancer_scan_does_not_drain_unrelated_requests():
    cfg = PoolConfig(2, 64, (4,))
    state = init_state(cfg, 40, np.zeros(40, np.int32))
    drv = MigrationDriver(
        state, cfg, LeapConfig(initial_area_blocks=4, budget_blocks_per_tick=4)
    )
    sess = drv.default_session()
    # 32 slowly-paced blocks at a priority below the scan's moves, so the
    # scan's areas drain first and its wait loop has no reason to finish them
    background = sess.leap(np.arange(8, 40), 1, priority=-1)
    ab = AutoBalancer(cfg, 40, AutoBalanceConfig(hot_threshold=1))
    ab.observe_driver(drv, np.arange(4), reader_region=1)
    moved = ab.scan_driver(drv)
    assert moved == 4
    # the scan waited for its own moves only; the big request is still going
    assert not background.done
    assert sess.drain()  # and it still completes normally afterwards


def test_autobalancer_respects_destination_capacity():
    cfg = PoolConfig(2, 8, (4,))
    state = init_state(cfg, 14, np.asarray([0] * 7 + [1] * 7, np.int32))
    drv = MigrationDriver(state, cfg)
    ab = AutoBalancer(cfg, 14, AutoBalanceConfig(hot_threshold=1))
    ab.observe_driver(drv, np.arange(7), reader_region=1)
    moved = ab.scan_driver(drv)  # only one free slot on region 1
    assert moved == 1
    InvariantChecker(drv).check_final()
