"""Tier-1 tests for the handle-based public API (`repro.api`).

Covers the `page_leap()` contract the facade exposes: request futures with
status/progress, cancellation that never leaks pool slots, strict priority
draining, per-handle deduplication, completion callbacks, the sealed
read-only facade, and pluggable placement policies.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import HandleStatus, LeapSession, Move, PoolFacade
from repro.core import (
    AutoBalanceConfig,
    AutoBalancer,
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
)


def make(n_blocks=16, slots=24, n_regions=2, huge_factor=1, **leap_kw):
    cfg = PoolConfig(n_regions, slots, (4,), huge_factor=huge_factor)
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    data = np.arange(n_blocks * 4, dtype=np.float32).reshape(n_blocks, 4)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    kw = dict(initial_area_blocks=4, budget_blocks_per_tick=4)
    kw.update(leap_kw)
    drv = MigrationDriver(state, cfg, LeapConfig(**kw))
    return cfg, drv, LeapSession(drv), data


def used_slots(cfg, drv):
    return sum(
        cfg.slots_per_region - drv.free_slots(r) for r in range(cfg.n_regions)
    )


# ---------------------------------------------------------------------------
# Handle lifecycle
# ---------------------------------------------------------------------------


def test_leap_commits_and_reports_progress():
    cfg, drv, sess, data = make()
    h = sess.leap(np.arange(16), 1)
    assert h.status == HandleStatus.QUEUED and h.requested == 16
    assert h.wait()
    assert h.status == HandleStatus.COMMITTED and h.done
    p = h.progress()
    assert p.committed + p.forced + p.cancelled == p.requested == 16
    assert p.remaining == 0 and p.cancelled == 0
    # handle accounting agrees with the global stats on this single request
    stats = sess.facade.snapshot_stats()
    assert stats.blocks_migrated == p.committed
    assert stats.blocks_forced == p.forced
    assert stats.blocks_cancelled == 0
    assert (sess.facade.placement() == 1).all()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), data)
    assert drv.verify_mirror()


def test_status_transitions_through_copying():
    _, drv, sess, _ = make(budget_blocks_per_tick=2, initial_area_blocks=2)
    h = sess.leap(np.arange(16), 1)
    assert h.status == HandleStatus.QUEUED
    sess.tick()
    assert h.status == HandleStatus.COPYING  # epochs open, nothing resolved
    assert h.wait()
    assert h.status == HandleStatus.COMMITTED


def test_on_done_callback_fires_exactly_once():
    _, drv, sess, _ = make()
    fired = []
    h = sess.leap(np.arange(8), 1, on_done=fired.append)
    assert fired == []
    assert h.wait()
    assert fired == [h]
    sess.drain()
    assert fired == [h]
    # vacuous request: callback fires immediately at submit time
    fired2 = []
    h2 = sess.leap(np.arange(8), 1, on_done=fired2.append)  # already there
    assert h2.requested == 0 and h2.done and fired2 == [h2]


# ---------------------------------------------------------------------------
# Cancellation
# ---------------------------------------------------------------------------


def test_cancel_before_copy_frees_everything():
    cfg, drv, sess, data = make()
    h = sess.leap(np.arange(16), 1)
    dropped = h.cancel()
    assert dropped == 16
    assert h.status == HandleStatus.CANCELLED and h.done
    p = h.progress()
    assert p.cancelled == p.requested == 16 and p.committed == p.forced == 0
    assert drv.done  # nothing left to migrate
    assert used_slots(cfg, drv) == 16  # no destination slot leaked
    assert drv.verify_mirror()
    assert (sess.facade.placement() == 0).all()  # untouched placement
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), data)
    # blocks are free for a fresh request afterwards
    h2 = sess.leap(np.arange(16), 1)
    assert h2.requested == 16 and h2.wait()


def test_cancel_mid_epoch_terminates_without_leaks():
    cfg, drv, sess, data = make(budget_blocks_per_tick=4, initial_area_blocks=4)
    h = sess.leap(np.arange(16), 1)
    sess.tick()  # opens epochs for the first areas and starts copying
    assert h.status == HandleStatus.COPYING
    # dirty every block so in-flight epochs reject at commit
    vals = np.ones((16, 4), np.float32)
    drv.write(jnp.arange(16), jnp.asarray(vals))
    h.cancel()
    assert sess.drain()  # in-flight epochs finish their verdict, then stop
    assert h.done
    p = h.progress()
    assert p.committed + p.forced + p.cancelled == p.requested == 16
    assert p.cancelled > 0  # queued areas (and dirty in-flight) were dropped
    assert h.status in (HandleStatus.CANCELLED, HandleStatus.PARTIAL)
    assert used_slots(cfg, drv) == 16
    assert drv.verify_mirror()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), vals)


def test_cancel_tiered_pool_keeps_invariants():
    G = 4
    cfg, drv, sess, data = make(n_blocks=16, slots=32, huge_factor=G)
    assert drv.adopt_huge(np.arange(16 // G)) == 16 // G
    h = sess.leap(np.arange(16), 1)
    assert h.cancel() == 16
    assert h.status == HandleStatus.CANCELLED
    assert drv.done and drv.verify_mirror() and drv.verify_tiers()
    assert used_slots(cfg, drv) == 16
    h2 = sess.leap(np.arange(16), 1)
    assert h2.wait() and drv.verify_tiers()
    assert (sess.facade.placement() == 1).all()


def test_cancel_is_idempotent():
    _, drv, sess, _ = make()
    h = sess.leap(np.arange(8), 1)
    assert h.cancel() == 8
    assert h.cancel() == 0
    assert h.progress().cancelled == 8  # not double-counted


# ---------------------------------------------------------------------------
# Priorities and deduplication
# ---------------------------------------------------------------------------


def test_priorities_drain_high_before_low():
    _, drv, sess, _ = make(budget_blocks_per_tick=4, initial_area_blocks=4)
    order = []
    h_low = sess.leap(np.arange(8), 1, priority=0,
                      on_done=lambda h: order.append("low"))
    h_high = sess.leap(np.arange(8, 16), 1, priority=5,
                       on_done=lambda h: order.append("high"))
    assert sess.drain()
    assert order == ["high", "low"]
    assert h_high.done and h_low.done


def test_duplicate_request_dedupes_to_vacuous_handle():
    _, drv, sess, _ = make()
    h1 = sess.leap(np.arange(8), 1)
    h2 = sess.leap(np.arange(8), 1)  # same blocks, still in flight
    assert h1.requested == 8
    assert h2.requested == 0 and h2.done
    assert h2.status == HandleStatus.COMMITTED
    assert sess.drain() and h1.done


def test_overlapping_request_accounts_only_new_blocks():
    _, drv, sess, _ = make()
    h1 = sess.leap(np.arange(8), 1)
    h2 = sess.leap(np.arange(4, 12), 1)  # 4..7 dedupe away, 8..11 enqueue
    assert h1.requested == 8 and h2.requested == 4
    assert sess.drain()
    p1, p2 = h1.progress(), h2.progress()
    assert p1.committed + p1.forced == 8
    assert p2.committed + p2.forced == 4
    assert (sess.facade.placement()[:12] == 1).all()


def test_high_priority_to_full_region_does_not_livelock():
    """A high-priority request to a slot-exhausted region must not starve the
    lower-priority migrations whose commits would free those slots."""
    cfg = PoolConfig(2, 8, (4,))
    # region 1 completely full (8/8); region 0 half full
    placement = np.asarray([0, 0, 0, 0] + [1] * 8, np.int32)
    state = init_state(cfg, 12, placement)
    drv = MigrationDriver(
        state, cfg, LeapConfig(initial_area_blocks=4, budget_blocks_per_tick=8)
    )
    sess = LeapSession(drv)
    h_evac = sess.leap(np.arange(4, 12), 0, priority=0)  # frees region 1...
    h_urgent = sess.leap(np.arange(4), 1, priority=10)  # ...which this needs
    assert sess.drain(max_ticks=200), "priority head-of-line livelock"
    assert h_evac.done and h_urgent.done
    assert (sess.facade.placement()[:4] == 1).all()
    assert (sess.facade.placement()[4:] == 0).all()
    assert drv.verify_mirror()


def test_move_priority_zero_is_honored_by_apply():
    _, drv, sess, _ = make()
    (h,) = sess.submit_moves([Move(np.arange(4), 1, priority=0)], priority=5)
    assert h.priority == 0  # explicit 0 is not overridden by the default
    (h2,) = sess.submit_moves([Move(np.arange(4, 8), 1)], priority=5)
    assert h2.priority == 5  # None defers to the apply() default
    assert sess.drain()


def test_terminal_requests_and_handles_are_pruned():
    _, drv, sess, _ = make()
    h = sess.leap(np.arange(8), 1)
    assert drv.requests and sess.handles == (h,)
    assert h.wait()
    sess.leap(np.arange(8), 0)  # next issue prunes terminal entries
    assert h.request_id not in drv.requests
    assert h not in sess.handles
    assert h.progress().committed + h.progress().forced == 8  # handle still reads
    assert sess.drain()


def test_duplicate_ids_within_one_call_collapse():
    _, drv, sess, _ = make()
    h = sess.leap(np.asarray([3, 3, 3, 5, 5]), 1)
    assert h.requested == 2
    assert h.wait() and h.progress().committed + h.progress().forced == 2


# ---------------------------------------------------------------------------
# Sealed facade
# ---------------------------------------------------------------------------


def test_facade_is_sealed_and_hands_out_copies():
    cfg, drv, sess, _ = make()
    facade = sess.facade
    assert isinstance(facade, PoolFacade)
    with pytest.raises(AttributeError):
        facade.driver = None
    with pytest.raises(AttributeError):
        facade.anything = 1
    place = facade.placement()
    place[:] = 99  # mutating the copy must not poison the driver
    assert (facade.placement() == 0).all()
    stats = facade.snapshot_stats()
    stats.blocks_migrated = 10**6
    assert facade.snapshot_stats().blocks_migrated != 10**6
    assert facade.free_slots(0) == cfg.slots_per_region - 16
    assert facade.region_of(0) == 0 and facade.slot_of(0) == 0
    assert facade.n_blocks == 16 and facade.n_regions == 2
    assert facade.verify_mirror()


# ---------------------------------------------------------------------------
# Pluggable placement policy
# ---------------------------------------------------------------------------


def test_autobalancer_policy_through_session():
    cfg, drv, sess, _ = make()
    ab = AutoBalancer(cfg, 16, AutoBalanceConfig(hot_threshold=2))
    for _ in range(3):  # region-1 readers keep hitting remote blocks 0..7
        ab.observe_driver(drv, np.arange(8), 1)
    handles = sess.apply(ab)
    assert len(handles) == 1 and handles[0].requested == 8
    assert sess.drain()
    assert (sess.facade.placement()[:8] == 1).all()
    assert (sess.facade.placement()[8:] == 0).all()
    # once local, the policy proposes nothing
    assert ab.decide(sess.facade) == []


def test_static_moves_and_tags():
    _, drv, sess, _ = make()
    handles = sess.submit_moves(
        [Move(np.arange(4), 1, priority=1, tag="a"), (np.arange(4, 8), 1)]
    )
    assert [h.tag for h in handles] == ["a", None]
    assert sess.drain() and all(h.done for h in handles)


# ---------------------------------------------------------------------------
# Legacy shims
# ---------------------------------------------------------------------------


def test_legacy_request_drain_shims_still_work():
    _, drv, sess, data = make()
    with pytest.warns(DeprecationWarning):
        n = drv.request(np.arange(16), 1)
    assert n == 16
    assert drv.drain()
    assert (drv.host_placement() == 1).all()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), data)
