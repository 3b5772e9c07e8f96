"""Paper Figs. 5/7 — migration under concurrent writes (small + huge blocks).

For each write-pressure case (low / high / extreme / skewed) and method
(page_leap at two initial area sizes, move_pages, auto-balancing):
migration completion time, achieved write throughput vs a no-migration
baseline, and final page status (reliability).  The paper's headline
results to reproduce: leap wins at the recommended initial size, adapts
under extreme pressure via splitting, and (unlike auto balancing) always
migrates everything.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import WriteBurst, emit, make_pool
from repro.api import LeapSession
from repro.core import AutoBalanceConfig, AutoBalancer, LeapConfig, SyncResharder

CASES = [  # (label, writes/tick, skew)
    ("low", 1, 0.0),
    ("high", 8, 0.0),
    ("extreme", 64, 0.0),
    ("skewed", 8, 0.75),
]


def _no_migration_throughput(n_blocks, block_kb, per_tick, ticks=60):
    _, drv, _ = make_pool(n_blocks, block_kb)
    burst = WriteBurst(drv, n_blocks, per_tick)
    t0 = time.perf_counter()
    for _ in range(ticks):
        burst.fire()
    jax.block_until_ready(drv.state.pool)
    return burst.done / (time.perf_counter() - t0)


def _leap(n_blocks, block_kb, per_tick, skew, area_blocks, label, huge_factor=1):
    lc = LeapConfig(
        initial_area_blocks=area_blocks,
        budget_blocks_per_tick=64,
        max_attempts_before_force=6,
    )
    _, drv, _ = make_pool(
        n_blocks, block_kb, leap=lc, huge_factor=huge_factor, adopt=huge_factor > 1
    )
    sess = LeapSession(drv)
    burst = WriteBurst(drv, n_blocks, per_tick, skew)
    handle = sess.leap(np.arange(n_blocks), 1)
    t0 = time.perf_counter()
    ticks = 0
    while not handle.done and ticks < 5000:
        sess.tick()
        burst.fire()
        ticks += 1
    ok = handle.wait(10_000)
    jax.block_until_ready(drv.state.pool)
    dt = time.perf_counter() - t0
    p = handle.progress()
    assert p.committed + p.forced + p.cancelled == p.requested, p
    stats = sess.facade.snapshot_stats()
    migrated = int((sess.facade.placement() == 1).sum())
    thr = burst.done / dt if dt > 0 else 0
    return dict(
        time=dt, thr=thr, migrated=migrated, retries=stats.dirty_rejections,
        forced=p.forced,
        extra_mb=stats.extra_bytes(drv.pool_cfg.block_bytes) / 2**20, ok=ok,
        demotions=stats.demotions,
        huge_committed=stats.huge_areas_committed,
    )


def _move_pages(n_blocks, block_kb, per_tick, skew):
    cfg, drv, _ = make_pool(n_blocks, block_kb)
    burst = WriteBurst(drv, n_blocks, per_tick, skew)
    rs = SyncResharder(cfg, fresh_alloc=True)
    t0 = time.perf_counter()
    # writes land before and after, but the call itself blocks them entirely
    burst.fire()
    res = rs.migrate_driver(drv, np.arange(n_blocks), 1)
    burst.fire()
    dt = time.perf_counter() - t0
    return dict(time=dt, thr=burst.done / dt, migrated=len(res.migrated),
                failed=len(res.failed))


def _autobalance(n_blocks, block_kb, per_tick, skew, ticks=400):
    cfg, drv, _ = make_pool(n_blocks, block_kb)
    burst = WriteBurst(drv, n_blocks, per_tick, skew)
    ab = AutoBalancer(cfg, n_blocks, AutoBalanceConfig(scan_budget_blocks=64))
    t0 = time.perf_counter()
    done_at = None
    for tick in range(ticks):
        ab.observe_driver(drv, np.arange(0, n_blocks, 4), 1)  # reader hints
        burst.fire()
        ab.observe_writes(burst.per_tick)
        ab.scan_driver(drv)
        if done_at is None and (drv.host_placement() == 1).all():
            done_at = time.perf_counter() - t0
            break
    jax.block_until_ready(drv.state.pool)
    dt = time.perf_counter() - t0
    migrated = int((drv.host_placement() == 1).sum())
    return dict(time=done_at or dt, thr=burst.done / dt, migrated=migrated)


def run(n_blocks=256, block_kb=64, page_label="small", huge_factor=1):
    total_mb = n_blocks * block_kb / 1024
    for label, per_tick, skew in CASES:
        _no_migration_throughput(n_blocks, block_kb, per_tick, ticks=5)  # warm
        base_thr = _no_migration_throughput(n_blocks, block_kb, per_tick)
        for area in (8, 64):
            _leap(n_blocks, block_kb, per_tick, skew, area, label, huge_factor)  # warm
            r = _leap(n_blocks, block_kb, per_tick, skew, area, label, huge_factor)
            tier = (
                f";huge_committed={r['huge_committed']};demotions={r['demotions']}"
                if huge_factor > 1
                else ""
            )
            emit(
                f"fig5_{page_label}/{label}/leap_area{area * block_kb}KB",
                r["time"] * 1e6,
                f"thr={100 * r['thr'] / base_thr:.0f}%;migrated={100 * r['migrated'] / n_blocks:.0f}%"
                f";retries={r['retries']};forced={r['forced']};extra={r['extra_mb']:.1f}MB"
                + tier,
            )
        _move_pages(n_blocks, block_kb, per_tick, skew)  # warm
        r = _move_pages(n_blocks, block_kb, per_tick, skew)
        emit(
            f"fig5_{page_label}/{label}/move_pages",
            r["time"] * 1e6,
            f"thr={100 * r['thr'] / base_thr:.0f}%;migrated={100 * r['migrated'] / n_blocks:.0f}%"
            f";failed={r['failed']}",
        )
        _autobalance(n_blocks, block_kb, per_tick, skew, ticks=20)  # warm
        r = _autobalance(n_blocks, block_kb, per_tick, skew)
        emit(
            f"fig5_{page_label}/{label}/auto_balance",
            r["time"] * 1e6,
            f"thr={100 * r['thr'] / base_thr:.0f}%;migrated={100 * r['migrated'] / n_blocks:.0f}%",
        )
    return True


def run_huge(real_tier: bool = True):
    """Paper Fig. 7 companion: migration under writes at huge granularity.

    ``real_tier=True`` (default) runs the actual two-tier pool — 8-slot huge
    blocks with buddy allocation, run copies, all-or-nothing commits, and
    §4.2 demotion under pressure.  ``real_tier=False`` keeps the old stand-in
    (8x larger uniform blocks, no tier interactions) for comparison.
    """
    if real_tier:
        return run(n_blocks=256, block_kb=64, page_label="huge", huge_factor=8)
    return run(n_blocks=64, block_kb=512, page_label="huge8x")


if __name__ == "__main__":
    run()
    run_huge()
