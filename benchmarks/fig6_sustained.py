"""Paper Fig. 6 — achieved write throughput over a fixed horizon while the
migration runs to completion (fast migration = local accesses earlier, so
the faster migrator sustains higher requested rates).

Here all methods run for a fixed tick budget; ``derived`` reports achieved
throughput as % of the no-migration baseline and the migrated fraction.
"""

import time

import jax
import numpy as np

from benchmarks.common import WriteBurst, emit, make_pool
from repro.api import LeapSession
from repro.core import AutoBalanceConfig, AutoBalancer, LeapConfig, SyncResharder

TICKS = 120


def run(n_blocks=256, block_kb=64, _warmed=[]):
    if not _warmed:
        for pt in (2, 8, 32, 128):  # compile-cache warmup for every shape
            _, d, _ = make_pool(n_blocks, block_kb,
                                leap=LeapConfig(initial_area_blocks=64, budget_blocks_per_tick=64,
                                                max_attempts_before_force=6))
            s = LeapSession(d)
            b = WriteBurst(d, n_blocks, pt)
            s.leap(np.arange(n_blocks), 1)
            for _ in range(3):
                s.tick(); b.fire()
            s.drain()
            cfgx, dx, _ = make_pool(n_blocks, block_kb)
            SyncResharder(cfgx, fresh_alloc=True).migrate_driver(dx, np.arange(n_blocks), 1)
        _warmed.append(True)
    for per_tick in (2, 8, 32, 128):
        base_thr = None
        # baseline: writes only
        _, d0, _ = make_pool(n_blocks, block_kb)
        b0 = WriteBurst(d0, n_blocks, per_tick)
        t0 = time.perf_counter()
        for _ in range(TICKS):
            b0.fire()
        jax.block_until_ready(d0.state.pool)
        base_thr = b0.done / (time.perf_counter() - t0)

        # page_leap (recommended initial area)
        lc = LeapConfig(initial_area_blocks=64, budget_blocks_per_tick=64,
                        max_attempts_before_force=6)
        _, d1, _ = make_pool(n_blocks, block_kb, leap=lc)
        s1 = LeapSession(d1)
        b1 = WriteBurst(d1, n_blocks, per_tick)
        h1 = s1.leap(np.arange(n_blocks), 1)
        t0 = time.perf_counter()
        for _ in range(TICKS):
            if not h1.done:
                s1.tick()
            b1.fire()
        jax.block_until_ready(d1.state.pool)
        thr1 = b1.done / (time.perf_counter() - t0)
        emit(
            f"fig6/leap_rate{per_tick}",
            1e6 * TICKS / max(thr1, 1),
            f"thr={100 * thr1 / base_thr:.0f}%;migrated={100 * (d1.host_placement() == 1).mean():.0f}%",
        )

        # move_pages: one blocking call at t=0
        cfg, d2, _ = make_pool(n_blocks, block_kb)
        b2 = WriteBurst(d2, n_blocks, per_tick)
        rs = SyncResharder(cfg, fresh_alloc=True)
        t0 = time.perf_counter()
        rs.migrate_driver(d2, np.arange(n_blocks), 1)
        for _ in range(TICKS):
            b2.fire()
        jax.block_until_ready(d2.state.pool)
        thr2 = b2.done / (time.perf_counter() - t0)
        emit(
            f"fig6/move_pages_rate{per_tick}",
            1e6 * TICKS / max(thr2, 1),
            f"thr={100 * thr2 / base_thr:.0f}%;migrated={100 * (d2.host_placement() == 1).mean():.0f}%",
        )

        # auto balancing
        cfg, d3, _ = make_pool(n_blocks, block_kb)
        b3 = WriteBurst(d3, n_blocks, per_tick)
        ab = AutoBalancer(cfg, n_blocks, AutoBalanceConfig(scan_budget_blocks=64))
        t0 = time.perf_counter()
        for _ in range(TICKS):
            ab.observe_driver(d3, np.arange(0, n_blocks, 4), 1)
            b3.fire()
            ab.observe_writes(per_tick)
            ab.scan_driver(d3)
        jax.block_until_ready(d3.state.pool)
        thr3 = b3.done / (time.perf_counter() - t0)
        emit(
            f"fig6/auto_balance_rate{per_tick}",
            1e6 * TICKS / max(thr3, 1),
            f"thr={100 * thr3 / base_thr:.0f}%;migrated={100 * (d3.host_placement() == 1).mean():.0f}%",
        )
    return True


if __name__ == "__main__":
    run()
