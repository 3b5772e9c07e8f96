"""Paper Table 2 — memory and time overhead of page_leap() over raw memcpy
under concurrent writes (100K-writes/s analogue = the "high" case).

memory overhead: extra bytes copied due to dirty retries (stats-based).
time overhead: wall time over copying the same useful bytes via raw copy.
control-path cost: device dispatches per tick and migration-program jit
compiles incurred during the run (fig9_dispatch.py measures these on the
drain and retry-storm workloads).

Runs the default dispatch generation (megastep: the whole tick as ONE
device program) with ``warm_dispatch=True``: steady-state variants compile
ahead of time at pool attach, mirroring how ``t_opt`` is itself measured
with the raw-copy program already warm — both sides of the overhead ratio
exclude one-time XLA compiles.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import WriteBurst, emit, flat_copy_ids, make_pool, timeit
from repro.core import LeapConfig
from repro.core.migrator import fused_copy


def run(n_blocks=256, block_kb=64, per_tick=8):
    from benchmarks.common import timeit_inplace

    ids, slots = jnp.arange(n_blocks), jnp.arange(n_blocks)
    cfg, drv0, _ = make_pool(n_blocks, block_kb)
    src, dst = flat_copy_ids(drv0, ids, 1, slots)
    st = fused_copy(drv0.state, src, dst)
    t_opt, _ = timeit_inplace(lambda s: fused_copy(s, src, dst), st)
    useful_mb = n_blocks * block_kb / 1024

    for area_blocks in (1, 8, 64, 256):
        lc = LeapConfig(
            initial_area_blocks=area_blocks,
            budget_blocks_per_tick=64,
            max_attempts_before_force=8,
            warm_dispatch=True,
        )
        _, drv, _ = make_pool(n_blocks, block_kb, leap=lc)
        sess = drv.default_session()
        burst = WriteBurst(drv, n_blocks, per_tick)
        # Warm the write-path program off the clock, like t_opt: the row
        # measures migration overhead, not the load generator's XLA compile.
        # (Writes block 0 directly so the burst's seeded stream — and with
        # it the retry pattern — is untouched.)
        drv.write(jnp.zeros(per_tick, dtype=jnp.int32), burst._vals)
        jax.block_until_ready(drv.state.pool)
        h = sess.leap(np.arange(n_blocks), 1)
        t0 = time.perf_counter()
        while not h.done:
            sess.tick()
            burst.fire()
        sess.drain()
        jax.block_until_ready(drv.state.pool)
        dt = time.perf_counter() - t0
        extra = drv.stats.extra_bytes(drv.pool_cfg.block_bytes)
        emit(
            f"table2/area_{area_blocks * block_kb}KB",
            dt * 1e6,
            f"mem_overhead={100 * extra / (useful_mb * 2**20):.1f}%"
            f";time_overhead={100 * (dt / t_opt - 1):.0f}%"
            f";retries={drv.stats.dirty_rejections}"
            f";disp_per_tick={drv.stats.dispatches_per_tick:.2f}"
            f";jit_misses={drv.stats.jit_cache_misses}",
        )

    # Two-tier pool: same workload at huge granularity, reporting the
    # per-tier MigrationStats counters (huge commits / demotions / promotions
    # / contiguous-run copy traffic).
    G = 8
    lc = LeapConfig(
        initial_area_blocks=64,
        budget_blocks_per_tick=64,
        demote_after_attempts=2,
        max_attempts_before_force=8,
        warm_dispatch=True,
    )
    _, drv, _ = make_pool(n_blocks, block_kb, leap=lc, huge_factor=G, adopt=True)
    sess = drv.default_session()
    burst = WriteBurst(drv, n_blocks, per_tick)
    drv.write(jnp.zeros(per_tick, dtype=jnp.int32), burst._vals)
    jax.block_until_ready(drv.state.pool)
    h = sess.leap(np.arange(n_blocks), 1)
    t0 = time.perf_counter()
    while not h.done:
        sess.tick()
        burst.fire()
    sess.drain()
    jax.block_until_ready(drv.state.pool)
    dt = time.perf_counter() - t0
    s = drv.stats
    extra = s.extra_bytes(drv.pool_cfg.block_bytes)
    emit(
        f"table2/huge_tier_{G * block_kb}KB",
        dt * 1e6,
        f"mem_overhead={100 * extra / (useful_mb * 2**20):.1f}%"
        f";huge_committed={s.huge_areas_committed}"
        f";demotions={s.demotions}"
        f";promotions={s.promotions}"
        f";huge_MB={s.bytes_copied_huge / 2**20:.1f}"
        f";retries={s.dirty_rejections}"
        f";disp_per_tick={s.dispatches_per_tick:.2f}"
        f";jit_misses={s.jit_cache_misses}",
    )
    return True


if __name__ == "__main__":
    run()
