"""Fig. 9 (beyond paper) — control-path cost of migration dispatch.

The xla backend hands each tick to the device as ONE program, the megastep,
whose operands pad to a budget-floored shared bucket (DESIGN.md §12).  Two
workloads:

  * ``quiet``  — the fig4 drain (no concurrent writes): pure dispatch count.
  * ``storm``  — the fig5 "high" case (concurrent writes -> dirty retries ->
                 adaptive splitting): unique batch lengths, i.e. compile storm.

Reported per workload: drain wall-clock (cold: includes compiles, and
warm: jit caches hot), dispatches/tick, and migration-program jit cache
misses during the run.  The batched generation runs only on the ppermute
backend, which needs a device mesh, so this one-device suite has no row for
it.
"""

import time

import jax
import numpy as np

from benchmarks.common import WriteBurst, emit, make_pool
from repro.core import LeapConfig


def _drain(n_blocks, block_kb, per_tick, seed=0):
    lc = LeapConfig(
        initial_area_blocks=64,
        budget_blocks_per_tick=64,
        max_attempts_before_force=6,
    )
    _, drv, _ = make_pool(n_blocks, block_kb, leap=lc, seed=seed)
    sess = drv.default_session()
    burst = WriteBurst(drv, n_blocks, per_tick)
    h = sess.leap(np.arange(n_blocks), 1)
    t0 = time.perf_counter()
    ticks = 0
    while not h.done and ticks < 20_000:
        sess.tick()
        burst.fire()
        ticks += 1
    ok = h.wait()
    jax.block_until_ready(drv.state.pool)
    dt = time.perf_counter() - t0
    assert ok and drv.verify_mirror()
    return dt, drv.stats


def run(n_blocks=256, block_kb=64):
    results = {}
    for wl_label, per_tick in (("quiet", 0), ("storm", 8)):
        # cold: first drain of this workload pays its compiles;
        # warm: same shapes again, so wall-clock isolates dispatch count.
        t_cold, stats_cold = _drain(n_blocks, block_kb, per_tick, seed=0)
        t_warm, stats_warm = _drain(n_blocks, block_kb, per_tick, seed=1)
        results[wl_label] = t_warm
        emit(
            f"fig9/{wl_label}/megastep",
            t_warm * 1e6,
            f"cold_us={t_cold * 1e6:.0f}"
            f";disp_per_tick={stats_warm.dispatches_per_tick:.2f}"
            f";jit_misses_cold={stats_cold.jit_cache_misses}"
            f";jit_misses_warm={stats_warm.jit_cache_misses}"
            f";retries={stats_warm.dirty_rejections}",
        )
    return results


if __name__ == "__main__":
    run()
