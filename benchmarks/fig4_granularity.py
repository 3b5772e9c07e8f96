"""Paper Fig. 4 — migration time vs (initial) area size, no concurrent
writes.  page_leap() sweeps area sizes; move_pages() and raw memcpy are the
baselines.  Expected shape (validated in EXPERIMENTS.md): tiny areas pay
per-dispatch overhead, large areas approach the copy optimum.
``derived`` = multiple of the memcpy optimum (1.0 = reached it).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, flat_copy_ids, make_pool, timeit
from repro.core import LeapConfig, SyncResharder
from repro.core.migrator import fused_copy


def run(n_blocks=512, block_kb=64):
    total_mb = n_blocks * block_kb / 1024
    ids, slots = jnp.arange(n_blocks), jnp.arange(n_blocks)

    from benchmarks.common import timeit_inplace

    cfg, drv, _ = make_pool(n_blocks, block_kb)
    src, dst = flat_copy_ids(drv, ids, 1, slots)
    st = fused_copy(drv.state, src, dst)
    t_opt, _ = timeit_inplace(lambda s: fused_copy(s, src, dst), st)
    emit(f"fig4/memcpy_optimum_{total_mb:.0f}MB", t_opt * 1e6, "x1.00")

    out = {}
    for area_blocks in (1, 4, 16, 64, 128, 256):
        area_kb = area_blocks * block_kb
        lc = LeapConfig(
            initial_area_blocks=area_blocks,
            budget_blocks_per_tick=max(64, area_blocks),
        )
        ts = []
        for rep in range(3):
            _, d, _ = make_pool(n_blocks, block_kb, leap=lc, seed=rep)
            t0 = time.perf_counter()
            s = d.default_session()
            assert s.leap(np.arange(n_blocks), 1).wait()
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        out[area_kb] = t
        emit(
            f"fig4/page_leap_area_{area_kb}KB",
            t * 1e6,
            f"x{t / t_opt:.2f};dispatches={d.stats.dispatches}",
        )

    ts = []
    for rep in range(3):
        cfg2, d2, _ = make_pool(n_blocks, block_kb, seed=rep)
        rs = SyncResharder(cfg2, fresh_alloc=True)
        t0 = time.perf_counter()
        rs.migrate_driver(d2, np.arange(n_blocks), 1)
        ts.append(time.perf_counter() - t0)
    t_mp = float(np.median(ts))
    emit(f"fig4/move_pages_{total_mb:.0f}MB", t_mp * 1e6, f"x{t_mp / t_opt:.2f}")
    return {"optimum": t_opt, "move_pages": t_mp, "leap": out}


if __name__ == "__main__":
    run()
