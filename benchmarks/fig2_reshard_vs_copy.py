"""Paper Fig. 2 — move_pages() vs raw memcpy (fresh vs pooled destination).

The raw copy is the optimum any migration can reach.  The move_pages()
analogue (SyncResharder) additionally pays the fresh-allocation zero pass
and the blocking table maintenance; leap's copy phase goes straight into
pooled slots.  ``derived`` = overhead % over the pooled-copy optimum.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, flat_copy_ids, make_pool, timeit
from repro.core import SyncResharder
from repro.core.migrator import fused_copy


def run(n_blocks=256, block_kb=256):
    total_mb = n_blocks * block_kb / 1024
    ids = jnp.arange(n_blocks)
    slots = jnp.arange(n_blocks)

    # raw copy into pooled (pre-allocated, pre-touched) memory
    cfg, drv, _ = make_pool(n_blocks, block_kb)
    from benchmarks.common import timeit_inplace

    src, dst = flat_copy_ids(drv, ids, 1, slots)
    st = fused_copy(drv.state, src, dst)  # pre-touch dst slots
    t_pooled, st = timeit_inplace(lambda s: fused_copy(s, src, dst), st)

    # raw copy into fresh memory (zero-fill pass first, like page faults)
    from repro.core.migrator import zero_fill

    def fresh(s):
        s = zero_fill(s, slots, 1)
        jax.block_until_ready(s.pool)
        return fused_copy(s, src, dst)

    t_fresh, st = timeit_inplace(fresh, st)

    emit(f"fig2/memcpy_pooled_{total_mb:.0f}MB", t_pooled * 1e6, "optimum")
    emit(
        f"fig2/memcpy_fresh_{total_mb:.0f}MB",
        t_fresh * 1e6,
        f"overhead={100 * (t_fresh / t_pooled - 1):.0f}%",
    )

    # move_pages() analogue: synchronous, fresh destination, blocking
    import time

    ts = []
    for _ in range(3):
        cfg2, drv2, _ = make_pool(n_blocks, block_kb)
        rs = SyncResharder(cfg2, fresh_alloc=True)
        t0 = time.perf_counter()
        rs.migrate_driver(drv2, np.arange(n_blocks), 1)
        ts.append(time.perf_counter() - t0)
    t_mp = float(np.median(ts))
    emit(
        f"fig2/move_pages_{total_mb:.0f}MB",
        t_mp * 1e6,
        f"overhead={100 * (t_mp / t_pooled - 1):.0f}%",
    )
    return {"pooled": t_pooled, "fresh": t_fresh, "move_pages": t_mp}


if __name__ == "__main__":
    run()
