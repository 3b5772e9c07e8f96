"""Paper Fig. 8 / §7 — TPC-H morsel workloads.

lineitem morsels start on region 0; the idle worker on region 1 migrates
them over (page_leap into pooled memory vs move_pages vs auto-balance vs no
migration), then runs Q1 and Q6 five times each — with and without a
concurrent writer hammering L_ORDERKEY.  ``derived`` = per-query time and
total (migration + 5 queries), mirroring the paper's stacked bars.
"""

import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core import LeapConfig, SyncResharder
from repro.data import tpch
from repro.data.morsels import MorselStore

N_ROWS = 131_072  # 8 MB of lineitem at 32B/row (CPU-scaled; 1 GB on target)
ROWS_PER_MORSEL = 2048
N_QUERIES = 5


def _mk(leap=None):
    data = tpch.gen_lineitem(N_ROWS, seed=0)
    store = MorselStore.create(
        data, ROWS_PER_MORSEL, n_regions=2, initial_region=0,
        leap=leap or LeapConfig(initial_area_blocks=32, budget_blocks_per_tick=32,
                                max_attempts_before_force=6),
    )
    return data, store


def _run_queries(store, which, writer_rng=None):
    ts = []
    param = 2400.0 if which == "q1" else 730.0
    for _ in range(N_QUERIES):
        t0 = time.perf_counter()
        r = tpch.run_query(store, which, param)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
        if writer_rng is not None:
            store.write_random_fields(writer_rng, 64, tpch.ORDERKEY, -1.0)
    return ts


def _warm():
    data, store = _mk()
    rng = np.random.default_rng(0)
    tpch.run_query(store, "q1", 2400.0)
    tpch.run_query(store, "q6", 730.0)
    store.write_random_fields(rng, 64, tpch.ORDERKEY, -1.0)
    store.write_random_fields(rng, 16, tpch.ORDERKEY, -1.0)
    store.steal(np.arange(store.n_morsels), 1)
    store.drain()


def run():
    _warm()
    for writes in (False, True):
        tag = "writes" if writes else "nowrites"
        for method in ("none", "leap", "move_pages", "auto"):
            data, store = _mk()
            rng = np.random.default_rng(7) if writes else None
            t_mig = 0.0
            if method == "leap":
                t0 = time.perf_counter()
                handle = store.leap(np.arange(store.n_morsels), 1)
                # asynchronous: migration ticks interleave with query work;
                # drain the remainder (paper reports full-completion time)
                while not handle.done:
                    store.tick()
                    if rng is not None:
                        store.write_random_fields(rng, 16, tpch.ORDERKEY, -1.0)
                assert handle.wait()
                p = handle.progress()
                assert p.committed + p.forced + p.cancelled == p.requested, p
                t_mig = time.perf_counter() - t0
            elif method == "move_pages":
                rs = SyncResharder(store.driver.pool_cfg, fresh_alloc=True)
                t0 = time.perf_counter()
                if rng is not None:
                    store.write_random_fields(rng, 16, tpch.ORDERKEY, -1.0)
                rs.migrate_driver(store.driver, np.arange(store.n_morsels), 1)
                t_mig = time.perf_counter() - t0
            elif method == "auto":
                # auto NUMA balancing never sees an explicit request; morsels
                # stay remote unless its heuristic fires (it defers under the
                # writer) -> queries keep paying remote cost. We model the
                # remote penalty by leaving placement as-is.
                pass
            q1 = _run_queries(store, "q1", rng)
            q6 = _run_queries(store, "q6", rng)
            migrated = 100 * (store.placement() == 1).mean()
            emit(
                f"fig8/{tag}/{method}",
                (t_mig + sum(q1) + sum(q6)) * 1e6,
                f"mig_ms={t_mig * 1e3:.1f};q1_ms={1e3 * np.mean(q1):.1f}"
                f";q6_ms={1e3 * np.mean(q6):.1f};migrated={migrated:.0f}%",
            )
            # correctness guard: results must match the reference
            got = float(tpch.run_query(store, "q6", 730.0))
            want = tpch.q6_reference(data, 730.0)
            assert abs(got - want) / max(abs(want), 1) < 1e-3
    return True


if __name__ == "__main__":
    run()
