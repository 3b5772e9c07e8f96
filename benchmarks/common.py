"""Shared benchmark utilities: timing, workload simulation, reporting.

Every benchmark prints ``name,us_per_call,derived`` CSV rows (one per
configuration) and returns them as dicts.  Wall times are measured on this
host (CPU backend — *relative* comparisons between methods mirror the
paper's figures); the ``derived`` column carries the figure-specific metric
(overhead %, achieved-throughput %, pages migrated %, modeled TPU time...).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
)

ROWS = []

# --trace support (benchmarks.run): when TRACING is on, every pool built via
# make_pool records telemetry, and its recorder lands in TRACE_SESSIONS as a
# (label, recorder) group for the per-suite Chrome trace file.  Timed numbers
# under --trace are for inspection, not for the regression gate.
TRACING = False
TRACE_SESSIONS: list[tuple[str, object]] = []


def emit(name: str, us_per_call: float, derived: str) -> dict:
    row = {"name": name, "us_per_call": us_per_call, "derived": derived}
    ROWS.append(row)
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    return row


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of fn(*args) with device sync."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def timeit_inplace(step, state, warmup: int = 1, iters: int = 3):
    """Time a donating state->state program by threading the state through
    (donated buffers cannot be reused).  Returns (median_s, final_state)."""
    for _ in range(warmup):
        state = step(state)
        jax.block_until_ready(jax.tree.leaves(state)[0])
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state = step(state)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), state


def make_pool(
    n_blocks: int,
    block_kb: int,
    n_regions: int = 2,
    initial_region: int = 0,
    leap: LeapConfig | None = None,
    seed: int = 0,
    huge_factor: int = 1,
    adopt: bool = False,
    topology=None,
):
    """A filled leap pool: every region can pool-hold everything (paper setup).

    With ``huge_factor`` G > 1 the pool is two-tier; ``adopt=True`` raises
    every aligned group to the huge tier in place (the dense initial placement
    already sits on aligned contiguous runs, so adoption is zero-copy).
    ``topology`` attaches a :class:`repro.topology.NumaTopology` (link-aware
    scheduling); None keeps the uniform scheduler.
    """
    elems = block_kb * 1024 // 4
    slack = huge_factor if huge_factor > 1 else 1
    cfg = PoolConfig(
        n_regions,
        n_blocks + slack,
        (1, elems),
        jnp.float32,
        huge_factor=huge_factor,
        topology=topology,
    )
    state = init_state(cfg, n_blocks, np.full(n_blocks, initial_region, np.int32))
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n_blocks, 1, elems), dtype=np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    jax.block_until_ready(state.pool)
    leap = leap or LeapConfig()
    if TRACING:
        leap = dataclasses.replace(leap, telemetry=True)
    drv = MigrationDriver(state, cfg, leap)
    if TRACING:
        TRACE_SESSIONS.append(
            (f"pool{len(TRACE_SESSIONS)}:{n_blocks}x{block_kb}KB", drv.telemetry)
        )
    if adopt and huge_factor > 1:
        drv.adopt_huge(np.arange(n_blocks // huge_factor))
    return cfg, drv, data


def flat_copy_ids(drv, ids, dst_region: int, dst_slots):
    """Operands of ``migrator.fused_copy`` that copy blocks ``ids`` into
    ``(dst_region, dst_slots)``: flat slot ids, ``region * S + slot``."""
    table = drv.host_table()
    s_per = drv.pool_cfg.slots_per_region
    ids = np.asarray(ids)
    src = table[ids, 0] * s_per + table[ids, 1]
    dst = dst_region * s_per + np.asarray(dst_slots)
    return jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)


class WriteBurst:
    """Uniform (or skewed) random single-block writes at a requested
    per-tick count, through the leap write path."""

    def __init__(self, driver, n_blocks: int, per_tick: int, skew: float = 0.0, seed=1):
        self.driver = driver
        self.n = n_blocks
        self.per_tick = per_tick
        self.skew = skew
        self.rng = np.random.default_rng(seed)
        self.done = 0
        shape = (per_tick,) + driver.pool_cfg.block_shape
        self._vals = jnp.asarray(
            self.rng.standard_normal(shape, dtype=np.float32)
        )
        self._hot = max(1, int(0.03125 * n_blocks))  # 3.125% of memory (paper)

    def fire(self):
        if self.per_tick == 0:
            return
        if self.skew > 0 and self.rng.random() < self.skew:
            ids = self.rng.choice(self._hot, size=self.per_tick, replace=False) \
                if self._hot >= self.per_tick else self.rng.integers(0, self._hot, self.per_tick)
        else:
            ids = self.rng.choice(self.n, size=self.per_tick, replace=False)
        self.driver.write(jnp.asarray(ids.astype(np.int32)), self._vals)
        self.done += self.per_tick


def measure_write_throughput(driver, n_blocks, per_tick, ticks, migrate: bool = False):
    """writes/s over ``ticks`` ticks, optionally with migration interleaved."""
    burst = WriteBurst(driver, n_blocks, per_tick)
    t0 = time.perf_counter()
    for _ in range(ticks):
        if migrate:
            driver.tick()
        burst.fire()
    jax.block_until_ready(driver.state.pool)
    dt = time.perf_counter() - t0
    return burst.done / dt, dt


def warmup_paths(n_blocks: int, block_kb: int, per_ticks=(1,)):
    """Compile-cache warmup: run every jitted shape (write bursts, copy,
    begin/commit, force) once on a throwaway pool so no timed section pays
    XLA compilation.  Benchmarks call this before their baselines."""
    from repro.core import LeapConfig
    import numpy as _np

    _, drv, _ = make_pool(n_blocks, block_kb,
                          leap=LeapConfig(initial_area_blocks=8, budget_blocks_per_tick=16))
    for pt in per_ticks:
        if pt:
            WriteBurst(drv, n_blocks, pt).fire()
    sess = drv.default_session()
    sess.leap(_np.arange(n_blocks // 2), 1)
    sess.drain()
    jax.block_until_ready(drv.state.pool)
