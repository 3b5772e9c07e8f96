"""Batched migration dispatch: bucketing, fused programs, control-path cost.

Covers the acceptance criteria of the dispatch-batching redesign:
  * <= 3 device dispatches per tick on a drain workload,
  * jit cache stability: a full adaptive-splitting run compiles a bounded
    number of megastep variants,
  * batched commits preserve dirty-rejection semantics and the host mirror.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FreeList,
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    bucket_size,
    init_state,
    leap_write,
    migrator,
    pad_to_bucket,
)


def make(n_regions=2, slots=64, n_blocks=32, block_shape=(4,), seed=0):
    cfg = PoolConfig(n_regions, slots, block_shape)
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n_blocks,) + block_shape).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    return cfg, state, data


# ---------------------------------------------------------------------------
# Bucketing utilities
# ---------------------------------------------------------------------------


def test_bucket_size_geometric():
    assert [bucket_size(n) for n in (1, 2, 4, 5, 16, 17, 64)] == [1, 4, 4, 16, 16, 64, 64]
    assert bucket_size(3, growth=2) == 4
    with pytest.raises(ValueError):
        bucket_size(0)


def test_pad_to_bucket_replicates_lane0():
    a, b = pad_to_bucket(4, np.asarray([7, 9]), np.asarray([1, 2]))
    assert a.tolist() == [7, 9, 7, 7] and b.tolist() == [1, 2, 1, 1]
    with pytest.raises(ValueError):
        pad_to_bucket(1, np.asarray([1, 2]))
    with pytest.raises(ValueError):
        pad_to_bucket(4, np.asarray([], np.int32))


def test_freelist_take_put_roundtrip():
    f = FreeList(np.arange(8)[::-1])  # descending => lowest slot pops first
    assert len(f) == 8
    got = f.take(3)
    assert got.tolist() == [2, 1, 0] and len(f) == 5
    assert f.take(6) is None and len(f) == 5  # failed take leaves state intact
    f.put(got)
    assert len(f) == 8 and sorted(f) == list(range(8))
    # deque-compat shims used by the baselines
    s = f.popleft()
    f.append(s)
    f.extend([])
    assert sorted(f) == list(range(8))


# ---------------------------------------------------------------------------
# Batched program semantics
# ---------------------------------------------------------------------------


def test_commit_areas_padding_is_idempotent():
    """Pad lanes replicate lane 0: the duplicate remap must not corrupt the
    table, and real verdict lanes slice out exactly."""
    cfg, state, data = make()
    ids = jnp.asarray([0, 1, 2])
    slots = jnp.asarray([0, 1, 2])
    state = migrator.begin_areas(state, ids)
    state = migrator.fused_copy(
        state,
        jnp.asarray(np.asarray(state.table)[np.asarray([0, 1, 2]), 0] * cfg.slots_per_region
                    + np.asarray(state.table)[np.asarray([0, 1, 2]), 1]),
        jnp.asarray(1 * cfg.slots_per_region + np.asarray([0, 1, 2])),
    )
    # dirty block 1 after its copy
    state = leap_write(state, jnp.asarray([1]), jnp.full((1, 4), 5.0))
    p_ids, p_reg, p_slots = pad_to_bucket(
        16, np.asarray([0, 1, 2]), np.asarray([1, 1, 1]), np.asarray([0, 1, 2])
    )
    state, verdict = migrator.commit_areas(
        state, jnp.asarray(p_ids), jnp.asarray(p_reg), jnp.asarray(p_slots)
    )
    v = np.asarray(verdict)[:3]  # host ignores pad lanes
    assert v.tolist() == [False, True, False]
    table = np.asarray(state.table)
    assert table[0].tolist() == [1, 0]  # clean: remapped
    assert table[1, 0] == 0  # dirty: kept old mapping
    assert table[2].tolist() == [1, 2]
    assert not np.asarray(state.in_flight)[:3].any()


def test_force_areas_mixed_destinations():
    """One batched force program serves blocks headed to different regions."""
    cfg, state, data = make(n_regions=3)
    ids = np.asarray([0, 1, 2], np.int32)
    regions = np.asarray([1, 2, 1], np.int32)
    slots = np.asarray([0, 0, 1], np.int32)
    p = pad_to_bucket(4, ids, regions, slots)
    state = migrator.force_areas(state, *(jnp.asarray(x) for x in p))
    table = np.asarray(state.table)
    assert table[0].tolist() == [1, 0]
    assert table[1].tolist() == [2, 0]
    assert table[2].tolist() == [1, 1]
    got = np.asarray(state.pool)[table[:3, 0], table[:3, 1]]
    np.testing.assert_array_equal(got, data[:3])


# ---------------------------------------------------------------------------
# Driver: dispatch counts, cache stability
# ---------------------------------------------------------------------------


def test_dispatches_per_tick_at_most_three():
    """fig4-style drain: begin + copy + commit, nothing else."""
    cfg, state, _ = make(n_blocks=128, slots=256)
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(initial_area_blocks=64, budget_blocks_per_tick=64),
    )
    drv.request(np.arange(128), 1)
    assert drv.drain()
    assert drv.stats.ticks > 0
    assert drv.stats.dispatches_per_tick <= 3.0
    assert drv.verify_mirror()


def test_full_adaptive_run_compiles_at_most_bucket_count_variants():
    """Recompilation stability: however the splitter fragments the work, the
    megastep compiles a bounded number of variants.

    Every per-block operand pads to the budget-floored bucket (64 here), so
    a variant is a set of phases present, not a batch length.  Measured as
    the process-wide jit-cache delta of the megastep across two full
    adaptive-splitting drains (distinct write schedules => distinct raw
    batch lengths).  The block shape is this test's own, so the drains
    compile their variants here, whatever ran before in the process."""
    before = migrator.program_cache_sizes()["megastep"]
    for seed in (11, 12):
        cfg, state, data = make(n_blocks=64, slots=128, block_shape=(7,), seed=seed)
        drv = MigrationDriver(
            state,
            cfg,
            LeapConfig(
                initial_area_blocks=16,
                budget_blocks_per_tick=64,
                max_attempts_before_force=4,
            ),
        )
        drv.request(np.arange(64), 1)
        rng = np.random.default_rng(seed)
        steps = 0
        while not drv.done and steps < 2000:
            drv.tick()
            ids = rng.choice(64, size=4, replace=False)
            drv.write(jnp.asarray(ids), jnp.asarray(rng.normal(size=(4, 7)).astype(np.float32)))
            steps += 1
        assert drv.drain()
        assert drv.verify_mirror()
        assert drv.stats.dirty_rejections > 0, "workload must exercise splitting"
    growth = migrator.program_cache_sizes()["megastep"] - before
    # one variant per phase set a drain meets (begin+copy, commit+begin+copy,
    # the commit-only tail), however fragmented the batch lengths
    assert 0 < growth <= 3, growth
    # driver-level stat agrees: bounded compiles despite the length storm
    assert drv.stats.jit_cache_misses <= 16


def test_driver_reports_control_path_stats():
    cfg, state, _ = make(n_blocks=16, slots=32)
    drv = MigrationDriver(state, cfg, LeapConfig(initial_area_blocks=8))
    assert drv.stats.dispatches_per_tick == 0.0
    drv.request(np.arange(16), 1)
    assert drv.drain()
    assert drv.stats.dispatches_per_tick > 0
    assert drv.stats.jit_cache_misses >= 0
