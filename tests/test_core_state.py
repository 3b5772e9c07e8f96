"""Unit tests for the leap pool state: table indirection, reads, writes, dirty."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PoolConfig,
    init_state,
    leap_read,
    leap_write,
    leap_write_rows,
    placement_histogram,
)
from repro.core.state import REGION, SLOT


def make(n_regions=4, slots=8, n_blocks=16, block_shape=(4, 8), dtype=jnp.float32):
    cfg = PoolConfig(n_regions, slots, block_shape, dtype)
    placement = np.arange(n_blocks) % n_regions
    state = init_state(cfg, n_blocks, placement)
    return cfg, state


def test_init_placement_and_slots_unique():
    cfg, state = make()
    table = np.asarray(state.table)
    assert table.shape == (16, 2)
    # slots unique within each region
    for r in range(cfg.n_regions):
        slots = table[table[:, REGION] == r, SLOT]
        assert len(np.unique(slots)) == len(slots)
    hist = placement_histogram(state, cfg.n_regions)
    assert hist.tolist() == [4, 4, 4, 4]


def test_init_capacity_checks():
    cfg = PoolConfig(2, 2, (4,))
    with pytest.raises(ValueError):
        init_state(cfg, 8, np.zeros(8, np.int32))  # over capacity total
    with pytest.raises(ValueError):
        init_state(cfg, 3, np.zeros(3, np.int32))  # region 0 over capacity
    with pytest.raises(ValueError):
        init_state(cfg, 3, np.zeros(5, np.int32))  # wrong placement length


def test_read_write_roundtrip():
    cfg, state = make()
    ids = jnp.asarray([3, 7, 11])
    vals = jnp.arange(3 * 4 * 8, dtype=jnp.float32).reshape(3, 4, 8)
    state = leap_write(state, ids, vals)
    out = leap_read(state, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(vals))
    # untouched blocks remain zero
    other = leap_read(state, jnp.asarray([0]))
    assert float(jnp.abs(other).sum()) == 0.0


def test_write_rows_partial():
    cfg, state = make()
    ids = jnp.asarray([5, 5, 9])
    offs = jnp.asarray([0, 2, 3])
    rows = jnp.ones((3, 8), jnp.float32) * jnp.asarray([[1.0], [2.0], [3.0]])
    state = leap_write_rows(state, ids, offs, rows)
    b5 = np.asarray(leap_read(state, jnp.asarray([5])))[0]
    assert b5[0].sum() == 8.0 and b5[2].sum() == 16.0 and b5[1].sum() == 0.0
    b9 = np.asarray(leap_read(state, jnp.asarray([9])))[0]
    assert b9[3].sum() == 24.0


def test_write_sets_dirty_only_when_in_flight():
    cfg, state = make()
    ids = jnp.asarray([1, 2])
    vals = jnp.ones((2, 4, 8), jnp.float32)
    state = leap_write(state, ids, vals)
    assert not bool(np.asarray(state.dirty).any())
    # open an epoch on block 2 only
    from repro.core.migrator import begin_areas

    state = begin_areas(state, jnp.asarray([2]))
    state = leap_write(state, ids, vals)
    dirty = np.asarray(state.dirty)
    assert not dirty[1] and dirty[2]


def test_write_rows_sets_dirty_when_in_flight():
    cfg, state = make()
    from repro.core.migrator import begin_areas

    state = begin_areas(state, jnp.asarray([5]))
    state = leap_write_rows(
        state, jnp.asarray([5]), jnp.asarray([1]), jnp.ones((1, 8), jnp.float32)
    )
    assert bool(np.asarray(state.dirty)[5])


def test_bf16_pool():
    cfg, state = make(dtype=jnp.bfloat16)
    ids = jnp.asarray([0])
    vals = jnp.full((1, 4, 8), 1.5, jnp.bfloat16)
    state = leap_write(state, ids, vals)
    out = leap_read(state, ids)
    assert out.dtype == jnp.bfloat16
    assert float(out.astype(jnp.float32).mean()) == 1.5
