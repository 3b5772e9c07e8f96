"""Hypothesis property tests: arbitrary write/migration interleavings never
lose data, always terminate, and conserve slots.

Kept separate from test_core_migrator.py so the main suite collects when the
optional ``hypothesis`` dev dependency (requirements-dev.txt) is absent.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.chaos import InvariantChecker
from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n_blocks=st.integers(4, 24),
    initial_area=st.sampled_from([2, 4, 8]),
    writes_per_tick=st.integers(0, 6),
    n_regions=st.sampled_from([2, 3, 4]),
)
def test_property_interleaved_writes_preserve_contents(
    seed, n_blocks, initial_area, writes_per_tick, n_regions
):
    rng = np.random.default_rng(seed)
    cfg = PoolConfig(n_regions, n_blocks * 2, (4,))
    placement = rng.integers(0, n_regions, size=n_blocks).astype(np.int32)
    state = init_state(cfg, n_blocks, placement)
    data = rng.normal(size=(n_blocks, 4)).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(
            initial_area_blocks=initial_area,
            budget_blocks_per_tick=4,
            max_attempts_before_force=3,
        ),
    )
    expected = data.copy()
    target = int(rng.integers(0, n_regions))
    drv.request(np.arange(n_blocks), dst_region=target)
    steps = 0
    while not drv.done and steps < 1000:
        drv.tick()
        if writes_per_tick:
            ids = rng.integers(0, n_blocks, size=writes_per_tick)
            vals = rng.normal(size=(writes_per_tick, 4)).astype(np.float32)
            drv.write(jnp.asarray(ids), jnp.asarray(vals))
            # duplicate ids in one write batch: last-wins is NOT guaranteed by
            # scatter; emulate set-semantics by deduping (keep last occurrence)
            _, last = np.unique(ids[::-1], return_index=True)
            keep = len(ids) - 1 - last
            expected[ids[keep]] = vals[keep]
        steps += 1
    assert drv.done
    assert (drv.host_placement() == target).all()
    # the shared standing invariants: slot conservation, accounting closure,
    # mirror consistency, and payload integrity against the expected copy
    InvariantChecker(drv).check_final(expected=expected)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_random_requests_slot_conservation(seed):
    rng = np.random.default_rng(seed)
    n_blocks, n_regions = 12, 3
    cfg = PoolConfig(n_regions, 24, (2,))
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    drv = MigrationDriver(state, cfg, LeapConfig(initial_area_blocks=4))
    checker = InvariantChecker(drv)
    for _ in range(4):
        ids = rng.choice(n_blocks, size=rng.integers(1, n_blocks + 1), replace=False)
        drv.request(ids, dst_region=int(rng.integers(0, n_regions)))
        assert drv.drain()
        checker.check_final()
