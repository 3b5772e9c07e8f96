"""Hypothesis property tests for the handle-based API: arbitrary
leap/cancel/write interleavings terminate, account exactly, preserve data,
and never leak pool slots.

Kept separate (importorskip) so the tier-1 suite collects without the
optional ``hypothesis`` dev dependency.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.api import LeapSession
from repro.chaos import InvariantChecker
from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    leap_write,
)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n_blocks=st.integers(4, 20),
    n_regions=st.sampled_from([2, 3]),
    ops=st.integers(10, 40),
)
def test_property_leap_cancel_write_interleavings(seed, n_blocks, n_regions, ops):
    rng = np.random.default_rng(seed)
    cfg = PoolConfig(n_regions, n_blocks * 2, (4,))
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    data = rng.normal(size=(n_blocks, 4)).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    drv = MigrationDriver(
        state,
        cfg,
        LeapConfig(
            initial_area_blocks=4,
            budget_blocks_per_tick=4,
            max_attempts_before_force=3,
        ),
    )
    sess = LeapSession(drv)
    expected = data.copy()
    handles = []
    for _ in range(ops):
        op = rng.integers(0, 4)
        if op == 0:  # leap a random subset somewhere, at a random priority
            ids = rng.choice(n_blocks, size=int(rng.integers(1, n_blocks + 1)),
                             replace=False)
            handles.append(
                sess.leap(ids, int(rng.integers(0, n_regions)),
                          priority=int(rng.integers(0, 3)))
            )
        elif op == 1 and handles:  # cancel a random (possibly done) handle
            handles[int(rng.integers(0, len(handles)))].cancel()
        elif op == 2:  # concurrent writes
            k = int(rng.integers(1, 4))
            ids = rng.choice(n_blocks, size=k, replace=False)
            vals = rng.normal(size=(k, 4)).astype(np.float32)
            drv.write(jnp.asarray(ids.astype(np.int32)), jnp.asarray(vals))
            expected[ids] = vals
        sess.tick()
        sess.poll()
    assert sess.drain(), "interleaved leap/cancel/write must terminate"

    # every handle terminal, with exact per-handle accounting
    for h in handles:
        assert h.done
        p = h.progress()
        assert p.committed + p.forced + p.cancelled == p.requested
        assert p.remaining == 0
    # the shared standing invariants: global accounting closure, slot
    # conservation, mirror consistency, and no write lost (payload vs shadow)
    InvariantChecker(drv).check_final(expected=expected)
