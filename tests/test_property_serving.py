"""Hypothesis property test: decode is invariant under ANY interleaving of
decode steps, migration ticks, and rebalance requests.

Kept separate from test_serving.py so the main suite collects when the
optional ``hypothesis`` dev dependency (requirements-dev.txt) is absent.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import LeapConfig

# Reuse the module-scoped model fixture and engine helper; importing a fixture
# into a module's namespace registers it for that module's tests.
from test_serving import _engine, setup  # noqa: F401


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    schedule=st.lists(st.sampled_from(["decode", "tick", "rebalance"]), min_size=4, max_size=14),
)
def test_property_decode_invariant_under_any_migration_schedule(setup, seed, schedule):
    """Property: for ANY interleaving of decode steps, migration ticks, and
    rebalance requests, the decoded tokens equal the no-migration run."""
    cfg, params = setup
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 10))) for _ in range(2)]

    def run(with_migration: bool):
        eng = _engine(cfg, params, leap=LeapConfig(
            initial_area_blocks=2, budget_blocks_per_tick=1,
            max_attempts_before_force=2,
        ))
        sids = [eng.admit(p, region=i % 2) for i, p in enumerate(prompts)]
        toks = [[eng.seqs[s].tokens[-1]] for s in sids]
        flip = 0
        for op in schedule:
            if op == "decode":
                outs = eng.decode(sids)
                for i, t in enumerate(outs):
                    toks[i].append(t)
            elif with_migration and op == "tick":
                eng.tick()
            elif with_migration and op == "rebalance":
                eng.rebalance(sids[flip % 2], dst_region=(flip + 1) % 2)
                flip += 1
        if with_migration:
            assert eng.drain()
        return toks

    assert run(True) == run(False)
