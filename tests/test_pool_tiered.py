"""Two-tier pool integration: huge blocks migrate as single areas through the
fused dispatch path (one contiguous-run copy, not G gathers), demote under
sustained write pressure and still fully migrate, and promote/demote cleanly
from the serving engine (acceptance criteria of the two-tier redesign)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    group_dirty,
    huge_read,
    init_state,
    leap_write,
    migrator,
)
from repro.kernels import ops

G = 4


def make_tiered(n_blocks=16, n_regions=2, slots=32, block_shape=(1, 8), seed=0,
                adopt=True, **leap_kw):
    cfg = PoolConfig(n_regions, slots, block_shape, huge_factor=G)
    state = init_state(cfg, n_blocks, np.zeros(n_blocks, np.int32))
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n_blocks,) + block_shape).astype(np.float32)
    state = leap_write(state, jnp.arange(n_blocks), jnp.asarray(data))
    drv = MigrationDriver(state, cfg, LeapConfig(
        initial_area_blocks=8, budget_blocks_per_tick=16, **leap_kw))
    if adopt:
        assert drv.adopt_huge(np.arange(n_blocks // G)) == n_blocks // G
    return cfg, drv, data


# ---------------------------------------------------------------------------
# Kernels / programs
# ---------------------------------------------------------------------------


def test_copy_runs_matches_oracle():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(16, 8, 128)).astype(np.float32))
    src = jnp.asarray([0, 8], jnp.int32)
    dst = jnp.asarray([4, 12], jnp.int32)
    got = ops.copy_runs_impl(pool, src, dst, run=4, impl="pallas_interpret")
    want = ops.copy_runs_impl(pool, src, dst, run=4, impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got)[4:8], np.asarray(pool)[0:4])


def test_commit_groups_all_or_nothing():
    """One dirty member rejects the WHOLE huge block (huge-page semantics)."""
    cfg, drv, data = make_tiered()
    state = drv.state
    members = jnp.arange(G)  # group 0
    state = migrator.begin_areas(state, members)
    state = leap_write(state, jnp.asarray([2]), jnp.zeros((1, 1, 8)))  # dirty one
    assert bool(group_dirty(state, jnp.asarray([0]), G)[0])
    # the megastep's group-commit phase alone: group 0 to region 1, slot 0
    packed, layout = migrator.pack_operands(
        {"grp_members": np.arange(G), "grp_regions": [1], "grp_starts": [0]}
    )
    state, _, verdict, _ = migrator.megastep(state, packed, layout=layout, group=G)
    assert verdict.tolist() == [True]
    table = np.asarray(state.table)
    assert (table[:G, 0] == 0).all()  # nothing flipped, not even clean members


def test_huge_read_returns_contiguous_payload():
    cfg, drv, data = make_tiered()
    got = np.asarray(huge_read(drv.state, jnp.asarray([0, 2]), G))
    np.testing.assert_array_equal(got[0], data[0:G])
    np.testing.assert_array_equal(got[1], data[2 * G : 3 * G])


# ---------------------------------------------------------------------------
# Driver: huge migration as one area through the fused path
# ---------------------------------------------------------------------------


def test_huge_block_migrates_as_single_run_copy():
    """Acceptance: a huge block goes through the fused dispatch path as ONE
    contiguous-run copy — under megastep dispatch, 2 programs total (one
    megastep carrying begin + the run copy, one carrying the grouped
    commit), all bytes through the run program, and one all-or-nothing
    commit."""
    cfg, drv, data = make_tiered()
    assert drv.request([0], 1) == G  # touching one member migrates the block
    assert drv.drain()
    s = drv.stats
    assert s.dispatches == 2, "one begin+run-copy megastep + one commit megastep"
    assert s.huge_areas_committed == 1
    assert s.bytes_copied == s.bytes_copied_huge == G * cfg.block_bytes
    assert s.blocks_migrated == G
    table = drv.host_table()
    assert (table[:G, 0] == 1).all()
    start = table[0, 1]
    assert start % G == 0  # buddy alignment survives migration
    assert (table[np.arange(G), 1] == start + np.arange(G)).all()
    assert drv.verify_mirror() and drv.verify_tiers()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(G))), data[:G])


def test_huge_drain_full_pool():
    cfg, drv, data = make_tiered()
    drv.request(np.arange(16), 1)
    assert drv.drain()
    assert drv.stats.huge_areas_committed == 4
    assert (drv.host_placement() == 1).all()
    assert drv.verify_tiers()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), data)


# ---------------------------------------------------------------------------
# Demotion under writes (paper §4.2)
# ---------------------------------------------------------------------------


def test_sustained_writes_demote_then_fully_migrate():
    """Acceptance: a huge-area commit rejected under sustained writes demotes
    to small blocks that all eventually migrate (splitting/forcing as
    needed), with no write lost."""
    cfg, drv, data = make_tiered(
        demote_after_attempts=2, max_attempts_before_force=6
    )
    drv.request(np.arange(16), 1)
    rng = np.random.default_rng(1)
    expected = data.copy()
    steps = 0
    while not drv.done and steps < 500:
        drv.tick()
        ids = np.asarray([1, 6])  # hammer members of groups 0 and 1
        vals = rng.standard_normal((2, 1, 8)).astype(np.float32)
        drv.write(jnp.asarray(ids), jnp.asarray(vals))
        expected[ids] = vals
        steps += 1
    assert drv.drain()
    assert drv.stats.demotions >= 1
    assert not drv.tiers.tier[0] or not drv.tiers.tier[1]  # a hot group split
    assert (drv.host_placement() == 1).all(), "demoted blocks must still migrate"
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(16))), expected)
    assert drv.verify_mirror() and drv.verify_tiers()


def test_fragmented_destination_demotes():
    """No contiguous run at the destination (>= G free but fragmented) splits
    the huge block instead of stalling."""
    cfg, drv, data = make_tiered(n_blocks=8, slots=16)
    # fragment region 1: pin every other slot via direct buddy reservation
    drv.debug_free_list(1).reserve(np.arange(0, 16, 2))
    assert drv.debug_free_list(1).take_run() is None and drv.free_slots(1) == 8
    drv.request(np.arange(G), 1)
    assert drv.drain()
    assert drv.stats.demotions == 1
    assert not drv.tiers.tier[0]
    assert (drv.host_placement()[:G] == 1).all()
    assert drv.verify_mirror()


# ---------------------------------------------------------------------------
# Promotion (coalescing) and adoption
# ---------------------------------------------------------------------------


def test_promote_requires_aligned_fully_resident_run():
    cfg, drv, data = make_tiered(adopt=False)
    # scatter group 1's members across regions
    drv.request([4, 5], 1)
    assert drv.drain()
    assert not drv.promote_group(1)  # split residency: refused
    assert drv.promote_group(0)  # fully resident on region 0: promoted
    assert drv.tiers.tier[0] and not drv.tiers.tier[1]
    assert drv.verify_mirror() and drv.verify_tiers()
    np.testing.assert_array_equal(np.asarray(drv.read(np.arange(G))), data[:G])
    # bring group 1 home and coalesce it too
    drv.request([4, 5], 0)
    assert drv.drain()
    assert drv.promote_group(1)
    assert drv.verify_tiers()
    np.testing.assert_array_equal(
        np.asarray(drv.read(np.arange(2 * G))), data[: 2 * G]
    )


def test_promotion_refused_while_migrating_or_hot():
    cfg, drv, data = make_tiered(adopt=False, promote_cold_ticks=4)
    drv.request(np.arange(G), 1)
    assert not drv.promote_group(0)  # under migration
    assert drv.drain()
    drv.write(jnp.asarray([0]), jnp.zeros((1, 1, 8)))
    assert not drv.promote_group(0)  # too hot (written this tick)
    for _ in range(5):
        drv.tick()
    assert drv.promote_group(0)  # cold now
    assert drv.verify_tiers()


def test_auto_promote_per_tick():
    cfg, drv, _ = make_tiered(adopt=False, promote_per_tick=2)
    assert drv.promote_candidates() == [0, 1, 2, 3]
    drv.tick()
    drv.tick()
    assert drv.stats.promotions == 4
    assert drv.tiers.tier.all()
    assert drv.verify_tiers()


def test_adopt_huge_requires_contiguity():
    cfg, drv, _ = make_tiered(adopt=False)
    # swap two members' slots: send both away, bring them home in reverse
    # order so the lowest-address-fit crosses them over
    drv.request([0, 1], 1)
    assert drv.drain()
    drv.request([1], 0)
    assert drv.drain()
    drv.request([0], 0)
    assert drv.drain()
    assert drv.host_table()[0, 1] != 0  # block 0 no longer on slot 0
    adopted = drv.adopt_huge(np.arange(4))
    assert adopted == 3  # group 0 is no longer an ascending contiguous run
    assert not drv.tiers.tier[0] and drv.tiers.tier[1:].all()
    assert drv.verify_tiers()


# ---------------------------------------------------------------------------
# Serving engine end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    from repro.configs.base import get_config
    from repro.configs.smoke import reduce
    from repro.models import lm

    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(model, **kw):
    from repro.serving.engine import PagedConfig, PagedEngine

    cfg, params = model
    pcfg = PagedConfig(
        block_tokens=4, max_blocks_per_seq=16, n_regions=2, slots_per_region=64, **kw
    )
    return PagedEngine(cfg, params, pcfg)


def test_engine_promotes_growing_sequences_and_matches_small_pool(model):
    eng = _engine(model, huge_factor=2)
    ref = _engine(model, huge_factor=1)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, model[0].vocab_size, size=9)
    sid, rid = eng.admit(prompt), ref.admit(prompt)
    for _ in range(12):
        eng.decode([sid])
        ref.decode([rid])
    assert eng.driver.stats.promotions >= 1, "long KV must coalesce to huge"
    assert eng.seqs[sid].promoted
    assert eng.seqs[sid].tokens == ref.seqs[rid].tokens
    assert eng.driver.verify_mirror() and eng.driver.verify_tiers()


def test_engine_huge_rebalance_while_decoding(model):
    eng = _engine(model, huge_factor=2)
    ref = _engine(model, huge_factor=1)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, model[0].vocab_size, size=9)
    sid, rid = eng.admit(prompt), ref.admit(prompt)
    for _ in range(10):
        eng.decode([sid])
    assert eng.driver.stats.promotions >= 1
    moved = np.asarray(eng.seqs[sid].block_ids)  # what rebalance requests
    eng.rebalance(sid, 1)
    steps = 0
    while not eng.driver.done and steps < 200:
        eng.tick()
        eng.decode([sid])
        steps += 1
    assert eng.drain()
    assert eng.driver.stats.huge_areas_committed >= 1
    table = eng.driver.host_table()
    # every page that existed at rebalance time landed on region 1 (frontier
    # pages allocated afterwards may still draw from region-0 spare groups)
    assert (table[moved, 0] == 1).all()
    assert eng.driver.verify_mirror() and eng.driver.verify_tiers()
    for _ in range(10 + steps):
        ref.decode([rid])
    assert eng.seqs[sid].tokens == ref.seqs[rid].tokens


def test_engine_demotion_under_live_appends(model):
    """Acceptance: demotion exercised end-to-end from serving — eager
    promotion puts the append frontier inside a huge block, live decode keeps
    dirtying it during rebalance, the commit rejects and the block demotes;
    decode output stays exact throughout."""
    leap = dataclasses.replace(
        LeapConfig(), demote_after_attempts=2, budget_blocks_per_tick=4
    )
    eng = _engine(model, huge_factor=2, promote_eager=True, leap=leap)
    ref = _engine(model, huge_factor=1)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, model[0].vocab_size, size=9)
    sid, rid = eng.admit(prompt), ref.admit(prompt)
    for _ in range(4):
        eng.decode([sid])
    assert eng.driver.stats.promotions >= 1
    eng.rebalance(sid, 1)
    steps = 0
    while not eng.driver.done and steps < 300:
        eng.tick()
        eng.decode([sid])  # live appends dirty the frontier huge block
        steps += 1
    assert eng.driver.done
    assert eng.driver.stats.demotions >= 1, "frontier huge block must demote"
    table = eng.driver.host_table()
    assert (table[np.asarray(eng.seqs[sid].block_ids), 0] == 1).all(), (
        "demoted blocks must all eventually migrate"
    )
    assert eng.driver.verify_mirror() and eng.driver.verify_tiers()
    for _ in range(4 + steps):
        ref.decode([rid])
    assert eng.seqs[sid].tokens == ref.seqs[rid].tokens
