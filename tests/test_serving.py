"""Serving engine tests: paged decode correctness vs the contiguous path,
and decode equivalence under live KV-block migration (the paper's
correctness property on the serving integration)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.kernels import paged_attn
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    pcfg = PagedConfig(block_tokens=4, max_blocks_per_seq=16,
                       n_regions=2, slots_per_region=64, **kw)
    return PagedEngine(cfg, params, pcfg)


def _contiguous_decode(cfg, params, prompt, n_steps):
    max_len = len(prompt) + n_steps
    logits, cache = jax.jit(lambda p, t: lm.prefill(p, t, cfg, max_len))(
        params, jnp.asarray(prompt)[None]
    )
    toks = [int(jnp.argmax(logits, -1)[0])]
    step = jax.jit(lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg))
    pos = len(prompt)
    for i in range(n_steps - 1):
        logits, cache = step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray(pos, jnp.int32),
        )
        toks.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return toks


def test_paged_matches_contiguous(setup):
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=9)  # crosses block boundary
    want = _contiguous_decode(cfg, params, prompt, 6)
    eng = _engine(cfg, params)
    sid = eng.admit(prompt)
    got = [eng.seqs[sid].tokens[-1]]  # first token comes from prefill logits
    for _ in range(5):
        got.extend(eng.decode([sid]))
    assert got == want, (got, want)


def test_paged_batched_multiple_sequences(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 12)]
    want = [_contiguous_decode(cfg, params, p, 4) for p in prompts]
    eng = _engine(cfg, params)
    sids = [eng.admit(p, region=i % 2) for i, p in enumerate(prompts)]
    got = [[eng.seqs[s].tokens[-1]] for s in sids]
    for _ in range(3):
        outs = eng.decode(sids)
        for i, t in enumerate(outs):
            got[i].append(t)
    assert got == want


def test_kv_chunks_read_counts_the_kernels_chunks(setup, monkeypatch):
    """After a decode step ``kv_chunks_read`` is, for every layer, the sum over
    the batch of ``ceil(ceil(len / BLK) / P)``: the chunks of ``P`` pages the
    paged kernel copies each sequence's held pages in."""
    cfg, params = setup
    width = cfg.n_kv_heads * cfg.head_dim
    # two 4-token pages a chunk, so that sequences take one to three chunks
    monkeypatch.setattr(paged_attn, "CHUNK_BYTES", 2 * 4 * width * 4)
    eng = _engine(cfg, params, leap=LeapConfig(telemetry=True))
    blk, maxb = eng.pcfg.block_tokens, eng.pcfg.max_blocks_per_seq
    pages = paged_attn.chunk_pages(blk, width, eng.driver.state.pool.dtype, maxb)
    assert pages == 2
    rng = np.random.default_rng(4)
    sids = [eng.admit(rng.integers(0, cfg.vocab_size, size=n)) for n in (3, 8, 9, 21)]
    eng.decode(sids)
    lens = np.asarray([eng.seqs[s].length for s in sids])  # tokens attended
    held = -(-lens // blk)
    assert held.tolist() == [1, 3, 3, 6]
    assert eng.stats.kv_pages_read == held.sum()
    assert eng.stats.kv_chunks_read == np.sum(-(-held // pages)) * cfg.n_layers == 16
    assert eng.driver.telemetry.counter_totals()["serve.kv_chunks_read"] == 16


def test_decode_correct_under_live_migration(setup):
    """Decode while the sequence's KV pages leap-migrate between regions:
    outputs must equal a no-migration run (reads through the table; appends
    dirty in-flight pages; retries preserve every append)."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, size=10)
    n_steps = 10
    want = _contiguous_decode(cfg, params, prompt, n_steps)

    eng = _engine(cfg, params, leap=LeapConfig(
        initial_area_blocks=2, budget_blocks_per_tick=1,
        max_attempts_before_force=3,
    ))
    sid = eng.admit(prompt)
    eng.rebalance(sid, dst_region=1)  # start live migration
    got = [eng.seqs[sid].tokens[-1]]
    for i in range(n_steps - 1):
        eng.tick()  # migration slice
        got.extend(eng.decode([sid]))  # concurrent decode (appends!)
    assert eng.drain()
    # all pages ended up on region 1
    seq = eng.seqs[sid]
    assert all(
        int(r) == 1 for r in eng.facade.region_of(np.asarray(seq.block_ids))
    )
    assert got == want, (got, want)
    assert eng.driver.stats.blocks_migrated + eng.driver.stats.blocks_forced >= 3


def test_release_returns_blocks(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    free_before = sum(len(f) for f in eng._free_blocks)
    sid = eng.admit(np.arange(8) % cfg.vocab_size)
    assert sum(len(f) for f in eng._free_blocks) < free_before
    eng.release(sid)
    assert sum(len(f) for f in eng._free_blocks) == free_before


def test_paged_engine_moe_arch():
    """The paged engine also serves MoE stacks (dbrx family): decode through
    paged attention + expert FFN must match the contiguous path."""
    cfg = dataclasses.replace(reduce(get_config("dbrx_132b")), n_layers=2)
    params = lm.init_params(jax.random.key(1), cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=7)
    want = _contiguous_decode(cfg, params, prompt, 4)
    eng = _engine(cfg, params)
    sid = eng.admit(prompt)
    got = [eng.seqs[sid].tokens[-1]]
    for _ in range(3):
        got.extend(eng.decode([sid]))
    assert got == want, (got, want)


def test_rebalance_returns_handle_and_engine_is_a_policy(setup):
    """rebalance() hands back a LeapHandle future, and the engine's own
    ``decide()`` (sequence affinity) drives the session — policy separated
    from mechanism."""
    from repro.api import HandleStatus

    cfg, params = setup
    eng = _engine(cfg, params)
    sid = eng.admit(np.arange(8) % cfg.vocab_size)
    n_pages = len(eng.seqs[sid].block_ids)
    h = eng.rebalance(sid, dst_region=1)
    assert h.tag == sid and h.requested == n_pages
    assert h.wait()
    assert h.status == HandleStatus.COMMITTED
    p = h.progress()
    assert p.committed + p.forced == p.requested == n_pages
    regions = eng.facade.region_of(np.asarray(eng.seqs[sid].block_ids))
    assert (np.asarray(regions) == 1).all()
    # once every page is home, the affinity policy proposes nothing
    assert eng.decide(eng.facade) == []
    # cancellation on the serving path leaks nothing
    h2 = eng.rebalance(sid, dst_region=0)
    h2.cancel()
    assert h2.done and eng.drain()
    assert eng.driver.verify_mirror()


def test_rebalance_latency_attribution(setup):
    """With telemetry on, the engine attributes per-sequence rebalance
    latency from the KV pool's recorder; off, both accessors degrade to
    None/disabled rather than erroring."""
    cfg, params = setup
    eng = _engine(cfg, params, leap=LeapConfig(telemetry=True))
    sid = eng.admit(np.arange(8) % cfg.vocab_size)
    assert eng.rebalance_latency(sid) is None  # never rebalanced yet
    h = eng.rebalance(sid, dst_region=1)
    assert h.wait()
    lat = eng.rebalance_latency(sid)
    assert lat is not None and lat.rid == h.request_id
    assert lat.outcome == "COMMITTED"
    assert lat.requested == len(eng.seqs[sid].block_ids)
    assert lat.ticks_total >= 0 and lat.wall_s >= 0
    view = eng.telemetry()
    assert view.enabled
    assert view.counters()["blocks_migrated"] == eng.driver.stats.blocks_migrated

    eng_off = _engine(cfg, params)  # telemetry defaults off
    sid2 = eng_off.admit(np.arange(8) % cfg.vocab_size)
    h2 = eng_off.rebalance(sid2, dst_region=1)
    assert h2.wait()
    assert not eng_off.telemetry().enabled
    assert eng_off.rebalance_latency(sid2) is None


# Hypothesis property test over arbitrary decode/tick/rebalance schedules:
# see test_property_serving.py (guarded by pytest.importorskip("hypothesis")).
