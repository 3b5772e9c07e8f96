"""Serving-load subsystem tests: deterministic arrival streams, admission
backpressure under pool pressure, SLO-slack pacing and priority overtake
through the migration pipeline, per-tenant telemetry, autoscaler drain/fill
gating, and the chaos serving workload."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.chaos.driver import run_scenario
from repro.chaos.spec import FaultEvent, ScenarioSpec
from repro.configs.base import get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig, MigrationDriver, PoolConfig, init_state
from repro.core.pipeline import SloConfig, SloScheduler
from repro.load import (
    ArrivalStream,
    LoadGenerator,
    RegionAutoscaler,
    TenantSpec,
    WorkloadSpec,
    pow2_chunks,
)
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("block_tokens", 4)
    kw.setdefault("max_blocks_per_seq", 16)
    kw.setdefault("n_regions", 2)
    kw.setdefault("slots_per_region", 64)
    return PagedEngine(cfg, params, PagedConfig(**kw))


def _spec(**kw):
    kw.setdefault(
        "tenants",
        (
            TenantSpec("gold", rate=0.5, prompt_tokens=6, decode_tokens=8,
                       slo_latency=2.5, priority=2, region=0),
            TenantSpec("batch", rate=0.3, prompt_tokens=8, decode_tokens=12,
                       slo_latency=10.0, priority=0, region=1),
        ),
    )
    kw.setdefault("ticks", 12)
    kw.setdefault("seed", 7)
    return WorkloadSpec(**kw)


# -- workload determinism ---------------------------------------------------


def test_arrival_stream_deterministic():
    spec = _spec(ticks=64)
    a, b = ArrivalStream(spec), ArrivalStream(spec)
    assert np.array_equal(a.counts, b.counts)
    assert a.total() > 0
    # per-tick expansion replays identically too
    for t in (0, 13, 63):
        assert [i for i, _ in a.arrivals(t)] == [i for i, _ in b.arrivals(t)]
    # a different seed yields a different schedule
    c = ArrivalStream(dataclasses.replace(spec, seed=8))
    assert not np.array_equal(a.counts, c.counts)


def test_arrival_stream_tenant_isolated():
    """Adding a tenant must not perturb existing tenants' schedules."""
    spec = _spec(ticks=64)
    extra = spec.tenants + (
        TenantSpec("new", rate=1.0, prompt_tokens=4, decode_tokens=4,
                   slo_latency=5.0),
    )
    a = ArrivalStream(spec)
    b = ArrivalStream(dataclasses.replace(spec, tenants=extra))
    assert np.array_equal(a.counts, b.counts[: len(spec.tenants)])


def test_workload_spec_roundtrip_and_validation():
    spec = _spec(churn_every=3, churn_count=2)
    assert WorkloadSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError):
        WorkloadSpec(tenants=()).validate()
    with pytest.raises(ValueError):
        _spec(tenants=(
            TenantSpec("x", rate=-1, prompt_tokens=4, decode_tokens=4,
                       slo_latency=1.0),
        )).validate()
    with pytest.raises(ValueError):
        _spec(tenants=_spec().tenants + _spec().tenants).validate()  # dup names


def test_pow2_chunks():
    assert pow2_chunks(0) == []
    assert pow2_chunks(1) == [1]
    assert pow2_chunks(7) == [4, 2, 1]
    assert pow2_chunks(12) == [8, 4]
    for n in range(1, 40):
        chunks = pow2_chunks(n)
        assert sum(chunks) == n
        assert all(c & (c - 1) == 0 for c in chunks)


# -- generator over a live engine -------------------------------------------


def test_generator_deterministic_run(setup):
    cfg, params = setup
    spec = _spec(ticks=10, churn_every=2)
    reports = []
    for _ in range(2):
        eng = _engine(cfg, params, scheduler="slo",
                      leap=LeapConfig(budget_blocks_per_tick=4))
        gen = LoadGenerator(eng, spec, scheduler=eng.driver.scheduler)
        reports.append(gen.run())
        gen.verify_accounting()
    assert reports[0] == reports[1]  # modeled clock => bit-identical reports


def test_admission_backpressure_out_of_slots(setup):
    """Flooding a tiny pool queues and drops — never 'KV pool exhausted'."""
    cfg, params = setup
    # 8 pages per region, 16 total; each request's lifetime footprint is
    # ~4 pages, so only a couple of sequences fit concurrently.
    eng = _engine(cfg, params, slots_per_region=16)
    spec = _spec(
        tenants=(
            TenantSpec("flood", rate=3.0, prompt_tokens=6, decode_tokens=8,
                       slo_latency=5.0),
        ),
        ticks=12,
        max_queue=4,
    )
    gen = LoadGenerator(eng, spec)
    rep = gen.run()  # raises RuntimeError if backpressure ever fails
    gen.verify_accounting()
    assert rep["dropped"] > 0  # open-loop overflow went to drops...
    assert max(e["queued"] for e in gen.tick_log) > 0  # ...through the queue
    assert rep["completed"] > 0  # and the admitted work still finished
    acc = eng.page_accounting()
    assert acc["used"] + acc["spare"] + acc["free"] == acc["total"]


def test_generator_feeds_slo_scheduler(setup):
    cfg, params = setup
    eng = _engine(cfg, params, scheduler="slo",
                  leap=LeapConfig(budget_blocks_per_tick=8))
    sched = eng.driver.scheduler
    assert isinstance(sched, SloScheduler)
    gen = LoadGenerator(eng, _spec(ticks=8, churn_every=2),
                        scheduler=sched)
    gen.run()
    # registration + observation closed the loop: the scheduler holds
    # latency windows for both tenants and computes a real slack
    assert set(sched._slo) == {"gold", "batch"}
    assert sched.min_slack() < 1.0


# -- SLO scheduler policy ---------------------------------------------------


def test_slo_pacing_factor_curve():
    sched = SloScheduler(SloConfig(window=8, low_slack=0.1, high_slack=0.5))
    sched.register_tenant("t", slo_latency=2.0)
    assert sched.pacing_factor() == 1.0  # no data: assume healthy
    sched.observe_tokens("t", [0.5] * 8)  # slack 0.75 > high
    assert sched.pacing_factor() == 1.0
    sched.observe_tokens("t", [1.8] * 8)  # slack 0.1 <= low
    assert sched.pacing_factor() == 0.0
    sched.observe_tokens("t", [1.4] * 8)  # slack 0.3: mid-ramp
    assert 0.0 < sched.pacing_factor() < 1.0
    cfg = LeapConfig(budget_blocks_per_tick=8)
    assert sched.tick_budget(cfg) >= sched.cfg.min_blocks
    assert sched.link_unit(cfg, 8) >= sched.cfg.min_blocks


def test_slo_migration_priority_orders_by_slack():
    sched = SloScheduler(SloConfig(window=8))
    sched.register_tenant("tight", slo_latency=1.0)
    sched.register_tenant("loose", slo_latency=10.0)
    sched.observe_tokens("tight", [0.95] * 8)
    sched.observe_tokens("loose", [0.95] * 8)
    assert sched.migration_priority("tight") > sched.migration_priority("loose")


def test_slo_priority_overtakes_background_drain():
    """A request prioritized by SLO slack overtakes an in-flight drain."""
    pool_cfg = PoolConfig(2, 64, (4,))
    state = init_state(pool_cfg, 32, np.zeros(32, np.int32))
    driver = MigrationDriver(
        state, pool_cfg,
        LeapConfig(initial_area_blocks=2, budget_blocks_per_tick=2),
    )
    session = driver.default_session()
    sched = SloScheduler(SloConfig(window=8))
    sched.register_tenant("gold", slo_latency=1.0)
    sched.observe_tokens("gold", [0.99] * 8)  # nearly no slack
    background = session.leap(np.arange(16), 1, priority=0)
    session.tick()  # the drain is mid-pipeline now
    assert not background.done
    urgent = session.leap(
        np.arange(20, 24), 1, priority=sched.migration_priority("gold")
    )
    for _ in range(4):
        session.tick()
        if urgent.done:
            break
    assert urgent.done and not background.done, (
        urgent.progress(), background.progress()
    )
    assert session.drain()


# -- per-tenant telemetry ---------------------------------------------------


def test_tenant_metrics_exposition(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    sid = eng.admit(rng.integers(0, cfg.vocab_size, size=6), region=0,
                    tenant="gold")
    eng.admit(rng.integers(0, cfg.vocab_size, size=6), region=1,
              tenant="batch")
    eng.observe_tokens("gold", [1.0, 2.0, 3.0])
    eng.observe_tokens("batch", 5.0)
    handle = eng.rebalance(sid, 1)
    while not handle.done:
        eng.tick()
    text = eng.telemetry().metrics_text()
    assert 'leap_tenant_tokens_total{tenant="gold"} 3' in text
    assert 'leap_tenant_tokens_total{tenant="batch"} 1' in text
    assert 'leap_tenant_token_latency_bucket{tenant="gold",le="2"} 2' in text
    assert 'leap_tenant_token_latency_count{tenant="gold"} 3' in text
    # migration bytes attributed to the rebalanced sequence's tenant only
    p = handle.progress()
    moved = (p.committed + p.forced) * eng.pool_cfg.block_bytes
    assert moved > 0
    assert (
        f'leap_tenant_migration_bytes_total{{tenant="gold"}} {moved}' in text
    )
    assert 'leap_tenant_migration_bytes_total{tenant="batch"}' not in text
    stats = eng.tenant_stats()
    assert stats["gold"]["migration_bytes"] == moved
    assert stats["batch"]["tokens"] == 1
    # JSON rendering carries the same labeled series
    js = eng.telemetry().metrics_json()
    assert js["counters"]['leap_tenant_tokens_total{tenant="gold"}'] == 3
    assert 'leap_tenant_token_latency{tenant="gold"}' in js["histograms"]


# -- autoscaler -------------------------------------------------------------


def test_autoscaler_drains_when_slack_allows(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    for _ in range(4):
        eng.admit(rng.integers(0, cfg.vocab_size, size=6), region=0)
    sched = SloScheduler(SloConfig(window=4))
    sched.register_tenant("t", slo_latency=2.0)
    sched.observe_tokens("t", [0.5] * 4)  # plenty of slack
    scaler = RegionAutoscaler(eng, sched, max_moves_per_tick=1)
    moved = scaler.step()
    assert len(moved) == 1 and moved[0][1] == 1
    assert eng.seqs[moved[0][0]].region == 1


def test_autoscaler_yields_under_slo_pressure(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(2)
    for _ in range(4):
        eng.admit(rng.integers(0, cfg.vocab_size, size=6), region=0)
    sched = SloScheduler(SloConfig(window=4))
    sched.register_tenant("t", slo_latency=2.0)
    sched.observe_tokens("t", [1.9] * 4)  # slack nearly gone
    scaler = RegionAutoscaler(eng, sched, max_moves_per_tick=2)
    assert scaler.step() == []
    assert scaler.yields == 1
    # without a scheduler attached the same imbalance does drain
    assert len(RegionAutoscaler(eng, None, max_moves_per_tick=2).step()) == 2


# -- chaos serving workload -------------------------------------------------


def test_chaos_serving_scenario_runs_invariants():
    spec = ScenarioSpec(
        seed=5, ticks=10, n_regions=2, slots_per_region=32,
        workload="serving", scheduler="slo",
        serving_rate=0.5, serving_churn_every=2,
        faults=(FaultEvent("cancel_storm", tick=5, args={"frac": 0.5}),),
    )
    assert ScenarioSpec.from_json(spec.to_json()) == spec
    rep = run_scenario(spec)
    assert rep.completed
    assert rep.checks_run > spec.ticks  # per-tick + per-event checks ran
    assert rep.blocks_requested > 0  # churn really exercised migration


def test_chaos_serving_rejects_raw_pool_faults():
    with pytest.raises(ValueError, match="serving"):
        ScenarioSpec(
            workload="serving",
            faults=(FaultEvent("out_of_slots", tick=1),),
        ).validate()
