"""Fig. 11 (beyond paper) — closed-loop hot/cold tiering over CXL.

Working-set-shift workloads on a ``cxl_pooled(2, 1)`` machine (two compute
sockets + one far memory expander, DESIGN.md §13): zipfian reads whose hot
set ROTATES mid-run onto blocks that start on the far tier.  Three placement
strategies over identical access traces:

  * ``static``   — first-touch placement, never migrates (the initial hot
                   set sits near; after the rotation every hot read crosses
                   the 0.25x-bandwidth expander link).
  * ``sampling`` — the autonuma-style :class:`AutoBalancer`: remote-access
                   counters move blocks toward their reader.  Adapts, but
                   with alternating reader sockets and no hysteresis it
                   bounces hot blocks 0↔1 (``ping_pong_migrations``).
  * ``tiering``  — the closed-loop :class:`repro.tiering.TieringPolicy`:
                   device-maintained heat (the megastep's fused phase),
                   watermark promotion/demotion, cooldown hysteresis.

Completion time is modeled machine time (``modeled_tick_time``): each tick
charges the *access* bytes (every remote read billed on its reader→home
link) merged with the tick's migration byte deltas, and the slowest link
paces the tick — so far-tier reads and migration churn both cost, on the
same hardware model for every strategy.  ``derived`` carries the modeled
time, the hot-tier hit rate (reads served from a near region) and its
complement ``miss`` (gated), and the ping-pong count (gated).

A second scenario replays the loop at serving granularity: a
:class:`PagedEngine` KV cache over the same topology, where the *active
sequence set* shifts mid-run to sequences whose pages overflowed to the far
tier at admission.  Decode feeds page reads into the heat plane
(``driver.note_reads``) and the same policy promotes the newly hot KV pages.
"""

import numpy as np

from benchmarks.common import emit, make_pool
from repro.core import LeapConfig
from repro.core.baselines import AutoBalancer
from repro.core.pipeline import SamplingConfig
from repro.tiering import TieringConfig, TieringPolicy, split_tiers
from repro.topology import NumaTopology, modeled_tick_time

N_BLOCKS = 96
BLOCK_KB = 8
TICKS = 240  # rotation at TICKS // 2
READS_PER_TICK = 16
ZIPF_A = 1.1


class ShiftTrace:
    """Deterministic zipfian read trace with a mid-run hot-set rotation.

    Block popularity follows rank^-a over a permutation of the ids; at the
    rotation tick the permutation rolls by half the pool, landing the hot
    mass on blocks the initial placement left on the far tier.  The reader
    socket alternates 0/1 per tick (both compute sockets touch the data).
    """

    def __init__(self, n_blocks=N_BLOCKS, seed=0):
        rng = np.random.default_rng(seed)
        ranks = np.arange(n_blocks)
        p = 1.0 / (ranks + 1.0) ** ZIPF_A
        self.p = p / p.sum()
        # phase 1 hot order: 0, 1, 2, ... (hot head starts near, by placement)
        self.order1 = ranks.copy()
        self.order2 = np.roll(ranks, n_blocks // 2)
        self.batches = [
            rng.choice(n_blocks, size=READS_PER_TICK, p=self.p) for _ in range(TICKS)
        ]

    def reads(self, tick):
        order = self.order1 if tick < TICKS // 2 else self.order2
        return order[self.batches[tick]], tick % 2  # (block ids, reader socket)


def _pool(tiering: bool, topo):
    # initial placement = phase-1 working set near: hot head split over the
    # two sockets, the tail (phase 2's future hot set) on the far expander
    leap = LeapConfig(budget_blocks_per_tick=8, tiering=tiering)
    _, drv, _ = make_pool(N_BLOCKS, BLOCK_KB, n_regions=3, leap=leap, topology=topo)
    sess = drv.default_session()
    third = N_BLOCKS // 4
    sess.leap(np.arange(third, 2 * third), 1)
    sess.leap(np.arange(2 * third, N_BLOCKS), 2)
    assert sess.drain()
    drv.stats.bytes_per_link.clear()  # setup traffic is not part of the run
    return drv, sess


def _run_strategy(strategy: str, topo, trace: ShiftTrace):
    drv, sess = _pool(tiering=(strategy == "tiering"), topo=topo)
    near, _ = split_tiers(topo)
    unit_bytes = drv.cfg.budget_blocks_per_tick * drv.pool_cfg.block_bytes
    bb = drv.pool_cfg.block_bytes

    policy = None
    balancer = None
    if strategy == "tiering":
        policy = TieringPolicy(
            drv,
            TieringConfig(
                hot_watermark=1.0,
                cold_watermark=0.05,
                cooldown_ticks=24,
                epoch_ticks=4,
                max_promotions=16,
                max_demotions=8,
            ),
        )
    elif strategy == "sampling":
        balancer = AutoBalancer(
            drv.pool_cfg,
            N_BLOCKS,
            SamplingConfig(scan_budget_blocks=8, hot_threshold=3, decay=0.5),
        )

    prev_link: dict = {}
    modeled = 0.0
    hits = reads = 0
    for tick in range(TICKS):
        ids, reader = trace.reads(tick)
        placement = drv.host_placement()
        regions = placement[ids]
        hits += int(np.isin(regions, near).sum())
        reads += len(ids)
        # access bytes: every remote read moves one block over reader->home
        access: dict = {}
        for d in regions[regions != reader]:
            key = (reader, int(d))
            access[key] = access.get(key, 0) + bb
        drv.note_reads(ids)
        if balancer is not None:
            balancer.observe_driver(drv, ids, reader)
            if tick % 4 == 3:
                sess.apply(balancer)
        if policy is not None:
            policy.maybe_apply(sess)
        drv.tick()
        cur = dict(drv.stats.bytes_per_link)
        for k, v in cur.items():
            delta = v - prev_link.get(k, 0)
            if delta:
                access[k] = access.get(k, 0) + delta
        prev_link = cur
        modeled += modeled_tick_time(access, topo, unit_bytes)
    assert sess.drain()
    assert drv.verify_mirror()
    hit = hits / reads
    return {
        "drv": drv,
        "modeled": modeled,
        "hit": hit,
        "miss": 100.0 * (1.0 - hit),
        "pingpong": drv.stats.ping_pong_migrations,
    }


def run():
    topo = NumaTopology.cxl_pooled(2, 1)
    trace = ShiftTrace()
    res = {s: _run_strategy(s, topo, trace) for s in ("static", "sampling", "tiering")}

    st, sa, ti = res["static"], res["sampling"], res["tiering"]
    # acceptance: the closed loop adapts to the rotation (beats never-moving
    # placement on modeled time) AND its hysteresis beats the sampler on churn
    assert ti["modeled"] < st["modeled"], (ti["modeled"], st["modeled"])
    assert sa["pingpong"] > 0, "sampling baseline must exhibit ping-pong"
    assert ti["pingpong"] < sa["pingpong"], (ti["pingpong"], sa["pingpong"])

    for name in ("static", "sampling", "tiering"):
        r = res[name]
        drv = r["drv"]
        extra = ""
        if name == "tiering":
            extra = (
                f";promoted={drv.stats.tier_promotions}"
                f";demoted={drv.stats.tier_demotions}"
                f";speedup=x{st['modeled'] / r['modeled']:.2f}"
            )
        emit(
            f"fig11/shift/{name}",
            r["modeled"] * 1e3,
            f"modeled={r['modeled']:.1f};hit={100 * r['hit']:.1f}%"
            f";miss={r['miss']:.1f}%;pingpong={r['pingpong']}" + extra,
        )

    run_serving(topo)
    return res


# ---------------------------------------------------------------------------
# Serving scenario: KV-cache working-set shift over PagedEngine
# ---------------------------------------------------------------------------

SERVE_STEPS = 24  # decode steps per phase


def _serving_case(tiering: bool, topo):
    import dataclasses

    import jax

    from repro.configs.base import get_config
    from repro.configs.smoke import reduce
    from repro.models import lm
    from repro.serving.engine import PagedConfig, PagedEngine

    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    eng = PagedEngine(
        cfg,
        params,
        PagedConfig(
            block_tokens=4,
            max_blocks_per_seq=32,
            n_regions=3,
            slots_per_region=32,
            topology=topo,
            # small areas + force escalation: the append frontier is dirtied
            # every decode step, and must not drag its area-mates' verdicts
            leap=LeapConfig(
                initial_area_blocks=2,
                budget_blocks_per_tick=8,
                max_attempts_before_force=4,
                tiering=tiering,
            ),
        ),
    )
    drv = eng.driver
    near, _ = split_tiers(topo)
    rng = np.random.default_rng(3)
    # six sequences: four resident on the compute sockets, two late arrivals
    # capacity-admitted onto the CXL expander (the near page pools are sized
    # for the resident set) — the sequences whose KV the phase shift heats up
    homes = (0, 0, 1, 1, 2, 2)
    sids = [
        eng.admit(rng.integers(0, cfg.vocab_size, size=11), region=r)
        for r in homes
    ]
    policy = TieringPolicy(
        drv,
        TieringConfig(
            hot_watermark=1.5,
            cold_watermark=0.4,
            cooldown_ticks=24,
            epoch_ticks=2,
            max_promotions=8,
            max_demotions=8,
        ),
    )
    unit_bytes = drv.cfg.budget_blocks_per_tick * drv.pool_cfg.block_bytes
    bb = drv.pool_cfg.block_bytes
    prev_link: dict = {}
    modeled = 0.0
    hits = reads = 0
    toks = []
    for phase, active in enumerate(([sids[0], sids[2]], [sids[4], sids[5]])):
        for _ in range(SERVE_STEPS):
            placement = drv.host_placement()
            access: dict = {}
            for sid in active:
                regions = placement[np.asarray(eng.seqs[sid].block_ids)]
                hits += int(np.isin(regions, near).sum())
                reads += len(regions)
                for d in regions[regions != 0]:  # decode computes on socket 0
                    key = (0, int(d))
                    access[key] = access.get(key, 0) + bb
            if tiering:
                policy.maybe_apply(eng.session)
            eng.tick()
            toks.append(tuple(eng.decode(active)))
            cur = dict(drv.stats.bytes_per_link)
            for k, v in cur.items():
                delta = v - prev_link.get(k, 0)
                if delta:
                    access[k] = access.get(k, 0) + delta
            prev_link = cur
            modeled += modeled_tick_time(access, topo, unit_bytes)
    assert eng.drain()
    assert drv.verify_mirror()
    hit = hits / reads
    return {
        "modeled": modeled,
        "hit": hit,
        "miss": 100.0 * (1.0 - hit),
        "pingpong": drv.stats.ping_pong_migrations,
        "promoted": drv.stats.tier_promotions,
        "toks": toks,
    }


def run_serving(topo=None):
    topo = topo or NumaTopology.cxl_pooled(2, 1)
    st = _serving_case(tiering=False, topo=topo)
    ti = _serving_case(tiering=True, topo=topo)
    # identical token streams (migration never changes decode output) and a
    # strictly better hot-tier hit rate once the active set shifts far
    assert st["toks"] == ti["toks"], "tiering changed decode output"
    assert ti["hit"] > st["hit"], (ti["hit"], st["hit"])
    assert ti["modeled"] < st["modeled"], (ti["modeled"], st["modeled"])
    emit(
        "fig11/serving/static",
        st["modeled"] * 1e3,
        f"modeled={st['modeled']:.1f};hit={100 * st['hit']:.1f}%"
        f";miss={st['miss']:.1f}%;pingpong={st['pingpong']}",
    )
    emit(
        "fig11/serving/tiering",
        ti["modeled"] * 1e3,
        f"modeled={ti['modeled']:.1f};hit={100 * ti['hit']:.1f}%"
        f";miss={ti['miss']:.1f}%;pingpong={ti['pingpong']}"
        f";promoted={ti['promoted']}"
        f";speedup=x{st['modeled'] / ti['modeled']:.2f}",
    )
    return st, ti


if __name__ == "__main__":
    run()
