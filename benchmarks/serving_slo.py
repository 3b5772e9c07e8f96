"""Beyond-paper: SLO-aware serving under sustained migration load.

An open-loop multi-tenant workload (repro.load) decodes against a paged
engine while background churn keeps a standing migration queue, at two
load levels.  The same deterministic trace runs under the plain
LeapScheduler (fixed per-tick migration budget) and the SloScheduler
(budget paced by per-tenant p99 slack): the gate metric is p50/p99 token
latency vs. sustained migration rate, all in modeled time units so the
percentile surface is machine-independent and CI-gateable at tight
thresholds.

The acceptance property asserted here (and hence enforced by the bench
gate, which fails any suite reporting ok=false): at the high load level
the plain scheduler's migration traffic pushes the interactive tenant past
its per-token SLO, while the SLO scheduler holds p99 within the SLO *and*
keeps a nonzero sustained migration rate — pacing, not parking.
"""

import dataclasses

import jax

from benchmarks import common
from benchmarks.common import emit
from repro.configs.base import get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.load import LoadGenerator, TenantSpec, WorkloadSpec
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine

TICKS = 48
WARMUP = 16  # pacing needs a latency window before it engages
SLO_GOLD = 2.5  # interactive tenant per-token SLO, modeled units


def _spec(load: float) -> WorkloadSpec:
    return WorkloadSpec(
        tenants=(
            TenantSpec("gold", rate=0.45 * load, prompt_tokens=6,
                       decode_tokens=10, slo_latency=SLO_GOLD, priority=2,
                       region=0),
            TenantSpec("batch", rate=0.3 * load, prompt_tokens=8,
                       decode_tokens=14, slo_latency=10.0, priority=0,
                       region=1),
        ),
        ticks=TICKS,
        seed=11,
        churn_every=2,
        churn_count=2,
    )


def _run_one(scheduler: str, load: float) -> dict:
    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    leap = LeapConfig(initial_area_blocks=2, budget_blocks_per_tick=8, max_attempts_before_force=4)
    if common.TRACING:
        leap = dataclasses.replace(leap, telemetry=True)
    eng = PagedEngine(
        cfg, params,
        PagedConfig(block_tokens=4, max_blocks_per_seq=16, n_regions=2,
                    slots_per_region=96, leap=leap, scheduler=scheduler),
    )
    if common.TRACING:
        common.TRACE_SESSIONS.append(
            (f"serving_slo:{scheduler}@{load:g}", eng.driver.telemetry)
        )
    gen = LoadGenerator(eng, _spec(load), scheduler=eng.driver.scheduler)
    gen.run()
    gen.verify_accounting()
    rep = gen.report(warmup=WARMUP)
    assert rep["dropped"] == 0, "queue overflow at benchmark scale"
    return rep


def run():
    for load, tag in ((0.5, "low"), (1.0, "high")):
        reps = {}
        for scheduler in ("leap", "slo"):
            rep = _run_one(scheduler, load)
            reps[scheduler] = rep
            gold = rep["tenants"]["gold"]
            emit(
                f"serving_slo/{scheduler}_load_{tag}",
                rep["modeled_time"],
                f"modeled={rep['modeled_time']:.1f};p50={rep['p50']:.2f};"
                f"p99={rep['p99']:.2f};mig_rate=x{rep['mig_rate']:.3f};"
                f"gold_p99={gold['p99']:.2f};"
                f"slo={'met' if gold['slo_met'] else 'VIOLATED'}",
            )
        if tag == "high":
            # The PR's acceptance property, enforced by the bench gate.
            assert not reps["leap"]["tenants"]["gold"]["slo_met"], (
                "plain scheduler no longer violates the SLO at high load — "
                "retune the workload so the gate still separates the policies"
            )
            assert reps["slo"]["tenants"]["gold"]["slo_met"], (
                f"SloScheduler missed the gold SLO: "
                f"p99 {reps['slo']['tenants']['gold']['p99']:.2f}"
                f" > {SLO_GOLD}"
            )
            assert reps["slo"]["mig_rate"] > 0, (
                "SloScheduler parked migration entirely instead of pacing it"
            )
    return True


if __name__ == "__main__":
    run()
