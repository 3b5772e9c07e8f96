"""Beyond-paper: live paged-KV rebalancing during batched decode.

A batch of sequences decodes while one sequence's pages leap-migrate to
another replica region.  Compares decode throughput (tokens/s) with no
migration, with live leap migration, and with a stop-the-world sync
reshard — both the mean slowdown and the p99 per-step tail slowdown (the
tail is where migration interference hides from a mean).  Also asserts
token-identical outputs (the engine test's property, here at benchmark
scale).
"""

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.base import get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine

STEPS = 24


def _engine(cfg, params):
    return PagedEngine(
        cfg, params,
        PagedConfig(block_tokens=4, max_blocks_per_seq=32, n_regions=2,
                    slots_per_region=128,
                    leap=LeapConfig(initial_area_blocks=2, budget_blocks_per_tick=2,
                                    max_attempts_before_force=4)),
    )


def run():
    cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(4)]

    def decode_run(migrate: str):
        eng = _engine(cfg, params)
        sids = [eng.admit(p, region=0) for p in prompts]
        toks = []
        handle = None
        steps = []  # per-decode-step wall seconds (tail analysis)
        t0 = time.perf_counter()
        if migrate == "sync":
            # stop-the-world: wait the whole migration out before decoding
            handle = eng.rebalance(sids[0], 1)
            assert handle.wait()
        elif migrate == "live":
            handle = eng.rebalance(sids[0], 1)
        for _ in range(STEPS):
            s0 = time.perf_counter()
            if migrate == "live":
                eng.tick()
            toks.append(tuple(eng.decode(sids)))
            steps.append(time.perf_counter() - s0)
        if migrate == "live":
            assert handle.wait()
        if handle is not None:
            p = handle.progress()
            assert p.committed + p.forced + p.cancelled == p.requested, p
            assert handle.done and p.cancelled == 0
        dt = time.perf_counter() - t0
        return toks, dt, steps

    for mode in ("none", "live", "sync"):  # compile-cache warmup
        decode_run(mode)

    # Interleave the repetitions round-robin so every mode samples the same
    # host-load phases, then take each mode's best: noise only ever adds
    # time, and correlated load cancels out of the slowdown ratios the CI
    # bench gate enforces.
    outs: dict = {}
    times: dict = {"none": [], "live": [], "sync": []}
    steps: dict = {"none": [], "live": [], "sync": []}
    for _ in range(3):
        for mode in ("none", "live", "sync"):
            toks, dt, st = decode_run(mode)
            outs.setdefault(mode, toks)
            times[mode].append(dt)
            steps[mode].append(st)
    base, t_base = outs["none"], min(times["none"])
    live, t_live = outs["live"], min(times["live"])
    sync, t_sync = outs["sync"], min(times["sync"])
    assert live == base, "live migration changed decode outputs!"
    assert sync == base

    def p99(mode: str) -> float:
        # Elementwise best-of-reps per decode step (noise only ever adds
        # time, and the migration schedule is identical across reps), then
        # the tail of the per-step distribution.
        best = np.min(np.asarray(steps[mode]), axis=0)
        return float(np.percentile(best, 99))

    tps = STEPS * len(prompts)
    p99_base = p99("none")
    emit("serving/decode_no_migration", t_base / tps * 1e6, "tok_s_base")
    emit(
        "serving/decode_live_leap",
        t_live / tps * 1e6,
        f"slowdown={100 * (t_live / t_base - 1):.0f}%;"
        f"p99_slowdown={100 * (p99('live') / p99_base - 1):.0f}%;"
        f"outputs=identical",
    )
    emit(
        "serving/decode_sync_reshard",
        t_sync / tps * 1e6,
        f"slowdown={100 * (t_sync / t_base - 1):.0f}%;"
        f"p99_slowdown={100 * (p99('sync') / p99_base - 1):.0f}%;"
        f"outputs=identical",
    )
    return True


if __name__ == "__main__":
    run()
