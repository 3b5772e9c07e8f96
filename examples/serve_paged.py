"""Serving demo: batched decode over the leap-paged KV cache with live
replica rebalancing.

Admits a batch of prompts across two regions, decodes while one sequence's
KV pages migrate to the other region, and verifies outputs are identical to
an undisturbed run (the paper's correctness property, on the serving path).

    PYTHONPATH=src python examples/serve_paged.py
"""

import dataclasses

import jax
import numpy as np

from repro.configs.base import get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine


def main():
    cfg = dataclasses.replace(reduce(get_config("qwen2_7b")), n_layers=2)
    params = lm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (6, 11, 17, 9)]

    def serve(live_migration: bool):
        eng = PagedEngine(
            cfg, params,
            PagedConfig(block_tokens=4, max_blocks_per_seq=32, n_regions=2,
                        slots_per_region=128,
                        leap=LeapConfig(initial_area_blocks=2, budget_blocks_per_tick=2)),
        )
        sids = [eng.admit(p, region=i % 2) for i, p in enumerate(prompts)]
        handle = None
        if live_migration:
            handle = eng.rebalance(sids[0], dst_region=1)
            print(f"rebalancing seq {sids[0]}: {handle.requested} KV pages "
                  f"region 0 -> 1, live ({handle.status.name})")
        outs = []
        for step in range(16):
            if live_migration:
                eng.tick()
            outs.append(tuple(eng.decode(sids)))
        if live_migration:
            assert handle.wait()
            p = handle.progress()
            assert p.committed + p.forced + p.cancelled == p.requested
            s = eng.facade.snapshot_stats()
            print(f"migration: {handle.status.name} committed={p.committed} "
                  f"forced={p.forced} dirty_rejections={s.dirty_rejections}")
        return outs

    base = serve(live_migration=False)
    live = serve(live_migration=True)
    assert base == live, "live migration must not change decode outputs"
    print("16 decode steps x 4 sequences: outputs identical under live page migration ✓")
    print("sample tokens:", [t[:2] for t in base[:4]])


if __name__ == "__main__":
    main()
