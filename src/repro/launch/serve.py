"""Serving launcher: batched decode over the paged, migration-managed KV
cache, with optional live rebalancing.

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --smoke \
        --requests 8 --tokens 32 --rebalance

Without ``--smoke`` the model runs at its published widths and depth, with
its published multipliers and random weights, over 16-token KV pages and two
regions of 1024 slots.  For granite_3_2b that is 5.07 GB of bf16 weights and
a 2.68 GB pool of 1,310,720-byte pages, half of whose slots are migration
headroom: 1,024 pages, 16,384 tokens of KV, fit one TPU v5e.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, ModelConfig, canon, get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine


def model_config(arch: str, smoke: bool) -> ModelConfig:
    cfg = get_config(canon(arch))
    if smoke:
        cfg = dataclasses.replace(reduce(cfg), n_layers=2)
    if not cfg.embed_inputs:
        raise SystemExit(f"{cfg.name}: stub-frontend arch; serve the backbone "
                         f"via contiguous decode (launch.dryrun decode cells)")
    return cfg


def init_params(cfg: ModelConfig, seed: int):
    """Random weights from ``seed``, built on the device in one program."""
    return jax.jit(lm.init_params, static_argnums=1)(jax.random.key(seed), cfg)


def paged_config(smoke: bool, regions: int, max_tokens: int) -> PagedConfig:
    """KV pool for sequences of up to ``max_tokens`` (prompt + decode)."""
    blk = 4 if smoke else 16
    leap = (
        LeapConfig(initial_area_blocks=4, budget_blocks_per_tick=4)
        if smoke
        else LeapConfig()
    )
    return PagedConfig(
        block_tokens=blk,
        max_blocks_per_seq=max(-(-max_tokens // blk) + 2, 8),
        n_regions=regions,
        slots_per_region=256 if smoke else 1024,
        leap=leap,
    )


def serve(eng: PagedEngine, prompts, tokens: int, rebalance=(), on_step=None):
    """Admit ``prompts`` round-robin over the regions, start a live rebalance
    of each request index in ``rebalance`` to the next region, then decode
    ``tokens`` steps with one migration tick before each step.

    Waits for the rebalances to resolve.  Returns the sequence ids and the
    rebalance handles; ``eng.seqs[sid].tokens`` holds each prompt followed
    by its generated tokens.
    """
    n = eng.pcfg.n_regions
    sids = [eng.admit(p, region=i % n) for i, p in enumerate(prompts)]
    handles = [eng.rebalance(sids[i], dst_region=(i + 1) % n) for i in rebalance]
    for step in range(tokens):
        if handles:
            eng.tick()
        out = eng.decode(sids)
        if on_step is not None:
            on_step(step, out)
    for h in handles:
        if not h.wait():
            raise RuntimeError(f"rebalance {h!r} did not resolve")
    return sids, handles


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help="|".join(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--regions", type=int, default=2)
    ap.add_argument("--rebalance", action="store_true",
                    help="live-migrate request 0's KV pages mid-decode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = model_config(args.arch, args.smoke)
    params = init_params(cfg, args.seed)
    eng = PagedEngine(
        cfg,
        params,
        paged_config(args.smoke, args.regions, args.prompt_len + args.tokens),
    )
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=args.prompt_len)
        for _ in range(args.requests)
    ]

    def show(step, out):
        if step < 3 or step == args.tokens - 1:
            print(f"step {step:3d}: {out}")

    t0 = time.perf_counter()
    sids, handles = serve(
        eng, prompts, args.tokens, rebalance=(0,) if args.rebalance else (),
        on_step=show,
    )
    dt = time.perf_counter() - t0
    print(f"served {len(sids)} requests across {args.regions} regions")
    for h in handles:
        p = h.progress()
        print(f"rebalanced {p.requested} pages: committed={p.committed} "
              f"forced={p.forced}")
    total = args.tokens * len(sids)
    print(f"{total} tokens in {dt:.2f}s host wall time, compilation included")


if __name__ == "__main__":
    main()
