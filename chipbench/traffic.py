"""The one traffic generator: it turns a mix's parameters and a seed into work.

A mix is a JSON file under ``chipbench/traffic/`` holding the parameters
that :func:`write_bursts` reads.  The seed changes which blocks each burst
writes, not how many.
"""

from __future__ import annotations

import numpy as np


def write_bursts(mix: dict, seed: int, n_blocks: int):
    """Endless foreground write bursts: arrays of ``burst_blocks`` distinct
    block ids, drawn uniformly, or with probability ``hot_share`` from the
    first ``hot_fraction`` of the blocks (the paper's 3.125% hot set)."""
    rng = np.random.default_rng([seed, 1])  # any non-negative seed
    burst = int(mix["burst_blocks"])
    hot_share = float(mix.get("hot_share", 0.0))
    hot = max(burst, int(float(mix.get("hot_fraction", 0.03125)) * n_blocks))
    while True:
        span = hot if hot_share and rng.random() < hot_share else n_blocks
        yield rng.choice(span, burst, replace=False).astype(np.int32)
