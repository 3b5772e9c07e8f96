"""Paper Fig. 1 — local vs remote accesses under different patterns.

On the TPU target, a "remote access" reads a block whose physical slot lives
on another mesh region: the bytes traverse ICI instead of local HBM.  We
measure (CPU host): sequential/random reads and writes through the block
table with (a) all-local placement and (b) remote placement where every
access requires the cross-region staging copy.  The ``derived`` column adds
the modeled TPU ratio: HBM 819 GB/s vs ICI ~50 GB/s -> ~16x per byte, far
more pronounced than the 2-3x of 2-socket x86 NUMA (why migration pays off
*more* on pods).
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, flat_copy_ids, make_pool, timeit
from repro.core import leap_read, leap_write
from repro.core.migrator import fused_copy
from repro.roofline.model import HBM_BW, ICI_BW


def run(n_blocks=256, block_kb=256):
    cfg, drv, data = make_pool(n_blocks, block_kb, n_regions=2, initial_region=0)
    total_mb = n_blocks * block_kb / 1024
    rng = np.random.default_rng(0)
    seq_ids = jnp.arange(n_blocks)
    rnd_ids = jnp.asarray(rng.permutation(n_blocks).astype(np.int32))
    staging_slots = jnp.arange(n_blocks)
    vals = drv.read(seq_ids)  # realized buffer for writes

    def local_read(ids):
        return leap_read(drv.state, ids)

    def remote_read(src, dst):
        # access from region 1 to blocks resident on region 0: the bytes
        # cross the interconnect (staging copy into the reader's region)
        st = fused_copy(drv.state, src, dst)
        out = st.pool[1, staging_slots]
        drv.state = st
        return out

    for pattern, ids in (("seq", seq_ids), ("rand", rnd_ids)):
        t_loc = timeit(local_read, ids)
        t_rem = timeit(remote_read, *flat_copy_ids(drv, ids, 1, staging_slots))
        modeled = (1 / HBM_BW) / (1 / ICI_BW)
        emit(
            f"fig1/read_{pattern}_local_{total_mb:.0f}MB",
            t_loc * 1e6,
            f"GBps={total_mb / 1024 / t_loc:.2f}",
        )
        emit(
            f"fig1/read_{pattern}_remote_{total_mb:.0f}MB",
            t_rem * 1e6,
            f"measured_x{t_rem / t_loc:.2f};modeled_tpu_x{1/modeled:.1f}",
        )

    def local_write(ids):
        drv.state = leap_write(drv.state, ids, vals)
        return drv.state.pool

    t_w = timeit(local_write, seq_ids)
    emit(f"fig1/write_seq_local_{total_mb:.0f}MB", t_w * 1e6,
         f"GBps={total_mb / 1024 / t_w:.2f}")
    return True


if __name__ == "__main__":
    run()
