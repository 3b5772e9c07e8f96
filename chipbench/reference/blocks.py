"""Plain reference for block contents: the write-log hash and the per-block check.

Every block's contents are a pure function of (seed, block id, version),
where the version counts the writes the block has had.  A stale copy, a
lost write or a block read from the wrong slot differs from what its write
log implies.  Nothing here imports the system under test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("shape",))
def block_values(seed, ids, versions, shape):
    """Contents of blocks ``ids`` after ``versions`` writes: a hash of (seed,
    block, version, element) in [0, 1).  ``seed`` is taken modulo 2**32."""
    n = int(np.prod(shape))
    elem = jax.lax.iota(jnp.uint32, n).reshape(shape)
    key = (
        ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        ^ versions.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        ^ jnp.asarray(seed, jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    )
    x = elem[None] * jnp.uint32(0x27D4EB2F) + key.reshape((-1,) + (1,) * len(shape))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)


@jax.jit
def mismatches(blocks, want):
    """Per block: the number of elements that differ from ``want``."""
    return jnp.sum(blocks != want, axis=tuple(range(1, blocks.ndim)))


def count_bad_blocks(read, seed, versions, shape, batch=512) -> int:
    """Blocks whose contents differ from their write log.

    ``read(ids)`` returns the blocks ``ids`` as the system holds them; each
    is compared on the device with :func:`block_values` of its version.
    """
    bad = 0
    for lo in range(0, len(versions), batch):
        ids = np.arange(lo, min(lo + batch, len(versions)), dtype=np.int32)
        want = block_values(np.uint32(seed % 2**32), jnp.asarray(ids),
                            jnp.asarray(versions[ids]), shape)
        bad += int(np.count_nonzero(np.asarray(mismatches(read(ids), want))))
    return bad
