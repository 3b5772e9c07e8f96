"""The plain reference of block contents finds a block that is not as written."""

import jax.numpy as jnp
import numpy as np

from chipbench.reference import blocks


def test_write_log_check_finds_a_stale_block():
    shape = (8, 128)
    versions = np.array([0, 3, 1, 2], np.int32)
    ids = jnp.arange(4, dtype=jnp.int32)
    held = blocks.block_values(np.uint32(7), ids, jnp.asarray(versions), shape)
    assert blocks.count_bad_blocks(lambda i: held[np.asarray(i)], 7, versions, shape) == 0
    stale = held.at[2].set(blocks.block_values(np.uint32(7), ids[2:3], jnp.zeros(1, jnp.int32),
                                               shape)[0])
    assert blocks.count_bad_blocks(lambda i: stale[np.asarray(i)], 7, versions, shape) == 1
    # another seed is another write log
    assert blocks.count_bad_blocks(lambda i: held[np.asarray(i)], 8, versions, shape) == 4
