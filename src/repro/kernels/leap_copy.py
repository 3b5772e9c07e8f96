"""Pallas TPU kernel: block gather/scatter by dynamic slot index — the
physical-copy hot path of leap migration (the paper's ``memcpy`` analogue).

On TPU the migration copy is HBM -> HBM: each grid step issues one DMA from
the source slot (or contiguous run of slots) straight to its destination,
with the slot ids *scalar-prefetched* into SMEM so they address the DMA
descriptors directly.  Nothing is staged in VMEM, so the block size is not
bounded by the scoped VMEM limit, and a huge-block run moves as one DMA.

Layout: every kernel takes the pool as ``[S, *block]`` and slices only the
leading (slot) dim.  On TPU, HBM arrays are tiled over their two minor dims,
so a slot slice is tile-aligned for any payload, and the flat view
``[R*S, *block]`` of a ``[R, S, *block]`` pool is a bitcast
(:func:`repro.core.state.flat_pool_view`).  Payloads should keep a
lane-dense minor dim (a multiple of 128): a narrower minor dim is padded to
128 lanes in HBM, which doubles a 64-lane pool's footprint, and Mosaic
refuses a DMA of such a slice.

Kernels are written for TPU and validated on CPU with ``interpret=True``
(see tests/test_kernels_leap_copy.py); ``ops.py`` picks the implementation.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dma(src, dst, sem):
    copy = pltpu.make_async_copy(src, dst, sem)
    copy.start()
    copy.wait()


def _grid_spec(n_prefetch: int, n_in: int, k: int) -> pltpu.PrefetchScalarGridSpec:
    """``k`` sequential DMA steps; the ``n_in`` array operands and the output
    stay in HBM (``pl.ANY``), the slot ids are scalar-prefetched."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(k,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_in,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )


def _out_like(pool: jax.Array, shape) -> jax.ShapeDtypeStruct:
    """Output type: the pool's dtype, varying over the same mesh axes as the
    pool (required when a kernel runs inside ``shard_map``)."""
    return jax.ShapeDtypeStruct(shape, pool.dtype, vma=jax.typeof(pool).vma)


def _check(pool: jax.Array) -> None:
    if pool.ndim < 2:
        raise ValueError(f"pool must be [slots, *block], got {pool.shape}")


def gather_blocks_pallas(
    pool: jax.Array, idx: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Gather ``pool[idx]`` -> ``[K, *block]``, one block DMA per grid step.

    pool: ``[S, *block]`` region-local physical slots.
    idx:  ``[K]`` int32 slot ids (scalar-prefetched; address the DMAs).
    """
    _check(pool)
    k = idx.shape[0]

    def kernel(idx_ref, pool_ref, out_ref, sem):
        i = pl.program_id(0)
        _dma(pool_ref.at[idx_ref[i]], out_ref.at[i], sem)

    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(1, 1, k),
        out_shape=_out_like(pool, (k,) + pool.shape[1:]),
        name="leap_gather_blocks",
        interpret=interpret,
    )(idx, pool)


def scatter_blocks_pallas(
    pool: jax.Array, idx: jax.Array, blocks: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Scatter ``blocks`` into ``pool`` at slot ids ``idx`` (in-place via aliasing).

    pool:   ``[S, *block]`` (donated/aliased to the output — no pool copy).
    idx:    ``[K]`` int32 destination slots; duplicate ids: last grid step wins
            (grid steps are sequential and each waits for its DMA).
    blocks: ``[K, *block]``.
    """
    _check(pool)
    k = idx.shape[0]

    def kernel(idx_ref, blocks_ref, pool_ref, out_ref, sem):
        del pool_ref  # aliased to out_ref: untouched slots are preserved
        i = pl.program_id(0)
        _dma(blocks_ref.at[i], out_ref.at[idx_ref[i]], sem)

    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(1, 2, k),
        out_shape=_out_like(pool, pool.shape),
        # alias indices count every operand incl. scalar prefetch: pool is #2
        input_output_aliases={2: 0},
        name="leap_scatter_blocks",
        interpret=interpret,
    )(idx, blocks, pool)


def copy_blocks_pallas(
    pool: jax.Array,
    src_idx: jax.Array,
    dst_idx: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused intra-pool copy: ``pool[dst_idx[i]] = pool[src_idx[i]]``.

    The same-device path of a migration (regions that share one chip's HBM,
    or defragmentation): one grid step DMAs slot ``src_idx[i]`` to slot
    ``dst_idx[i]`` of the aliased pool, without a staging buffer.
    Destinations must be disjoint from sources (fresh allocations are).
    """
    _check(pool)
    k = src_idx.shape[0]

    def kernel(src_ref, dst_ref, pool_ref, out_ref, sem):
        i = pl.program_id(0)
        _dma(pool_ref.at[src_ref[i]], out_ref.at[dst_ref[i]], sem)

    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(2, 1, k),
        out_shape=_out_like(pool, pool.shape),
        input_output_aliases={2: 0},  # pool aliased to output
        name="leap_copy_blocks",
        interpret=interpret,
    )(src_idx, dst_idx, pool)


def copy_runs_pallas(
    pool: jax.Array,
    src_starts: jax.Array,
    dst_starts: jax.Array,
    run: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Contiguous-run copy: ``pool[dst_starts[i] : +run] = pool[src_starts[i] : +run]``.

    The huge-block fast path of a two-tier migration: one grid step moves a
    whole ``run``-slot huge block as ONE DMA of ``run`` contiguous slots
    instead of ``run`` separate per-slot copies.  Starts must be
    ``run``-aligned — guaranteed by the buddy allocator (G divides S, so a
    run never straddles a region of the flat view).
    """
    _check(pool)
    s = pool.shape[0]
    if run < 1 or s % run != 0:
        raise ValueError(f"run {run} must divide slot count {s}")
    k = src_starts.shape[0]

    def kernel(src_ref, dst_ref, pool_ref, out_ref, sem):
        i = pl.program_id(0)
        src = pl.multiple_of(src_ref[i], run)
        dst = pl.multiple_of(dst_ref[i], run)
        _dma(pool_ref.at[pl.ds(src, run)], out_ref.at[pl.ds(dst, run)], sem)

    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(2, 1, k),
        out_shape=_out_like(pool, pool.shape),
        input_output_aliases={2: 0},  # pool aliased to output
        name="leap_copy_runs",
        interpret=interpret,
    )(src_starts, dst_starts, pool)
