"""Leap pool state: the device-resident data plane of `page_leap()` on TPU.

The paper separates *virtual* pages (what the application names) from
*physical* pages (where bytes live) and migrates by copying physically and
re-mapping virtually.  Here the same separation is:

  logical block id  (0..n_blocks)    -- what the application names
  (region, slot)                     -- where the bytes live: ``pool[r, s]``

``pool`` is a single pre-allocated buffer ``[n_regions, slots_per_region,
*block_shape]`` whose leading (region) dimension is sharded over a mesh axis
in production, so region ``r`` physically lives in the HBM of mesh row ``r``
("NUMA region" ≙ mesh region).  The ``table`` maps logical blocks to their
physical location and is replicated (it is the page table).  ``dirty`` and
``in_flight`` implement the paper's write-detection protocol: a write to a
block that is currently being copied marks it dirty, which causes the commit
(the atomic "remap") to reject and requeue the block.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.topology import NumaTopology

REGION = 0  # column index of the region coordinate in ``table``
SLOT = 1  # column index of the slot coordinate in ``table``


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Static description of a leap pool.

    Attributes:
      n_regions: number of memory regions (NUMA analogue; mesh-axis size).
      slots_per_region: physical capacity of each region, in blocks.
      block_shape: shape of one block's payload (e.g. ``(rows, cols)`` for a
        morsel pool or ``(layers, 2, blk_tokens, kv_heads * head_dim)`` for
        KV).  On TPU keep the minor dim a multiple of 128 lanes: a narrower
        one is padded to 128 in HBM.
      dtype: payload dtype.
      region_axis: mesh axis name the region dim is sharded over, or None for
        single-device operation (tests / benches).
      huge_factor: G — small slots per huge block (two-tier pool; 1 = small
        only).  A huge block is G physically-contiguous, G-aligned slots in
        one region whose G logical blocks share one level-1 table entry (see
        repro.pool and DESIGN.md §5).  Must be a power of two dividing
        slots_per_region so huge runs never straddle a region boundary.
      topology: optional :class:`repro.topology.NumaTopology` describing
        region-pair distances and per-link bandwidth budgets.  With a
        topology attached the driver schedules link-aware (per-link budgets,
        congestion deferral, two-hop routing — DESIGN.md §7); ``None`` keeps
        the uniform all-links-equal behaviour.
    """

    n_regions: int
    slots_per_region: int
    block_shape: tuple[int, ...]
    dtype: jnp.dtype = jnp.float32
    region_axis: str | tuple[str, ...] | None = None
    huge_factor: int = 1
    topology: "NumaTopology | None" = None

    def __post_init__(self):
        g = self.huge_factor
        if g < 1 or (g & (g - 1)) != 0:
            raise ValueError(f"huge_factor must be a power of two, got {g}")
        if self.slots_per_region % g != 0:
            raise ValueError(
                f"huge_factor {g} must divide slots_per_region "
                f"{self.slots_per_region}"
            )
        if self.topology is not None and self.topology.n_regions != self.n_regions:
            raise ValueError(
                f"topology covers {self.topology.n_regions} regions, "
                f"pool has {self.n_regions}"
            )

    @property
    def block_elems(self) -> int:
        return int(np.prod(self.block_shape))

    @property
    def block_bytes(self) -> int:
        return self.block_elems * jnp.dtype(self.dtype).itemsize

    @property
    def capacity_blocks(self) -> int:
        return self.n_regions * self.slots_per_region


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LeapState:
    """Device-resident migration state (a pytree; all programs are pure).

    pool:      [R, S, *block_shape]  physical storage, region-major.
    table:     [N, 2] int32          logical block -> (region, slot).
    dirty:     [N]    bool           written while in flight (invalidates copy).
    in_flight: [N]    bool           currently under an open copy epoch.
    """

    pool: jax.Array
    table: jax.Array
    dirty: jax.Array
    in_flight: jax.Array

    @property
    def n_blocks(self) -> int:
        return self.table.shape[0]


def init_state(
    cfg: PoolConfig,
    n_blocks: int,
    initial_regions: Sequence[int] | np.ndarray,
    mesh: jax.sharding.Mesh | None = None,
) -> LeapState:
    """Create a pool with ``n_blocks`` logical blocks placed per ``initial_regions``.

    Blocks are assigned slots densely within each region, in block-id order
    (the host driver mirrors this allocation).  With a ``mesh`` the state is
    created already laid out per :func:`state_sharding`, so each device
    allocates only its own regions; without one it lands on the default
    device.
    """
    initial_regions = np.asarray(initial_regions, dtype=np.int32)
    if initial_regions.shape != (n_blocks,):
        raise ValueError(
            f"initial_regions must have shape ({n_blocks},), got {initial_regions.shape}"
        )
    if n_blocks > cfg.capacity_blocks:
        raise ValueError("more logical blocks than physical capacity")
    if n_blocks and (
        initial_regions.min() < 0 or initial_regions.max() >= cfg.n_regions
    ):
        raise ValueError(
            f"initial_regions must lie in [0, {cfg.n_regions}), got range "
            f"[{initial_regions.min()}, {initial_regions.max()}]"
        )
    # Dense per-region slot assignment in block-id order, vectorized: a stable
    # sort groups blocks by region while preserving id order, so the rank of a
    # block within its group is its slot.
    counts = np.bincount(initial_regions, minlength=cfg.n_regions)
    over = np.nonzero(counts > cfg.slots_per_region)[0]
    if len(over):
        raise ValueError(f"region {over[0]} over capacity during initial placement")
    order = np.argsort(initial_regions, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slots = np.empty(n_blocks, dtype=np.int32)
    slots[order] = np.arange(n_blocks, dtype=np.int32) - np.repeat(
        starts, counts
    ).astype(np.int32)
    table = np.stack([initial_regions, slots], axis=1).astype(np.int32)
    shape = (cfg.n_regions, cfg.slots_per_region) + tuple(cfg.block_shape)
    sh = state_sharding(cfg, mesh) if mesh is not None else None
    return LeapState(
        pool=jnp.zeros(shape, cfg.dtype, device=sh and sh.pool),
        table=jnp.asarray(table, device=sh and sh.table),
        dirty=jnp.zeros((n_blocks,), jnp.bool_, device=sh and sh.dirty),
        in_flight=jnp.zeros((n_blocks,), jnp.bool_, device=sh and sh.in_flight),
    )


def state_sharding(cfg: PoolConfig, mesh: jax.sharding.Mesh) -> LeapState:
    """NamedSharding pytree for a LeapState on ``mesh``.

    The pool's region dim is sharded over ``cfg.region_axis``; the table and
    flag vectors are replicated (they are the "page table" every region
    consults).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = cfg.region_axis
    ndim_payload = len(cfg.block_shape)
    pool_spec = P(axis, *([None] * (1 + ndim_payload)))
    rep = NamedSharding(mesh, P())
    return LeapState(
        pool=NamedSharding(mesh, pool_spec),
        table=rep,
        dirty=rep,
        in_flight=rep,
    )


# --------------------------------------------------------------------------
# Logical reads / writes through the table.
#
# ``leap_write`` is the SIGSEGV-handler analogue: the framework owns every
# mutation, so "trapping" a write is simply fusing ``dirty |= in_flight`` into
# the write program.  Writes always land at the *current* physical location;
# dirtiness only matters for blocks with an open copy epoch.
# --------------------------------------------------------------------------


@partial(jax.jit, donate_argnames=())
def leap_read(state: LeapState, block_ids: jax.Array) -> jax.Array:
    """Gather whole blocks: returns ``[len(block_ids), *block_shape]``."""
    loc = state.table[block_ids]
    return state.pool[loc[:, REGION], loc[:, SLOT]]


@partial(jax.jit, donate_argnames=("state",))
def leap_write(state: LeapState, block_ids: jax.Array, values: jax.Array) -> LeapState:
    """Overwrite whole blocks; marks in-flight blocks dirty."""
    loc = state.table[block_ids]
    pool = state.pool.at[loc[:, REGION], loc[:, SLOT]].set(
        values.astype(state.pool.dtype)
    )
    dirty = state.dirty.at[block_ids].set(
        state.dirty[block_ids] | state.in_flight[block_ids]
    )
    return dataclasses.replace(state, pool=pool, dirty=dirty)


@partial(jax.jit, donate_argnames=("state",))
def leap_write_rows(
    state: LeapState,
    block_ids: jax.Array,
    row_offsets: jax.Array,
    rows: jax.Array,
) -> LeapState:
    """Partial-block write: one row (first payload dim) per entry.

    ``rows`` has shape ``[K, *block_shape[1:]]``.  Same dirty semantics as
    ``leap_write`` — the paper's protocol does not care how much of the page
    was written, only *that* it was written during an open copy.
    """
    loc = state.table[block_ids]
    pool = state.pool.at[loc[:, REGION], loc[:, SLOT], row_offsets].set(
        rows.astype(state.pool.dtype)
    )
    dirty = state.dirty.at[block_ids].set(
        state.dirty[block_ids] | state.in_flight[block_ids]
    )
    return dataclasses.replace(state, pool=pool, dirty=dirty)


@jax.jit
def block_regions(state: LeapState, block_ids: jax.Array) -> jax.Array:
    return state.table[block_ids, REGION]


# --------------------------------------------------------------------------
# Tier-aware (group) semantics.
#
# A huge block is G logical blocks [g*G, (g+1)*G) whose table entries expand
# to one contiguous slot run, so the flat table/dirty/in_flight vectors keep
# working per block; the group views below are the level-1 semantics: a huge
# read is one contiguous slice, and a huge copy epoch is dirtied by a write
# to *any* member (the commit verdict is the OR over the run, exactly like a
# huge-page PTE covering G small pages).
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("huge_factor",))
def huge_read(state: LeapState, group_ids: jax.Array, huge_factor: int) -> jax.Array:
    """Read whole huge blocks: ``[len(group_ids), G, *block_shape]``.

    Resolves one level-1 entry (member 0's location) per group and slices the
    contiguous run — G blocks per table lookup instead of G lookups.
    """
    first = group_ids * huge_factor
    loc = state.table[first]
    slots = loc[:, SLOT, None] + jnp.arange(huge_factor)[None, :]
    return state.pool[loc[:, REGION, None], slots]


@partial(jax.jit, static_argnames=("huge_factor",))
def group_dirty(state: LeapState, group_ids: jax.Array, huge_factor: int) -> jax.Array:
    """Level-1 dirty view: a group is dirty iff any member is dirty."""
    members = group_ids[:, None] * huge_factor + jnp.arange(huge_factor)[None, :]
    return state.dirty[members].any(axis=1)


@partial(jax.jit, static_argnames=("huge_factor",))
def group_in_flight(
    state: LeapState, group_ids: jax.Array, huge_factor: int
) -> jax.Array:
    """Level-1 in-flight view: a group is in flight iff any member is."""
    members = group_ids[:, None] * huge_factor + jnp.arange(huge_factor)[None, :]
    return state.in_flight[members].any(axis=1)


def flat_pool_view(pool: jax.Array) -> jax.Array:
    """Reshape ``pool [R, S, *blk]`` to the kernel layout ``[R*S, *blk]``.

    A (region, slot) pair becomes the flat slot ``region * S + slot``.  Only
    the two leading dims merge and the payload keeps its shape: TPU tiles an
    HBM array over its two minor dims, so merging leading dims is a bitcast
    and a donated pool stays aliased through the view.  Collapsing the
    payload as well would not be free: folding ``[.., KVH, hd]`` into rows
    changes the minor-dim tiling, and XLA then copies the whole pool in and
    out of every program that takes the view.
    """
    r, s = pool.shape[:2]
    return pool.reshape((r * s,) + tuple(pool.shape[2:]))


def placement_histogram(state: LeapState, n_regions: int) -> np.ndarray:
    """Host-side histogram: how many blocks currently live on each region."""
    regions = np.asarray(state.table[:, REGION])
    return np.bincount(regions, minlength=n_regions)
