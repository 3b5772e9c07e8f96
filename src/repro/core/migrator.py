"""Jitted migration programs: the device data plane of ``page_leap()``.

An area's life cycle (driven from the host by :mod:`repro.core.driver`):

    begin_area   -> open the copy epoch (set in_flight, clear dirty)
    copy_chunk*  -> physical copy, source region -> pooled destination slots
                    (budgeted; an epoch may span many steps, which is the
                    window in which concurrent writes can dirty a block)
    commit_area  -> the atomic "remap": flip table entries of *clean* blocks
                    to their destination, return the dirty verdict so the
                    host can requeue dirty blocks with adaptive splitting

``force_migrate`` fuses copy+flip into one XLA program.  Because writes are
serialized against programs at step granularity, a fused copy+flip has no
race window at all — this is the write-through escalation that gives the
(beyond-paper) deterministic-termination guarantee.

Two copy backends:

  * ``xla``       — indexed gather/scatter across the sharded region dim;
                    GSPMD materializes the cross-region traffic.  Works on
                    any mesh (incl. compound ("pod","data") region axes) and
                    on a single device.
  * ``ppermute``  — shard_map + ``lax.ppermute`` with *static* src/dst
                    regions: exactly one point-to-point ICI transfer of the
                    area bytes (the `memcpy` analogue).  The local HBM
                    gather/scatter packing inside the shard is the
                    ``leap_copy`` Pallas kernel on TPU.

Three dispatch generations (DESIGN.md §3, §12):

  * the per-area/per-chunk programs (``begin_area``/``copy_chunk``/
    ``commit_area``/``force_migrate``) — one dispatch per chunk and per area,
    with the destination region baked in statically; retained as the
    benchmark baseline and for callers that drive single areas directly;
  * the batched programs (``begin_areas``/``fused_copy``/``commit_areas``/
    ``force_areas``) — one dispatch covers every area the driver scheduled
    this tick (<=3 programs per tick).  Batch lengths are padded to geometric
    buckets by replicating lane 0 (idempotent duplicate updates), so the jit
    cache holds O(log n) entries however the adaptive splitter fragments the
    work, and the destination region is a traced operand rather than a
    static one;
  * the :func:`megastep` program — the whole tick (commit verdicts of the
    previous epoch, then begin/zero/force/copy/run phases) fused into ONE
    device program over the flat pool view, with the pool buffers donated
    and the dirty verdict produced on device.  The host packs every index
    operand into one int32 vector, sliced in the program at static offsets,
    so a tick makes one host-to-device transfer.  Every phase operand shares a
    single bucketed batch length, floored at the steady-state tick budget,
    and phases pad with *out-of-bounds sentinel* lanes (JAX drops
    out-of-bounds scatter updates) so one compiled variant serves every
    tick — including retry storms, whose fragmented batch lengths all round
    up to the same bucket.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.state import REGION, SLOT, LeapState, flat_pool_view
from repro.kernels import ops

from jax import shard_map
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------
# Epoch control
# --------------------------------------------------------------------------


@partial(jax.jit, donate_argnames=("state",))
def begin_area(state: LeapState, block_ids: jax.Array) -> LeapState:
    """Open a copy epoch: mark blocks in flight, clear their dirty bits."""
    in_flight = state.in_flight.at[block_ids].set(True)
    dirty = state.dirty.at[block_ids].set(False)
    return dataclasses.replace(state, in_flight=in_flight, dirty=dirty)


@partial(jax.jit, donate_argnames=("state",), static_argnames=("dst_region",))
def copy_chunk(
    state: LeapState,
    block_ids: jax.Array,
    dst_slots: jax.Array,
    dst_region: int,
) -> LeapState:
    """Physical copy of ``block_ids`` into ``(dst_region, dst_slots)``.

    Pure data movement — the table is untouched, so readers keep hitting the
    source location (non-atomic copy phase, exactly as in the paper).
    """
    loc = state.table[block_ids]
    src = state.pool[loc[:, REGION], loc[:, SLOT]]
    pool = state.pool.at[dst_region, dst_slots].set(src)
    return dataclasses.replace(state, pool=pool)


def _ppermute_local(src_region, dst_region, axis_name, pool, table, block_ids, dst_slots):
    # pool arrives as the local shard [R/axis, S, *blk]; with one region per
    # shard, index 0 is "my region".
    slots = table[block_ids, SLOT]
    buf = pool[0, slots]  # garbage on non-source shards; masked below
    recv = lax.ppermute(buf, axis_name, perm=[(src_region, dst_region)])
    me = lax.axis_index(axis_name)
    cur = pool[0, dst_slots]
    upd = jnp.where(me == dst_region, recv, cur)
    return pool.at[0, dst_slots].set(upd)


@partial(
    jax.jit,
    donate_argnames=("state",),
    static_argnames=("src_region", "dst_region", "axis_name", "mesh"),
)
def copy_chunk_ppermute(
    state: LeapState,
    block_ids: jax.Array,
    dst_slots: jax.Array,
    src_region: int,
    dst_region: int,
    axis_name: str,
    mesh: jax.sharding.Mesh,
) -> LeapState:
    """Point-to-point copy backend: one ``ppermute`` of exactly the area bytes."""
    fn = shard_map(
        partial(_ppermute_local, src_region, dst_region, axis_name),
        mesh=mesh,
        in_specs=(
            P(axis_name),  # pool: region dim sharded
            P(),  # table replicated
            P(),  # block ids replicated
            P(),  # dst slots replicated
        ),
        out_specs=P(axis_name),
    )
    pool = fn(state.pool, state.table, block_ids, dst_slots)
    return dataclasses.replace(state, pool=pool)


@partial(jax.jit, donate_argnames=("state",), static_argnames=("dst_region",))
def commit_area(
    state: LeapState,
    block_ids: jax.Array,
    dst_slots: jax.Array,
    dst_region: int,
) -> tuple[LeapState, jax.Array]:
    """The atomic remap: flip table entries of clean blocks; report dirty ones.

    Mirrors Fig. 3b of the paper: a block that became dirty during its copy
    epoch keeps its old mapping (the stale destination copy is discarded by
    the host, which frees the reserved slots and requeues a split area).
    """
    verdict = state.dirty[block_ids]  # True => copy invalidated
    proposed = jnp.stack(
        [jnp.full_like(dst_slots, dst_region), dst_slots], axis=1
    ).astype(state.table.dtype)
    new_entries = jnp.where(verdict[:, None], state.table[block_ids], proposed)
    table = state.table.at[block_ids].set(new_entries)
    in_flight = state.in_flight.at[block_ids].set(False)
    return dataclasses.replace(state, table=table, in_flight=in_flight), verdict


@partial(jax.jit, donate_argnames=("state",), static_argnames=("dst_region",))
def force_migrate(
    state: LeapState,
    block_ids: jax.Array,
    dst_slots: jax.Array,
    dst_region: int,
) -> LeapState:
    """Fused copy+remap (write-through escalation): no race window exists.

    Any write dispatched before this program is copied; any write dispatched
    after it goes through the already-flipped table.  Used by the driver after
    ``max_attempts`` dirty rejections to guarantee termination (beyond-paper).
    """
    loc = state.table[block_ids]
    src = state.pool[loc[:, REGION], loc[:, SLOT]]
    pool = state.pool.at[dst_region, dst_slots].set(src)
    entries = jnp.stack(
        [jnp.full_like(dst_slots, dst_region), dst_slots], axis=1
    ).astype(state.table.dtype)
    table = state.table.at[block_ids].set(entries)
    in_flight = state.in_flight.at[block_ids].set(False)
    dirty = state.dirty.at[block_ids].set(False)
    return dataclasses.replace(
        state, pool=pool, table=table, in_flight=in_flight, dirty=dirty
    )


# --------------------------------------------------------------------------
# Batched dispatch: one device program per tick phase, multi-area, bucketed.
#
# All batch operands are padded to a bucket length by REPLICATING LANE 0
# (adaptive.pad_to_bucket).  Duplicate lanes re-apply lane 0's update with
# identical values, so every program below is idempotent under padding; hosts
# simply ignore verdict lanes past the real batch length.  Destination
# regions are traced operands, so one compiled variant serves every region
# pairing at a given bucket size.
# --------------------------------------------------------------------------


@partial(jax.jit, donate_argnames=("state",))
def begin_areas(state: LeapState, block_ids: jax.Array) -> LeapState:
    """Open copy epochs for every area scheduled this tick (one dispatch)."""
    in_flight = state.in_flight.at[block_ids].set(True)
    dirty = state.dirty.at[block_ids].set(False)
    return dataclasses.replace(state, in_flight=in_flight, dirty=dirty)


@partial(jax.jit, donate_argnames=("state",), static_argnames=("impl",))
def fused_copy(
    state: LeapState,
    src_flat: jax.Array,
    dst_flat: jax.Array,
    impl: str | None = None,
) -> LeapState:
    """Physical copy of the whole tick's chunk plan in one program.

    ``src_flat``/``dst_flat`` are flat slot ids (``region * S + slot``,
    host-computed from the exact table mirror), so one compiled variant moves
    blocks between arbitrary region pairs.  The move itself is the
    ``leap_copy`` intra-pool kernel: on TPU one HBM-to-HBM DMA per block,
    addressed by scalar-prefetched slot ids; elsewhere the jnp oracle.
    """
    flat = flat_pool_view(state.pool)
    flat = ops.copy_blocks_impl(flat, src_flat, dst_flat, impl=impl)
    return dataclasses.replace(state, pool=flat.reshape(state.pool.shape))


@partial(jax.jit, donate_argnames=("state",), static_argnames=("run", "impl"))
def fused_copy_runs(
    state: LeapState,
    src_starts: jax.Array,
    dst_starts: jax.Array,
    run: int,
    impl: str | None = None,
) -> LeapState:
    """Physical copy of whole huge blocks: one contiguous-run move per block.

    ``src_starts``/``dst_starts`` are flat slot ids of each run's first slot
    (``region * S + start``; G-aligned and intra-region because the buddy
    allocator hands out aligned runs and G divides S).  A huge block moves as
    ONE area through ONE kernel step — a single DMA of ``run`` contiguous
    slots via ``copy_runs`` — instead of ``run`` per-slot gathers.
    """
    flat = flat_pool_view(state.pool)
    flat = ops.copy_runs_impl(flat, src_starts, dst_starts, run=run, impl=impl)
    return dataclasses.replace(state, pool=flat.reshape(state.pool.shape))


@partial(jax.jit, donate_argnames=("state",), static_argnames=("group",))
def commit_groups(
    state: LeapState,
    block_ids: jax.Array,
    dst_regions: jax.Array,
    dst_starts: jax.Array,
    group: int,
) -> tuple[LeapState, jax.Array]:
    """All-or-nothing remap of huge areas; one verdict lane per group.

    ``block_ids`` is ``[K * group]`` (K huge areas' members, group-major);
    ``dst_regions``/``dst_starts`` are ``[K]`` level-1 destinations.  A group
    is dirty iff ANY member was written during the copy epoch — a huge entry
    maps all its small blocks at once, so a partially-stale run cannot flip
    (mirroring a huge-page PTE: there is no per-4K remap under a 2M mapping).
    Padding replicates lane-0's whole GROUP, which keeps the program
    idempotent under duplicate lanes just like the per-block programs.
    """
    k = dst_starts.shape[0]
    members = block_ids.reshape(k, group)
    verdict = state.dirty[members].any(axis=1)  # True => whole run invalidated
    member_slots = dst_starts[:, None] + jnp.arange(group)[None, :]
    proposed = jnp.stack(
        [jnp.broadcast_to(dst_regions[:, None], (k, group)), member_slots], axis=-1
    ).astype(state.table.dtype)
    new_entries = jnp.where(
        verdict[:, None, None], state.table[members], proposed
    )
    table = state.table.at[members.reshape(-1)].set(new_entries.reshape(-1, 2))
    in_flight = state.in_flight.at[block_ids].set(False)
    return dataclasses.replace(state, table=table, in_flight=in_flight), verdict


@partial(jax.jit, donate_argnames=("state",))
def commit_areas(
    state: LeapState,
    block_ids: jax.Array,
    dst_regions: jax.Array,
    dst_slots: jax.Array,
) -> tuple[LeapState, jax.Array]:
    """Atomic remap of every commit-ready area, returning one packed verdict.

    Same per-block semantics as :func:`commit_area`; the host slices the
    packed verdict vector back into per-area views at known offsets.
    """
    verdict = state.dirty[block_ids]  # True => copy invalidated
    proposed = jnp.stack([dst_regions, dst_slots], axis=1).astype(state.table.dtype)
    new_entries = jnp.where(verdict[:, None], state.table[block_ids], proposed)
    table = state.table.at[block_ids].set(new_entries)
    in_flight = state.in_flight.at[block_ids].set(False)
    return dataclasses.replace(state, table=table, in_flight=in_flight), verdict


@partial(jax.jit, donate_argnames=("state",))
def force_areas(
    state: LeapState,
    block_ids: jax.Array,
    dst_regions: jax.Array,
    dst_slots: jax.Array,
) -> LeapState:
    """Batched write-through escalation: fused copy+flip for every forced area."""
    loc = state.table[block_ids]
    src = state.pool[loc[:, REGION], loc[:, SLOT]]
    pool = state.pool.at[dst_regions, dst_slots].set(src)
    entries = jnp.stack([dst_regions, dst_slots], axis=1).astype(state.table.dtype)
    table = state.table.at[block_ids].set(entries)
    in_flight = state.in_flight.at[block_ids].set(False)
    dirty = state.dirty.at[block_ids].set(False)
    return dataclasses.replace(
        state, pool=pool, table=table, in_flight=in_flight, dirty=dirty
    )


def _fused_ppermute_local(src_region, dst_region, axis_name, impl, pool, src_slots, dst_slots):
    # pool arrives as the local shard [1, S, *blk]; its flat view [S, *blk]
    # is the kernel layout, so the local HBM pack/unpack runs through the
    # leap_copy Pallas kernels on TPU (jnp oracle elsewhere).
    flat = flat_pool_view(pool)
    buf = ops.gather_blocks_impl(flat, src_slots, impl=impl)  # garbage off-src
    recv = lax.ppermute(buf, axis_name, perm=[(src_region, dst_region)])
    me = lax.axis_index(axis_name)
    cur = flat[dst_slots]
    upd = jnp.where(me == dst_region, recv, cur)  # non-dst shards: no-op write
    flat = ops.scatter_blocks_impl(flat, dst_slots, upd, impl=impl)
    return flat.reshape(pool.shape)


@partial(
    jax.jit,
    donate_argnames=("state",),
    static_argnames=("src_region", "dst_region", "axis_name", "mesh", "impl"),
)
def fused_copy_ppermute(
    state: LeapState,
    src_slots: jax.Array,
    dst_slots: jax.Array,
    src_region: int,
    dst_region: int,
    axis_name: str,
    mesh: jax.sharding.Mesh,
    impl: str | None = None,
) -> LeapState:
    """Batched point-to-point copy: all of one tick's (src, dst) traffic in a
    single ppermute of exactly the scheduled bytes (slot ids host-computed)."""
    fn = shard_map(
        partial(_fused_ppermute_local, src_region, dst_region, axis_name, impl),
        mesh=mesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=P(axis_name),
    )
    pool = fn(state.pool, src_slots, dst_slots)
    return dataclasses.replace(state, pool=pool)


@partial(jax.jit, donate_argnames=("state",), static_argnames=("dst_region",))
def zero_fill(state: LeapState, slots: jax.Array, dst_region: int) -> LeapState:
    """Zero destination slots before a copy lands (page-fault analogue).

    The move_pages()/autonuma-style schedulers migrate into *freshly
    allocated* memory, which the kernel zero-fills on first touch; issuing
    this as its own program keeps XLA from eliding the dead store, so the
    extra pass is actually paid (Fig. 2 accounting).
    """
    pool = state.pool.at[dst_region, slots].set(0)
    return dataclasses.replace(state, pool=pool)


# --------------------------------------------------------------------------
# Megastep dispatch: the whole tick in ONE device program (DESIGN.md §12).
#
# Padding discipline differs from the batched generation.  Every pure-jnp
# phase operand is padded to the shared bucket ``B`` with OUT-OF-BOUNDS
# SENTINELS (block ids -> N, regions -> R, slots -> S, flat ids -> R*S):
# JAX drops out-of-bounds scatter rows and clamps out-of-bounds gather
# indices, so a padded lane performs no state update and yields garbage
# verdict lanes the host already ignores (it slices verdicts by real
# offsets).  The two kernel phases (``copy_blocks_impl``/``copy_runs_impl``)
# must NOT see out-of-bounds ids — Pallas scalar-prefetched index maps are
# undefined there — so the host pads the copy plan by replicating lane 0
# (identical duplicate writes; destination slots are freshly allocated and
# disjoint from every source) or, when the tick copies nothing, with slot-0
# self-copies (value-identical no-ops).  Every phase, the huge-group ones
# (``grp_*``/``run_*``) included, is trace-time skippable: a zero-length
# segment of the packed operand compiles a variant without that phase, so
# small-only pools never pay for the huge tier.
# --------------------------------------------------------------------------


#: The megastep's index operands, in the order the host packs them into its
#: one int32 vector (and the program slices them back out).
MEGASTEP_SEGMENTS = (
    "commit_ids",
    "commit_regions",
    "commit_slots",
    "grp_members",
    "grp_regions",
    "grp_starts",
    "begin_ids",
    "zero_flat",
    "force_ids",
    "force_regions",
    "force_slots",
    "copy_src",
    "copy_dst",
    "run_src",
    "run_dst",
    "heat_ids",
)


def pack_operands(segments: dict[str, np.ndarray]) -> tuple[np.ndarray, tuple[int, ...]]:
    """Host side: write the phase vectors back to back into ONE int32 array.

    ``segments`` maps names of :data:`MEGASTEP_SEGMENTS` to int32 vectors; a
    missing name is an empty phase and takes zero lanes.  Returns the packed
    array and its static layout (one length per segment), which keys the
    megastep's compile cache exactly as the separate operand shapes did.
    """
    parts = [np.asarray(segments.get(name, ()), np.int32) for name in MEGASTEP_SEGMENTS]
    return np.concatenate(parts), tuple(len(p) for p in parts)


def unpack_operands(packed: jax.Array, layout: tuple[int, ...]) -> tuple[jax.Array, ...]:
    """Program side: slice the packed vector back into its segments, in
    :data:`MEGASTEP_SEGMENTS` order, at static (trace-time) offsets."""
    ends = np.cumsum(layout).tolist()
    return tuple(packed[e - n : e] for n, e in zip(layout, ends))


@partial(
    jax.jit,
    donate_argnames=("state", "heat"),
    static_argnames=("layout", "group", "impl", "heat_decay"),
)
def megastep(
    state: LeapState,
    packed: jax.Array,
    heat: jax.Array | None = None,
    heat_w: jax.Array | None = None,
    *,
    layout: tuple[int, ...],
    group: int = 1,
    impl: str | None = None,
    heat_decay: float = 1.0,
) -> tuple[LeapState, jax.Array, jax.Array, jax.Array | None]:
    """One tick = one dispatch: commit -> begin -> zero -> force -> copy -> heat.

    Fuses the previous epoch's commit verdicts with this tick's begin/zero/
    force/copy phases into a single XLA program over the donated pool
    buffers.  Phase order matches the batched generation's cross-program
    order exactly (commit verdicts are read from the *input* ``dirty`` before
    begin/force clear their — disjoint — id sets; the force phase reads the
    post-commit table and the post-zero pool).  The verdict vectors stay on
    device: the host wraps them in :class:`~repro.core.queues.CommitBatch`
    futures and harvests them asynchronously, off the tick critical path.

    Every index operand arrives in ``packed`` (one host-to-device transfer),
    cut into the segments of :data:`MEGASTEP_SEGMENTS` by the static
    ``layout``; a zero-length segment is an absent phase.

    The trailing heat phase (closed-loop tiering, DESIGN.md §13) folds the
    tick's access samples into the donated per-block heat plane — it touches
    no pool/table state, so its ordering is free, and its trace-time guard
    compiles the phase away when the tick has no samples.  Without samples
    the host passes no heat buffers at all (``None``) and gets ``heat``
    back untouched.
    """
    (
        commit_ids, commit_regions, commit_slots,
        grp_members, grp_regions, grp_starts,
        begin_ids, zero_flat,
        force_ids, force_regions, force_slots,
        copy_src, copy_dst, run_src, run_dst,
        heat_ids,
    ) = unpack_operands(packed, layout)
    table, dirty, in_flight = state.table, state.dirty, state.in_flight
    s_per = state.pool.shape[1]

    # -- commit (previous epoch): small blocks, then all-or-nothing groups --
    if commit_ids.shape[0]:
        verdict_small = dirty[commit_ids]  # True => copy invalidated
        proposed = jnp.stack([commit_regions, commit_slots], axis=1).astype(table.dtype)
        new_entries = jnp.where(verdict_small[:, None], table[commit_ids], proposed)
        table = table.at[commit_ids].set(new_entries)
        in_flight = in_flight.at[commit_ids].set(False)
    else:
        verdict_small = jnp.zeros((0,), dtype=jnp.bool_)

    if grp_starts.shape[0]:
        k = grp_starts.shape[0]
        members = grp_members.reshape(k, group)
        verdict_groups = dirty[members].any(axis=1)
        member_slots = grp_starts[:, None] + jnp.arange(group)[None, :]
        gprop = jnp.stack(
            [jnp.broadcast_to(grp_regions[:, None], (k, group)), member_slots],
            axis=-1,
        ).astype(table.dtype)
        gnew = jnp.where(verdict_groups[:, None, None], table[members], gprop)
        table = table.at[members.reshape(-1)].set(gnew.reshape(-1, 2))
        in_flight = in_flight.at[grp_members].set(False)
    else:
        verdict_groups = jnp.zeros((0,), dtype=jnp.bool_)

    # -- begin: open this tick's copy epochs --------------------------------
    if begin_ids.shape[0]:
        in_flight = in_flight.at[begin_ids].set(True)
        dirty = dirty.at[begin_ids].set(False)

    # -- zero freshly allocated destinations (page-fault analogue) ----------
    flat = flat_pool_view(state.pool)
    if zero_flat.shape[0]:
        flat = flat.at[zero_flat].set(0)

    # -- force: fused copy+flip escalations (reads the post-commit table) ---
    if force_ids.shape[0]:
        loc = table[force_ids]
        force_src = loc[:, REGION] * s_per + loc[:, SLOT]
        force_dst = force_regions * s_per + force_slots
        flat = flat.at[force_dst].set(flat[force_src])
        fentries = jnp.stack([force_regions, force_slots], axis=1).astype(table.dtype)
        table = table.at[force_ids].set(fentries)
        in_flight = in_flight.at[force_ids].set(False)
        dirty = dirty.at[force_ids].set(False)

    # -- physical copy: the leap_copy kernel over the flat pool view --------
    if copy_src.shape[0]:
        flat = ops.copy_blocks_impl(flat, copy_src, copy_dst, impl=impl)
    if run_src.shape[0]:
        flat = ops.copy_runs_impl(flat, run_src, run_dst, run=group, impl=impl)

    # -- access heat: decay + accumulate this tick's samples (tiering) ------
    if heat_ids.shape[0]:
        heat = ops.heat_scan_impl(heat, heat_ids, heat_w, heat_decay, impl=impl)

    state = dataclasses.replace(
        state,
        pool=flat.reshape(state.pool.shape),
        table=table,
        dirty=dirty,
        in_flight=in_flight,
    )
    return state, verdict_small, verdict_groups, heat


@partial(jax.jit, donate_argnames=("heat",), static_argnames=("decay", "impl"))
def heat_update(
    heat: jax.Array,
    ids: jax.Array,
    w: jax.Array,
    decay: float,
    impl: str | None = None,
) -> jax.Array:
    """Standalone access-heat pass for the batched/legacy dispatch
    generations (under megastep the same update rides the tick's single
    program as its trailing phase)."""
    return ops.heat_scan_impl(heat, ids, w, decay, impl=impl)


# --------------------------------------------------------------------------
# Compile-cache introspection (control-path cost accounting)
# --------------------------------------------------------------------------

_PROGRAMS = {
    "megastep": megastep,
    "heat_update": heat_update,
    "zero_fill": zero_fill,
    "begin_area": begin_area,
    "copy_chunk": copy_chunk,
    "copy_chunk_ppermute": copy_chunk_ppermute,
    "commit_area": commit_area,
    "force_migrate": force_migrate,
    "begin_areas": begin_areas,
    "fused_copy": fused_copy,
    "fused_copy_runs": fused_copy_runs,
    "commit_areas": commit_areas,
    "commit_groups": commit_groups,
    "force_areas": force_areas,
    "fused_copy_ppermute": fused_copy_ppermute,
}


def program_cache_sizes() -> dict[str, int]:
    """Compiled-variant count per migration program (process-wide).

    Every distinct operand shape that ever hit a program is one cache entry,
    i.e. one XLA trace+compile; the driver differences this to report
    ``MigrationStats.jit_cache_misses``.
    """
    out = {}
    for name, fn in _PROGRAMS.items():
        try:
            out[name] = fn._cache_size()
        except AttributeError:  # pragma: no cover - older/newer jax
            out[name] = 0
    return out


def program_cache_size() -> int:
    """Total compiled migration-program variants (process-wide)."""
    return sum(program_cache_sizes().values())
