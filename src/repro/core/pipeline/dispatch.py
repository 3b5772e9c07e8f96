"""Dispatch stage: epoch opens and single-dispatch tick assembly.

Owns the per-tick scheduling loop (``run_tick``): advances copies of open
epochs, opens new epochs off the priority queue, and hands the tick's work
to the device in the one dispatch generation its copy backend
(``LeapConfig.backend``) runs:

  * ``xla`` (default): the megastep — the entire tick is ONE device program
    (:func:`repro.core.migrator.megastep`): the previous epoch's commits,
    then begin/zero/force/copy, over the donated flat pool view.  The host
    side of this stage is pure *plan assembly*: it gathers numpy id vectors,
    pads them with out-of-bounds sentinels to one shared bucket, and crosses
    the host/device boundary exactly once per tick.  The dirty verdict never
    crosses back here — it stays device-resident inside the
    :class:`~repro.core.queues.CommitBatch` future, harvested by the verdict
    stage off the tick critical path (DESIGN.md §12).
  * ``ppermute``: the batched programs — one fused program per tick phase
    (``begin_areas``, zero fill, ``force_areas``, one ``fused_copy_ppermute``
    per region pair, ``commit_areas``), padded to geometric buckets so the
    jit cache stays O(log n) (DESIGN.md §3).  Its copies are shard_map
    programs with static endpoints, which cannot fuse into the megastep.
    The driver refuses a two-tier pool on this backend, so no area here is
    huge.

Budget decisions (how much a link grants, congestion deferral) come from
the budget stage; dirty verdicts are harvested later by the verdict stage.
Tier transitions (promotion/adoption) live here too: a promotion is just a
compaction dispatch through the atomic force program.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core import migrator
from repro.core.adaptive import Area, bucket_size, demote_area, pad_to_bucket
from repro.core.pipeline.accounting import AccountingStage
from repro.core.pipeline.budget import BudgetStage, TickBudget
from repro.core.pipeline.context import PipelineContext
from repro.core.queues import CommitBatch
from repro.core.state import REGION, SLOT


class DispatchStage:
    def __init__(
        self,
        ctx: PipelineContext,
        budget: BudgetStage,
        accounting: AccountingStage,
    ):
        self.ctx = ctx
        self.budget = budget
        self.accounting = accounting
        # Dispatch generation, fixed by the copy backend: batched programs
        # for ppermute, the megastep for xla.
        self._batched = ctx.cfg.backend == "ppermute"
        # Source slots freed by this tick's forced escalations, quarantined
        # until the tick's device batches are dispatched (see run_tick).
        self._freed: list[np.ndarray] = []
        # Megastep mode: commit-ready areas staged by commit_ready() for the
        # tick's single dispatch (they stay in ctx.active until it fires).
        self._staged_small: list[Area] = []
        self._staged_huge: list[Area] = []
        if not self._batched and ctx.cfg.warm_dispatch:
            self._warm_megastep()

    # -- the per-tick scheduling loop --------------------------------------

    def commit_ready(self) -> None:
        """Dispatch commits for areas whose copy completed in an earlier
        tick.  Deferring the commit by one tick keeps the copy->remap window
        open across at least one application step, faithfully reproducing
        the paper's race (its footnote 1: a write can land after the copy
        but before the remap)."""
        ctx = self.ctx
        with ctx.telemetry.stage("dispatch.commit_ready"):
            ready = [a for a in ctx.active if a.copied == len(a)]
            if self._batched:
                self._dispatch_commit_batch(ready)  # small areas only
            else:
                # No dispatch here: the commits ride this tick's megastep.
                # Ready areas stay in ctx.active until it fires, so emptiness
                # checks (huge stall detection, done()) see them as live.
                self._staged_small = [a for a in ready if not a.huge]
                self._staged_huge = [a for a in ready if a.huge]

    def run_tick(self, tb: TickBudget) -> None:
        """Spend the tick budget: advance open epochs, open new ones."""
        with self.ctx.telemetry.stage("dispatch.run_tick"):
            self._run_tick(tb)

    def _run_tick(self, tb: TickBudget) -> None:
        ctx = self.ctx
        with ctx.telemetry.stage("dispatch.plan"):
            opened, forced, zeros, plan, run_plan = self._plan(tb)
        if not self._batched:
            # The whole tick — staged commits, begins, zeros, forces, copies —
            # crosses the host/device boundary as ONE program.  Phase order
            # inside the program matches the batched generation's cross-
            # program order; the quarantine note below applies identically.
            with ctx.telemetry.stage("dispatch.device"):
                self._dispatch_megastep(opened, zeros, forced, plan, run_plan)
        else:
            # Device order matters: begin before copy (epoch flags gate dirty
            # tracking), force before copy (a forced block's freed source slot
            # may be reallocated as a copy destination next tick), zero-fill
            # before force AND copy (a fresh area's zero pass must land before
            # its own force/copy overwrites the same slots with the payload).
            # This ordering is only sound because slots freed by this tick's
            # forces are QUARANTINED until the flush below: no open in this
            # tick can hand a force's still-unread source slot to another
            # area as a zero/force/copy destination.
            with ctx.telemetry.stage("dispatch.device"):
                self._dispatch_begin_batch(opened)
                self._dispatch_zero_batch(zeros)
                self._dispatch_force_batch(forced)
                self._dispatch_copy_batch(plan)
            # The tick's access-heat samples flush as their own program (the
            # megastep folds them into its single dispatch).
            self._flush_heat()
        # End of tick: every program that reads a forced area's old source
        # slots is dispatched; release them for the next tick's allocations.
        for old in self._freed:
            for r in np.unique(old[:, REGION]):
                ctx.free[r].put(old[old[:, REGION] == r, SLOT])
        self._freed = []

    def _plan(self, tb: TickBudget):
        """The tick's scheduling loop: grant copies to open epochs and open
        new ones off the queue within ``tb``; requeue congested and blocked
        areas.  Returns ``(opened, forced, zeros, plan, run_plan)``, which the
        tick dispatches after planning."""
        ctx = self.ctx
        skipped: set[int] = set()  # active areas deferred this tick (link dry)
        opened: list[Area] = []  # epochs opened this tick (begin phase)
        forced: list[Area] = []  # escalations this tick (force phase)
        blocked: list[Area] = []  # areas whose destination is out of slots
        congested: list[Area] = []  # queued areas whose link budget ran dry
        zeros: list[Area] = []  # fresh-alloc epochs (zero-fill phase)
        plan: list[tuple[Area, np.ndarray, np.ndarray]] = []  # copy chunks
        run_plan: list[Area] = []  # huge areas copied as whole contiguous runs
        while tb.blocks > 0:
            area = self._next_copyable(skipped)
            if area is not None:
                if area.huge:
                    need = len(area) - area.copied
                    if self.budget.grant_huge(tb, area, need) == 0:
                        skipped.add(id(area))
                        continue
                    run_plan.append(area)
                    tb.blocks -= need
                    area.copied = len(area)
                    continue
                want = min(len(area) - area.copied, tb.blocks)
                n = self.budget.grant_copy(tb, area, want)
                if n == 0:
                    skipped.add(id(area))
                    continue
                ids = area.block_ids[area.copied : area.copied + n]
                slots = area.dst_slots[area.copied : area.copied + n]
                plan.append((area, ids, slots))
                area.copied += n
                tb.blocks -= n
                continue
            if ctx.queue:
                area = ctx.queue.popleft()
                if not self.budget.may_open(tb, area):
                    congested.append(area)
                    continue
                if not self._open_epoch(area, opened, forced, zeros):
                    # Destination out of slots.  A relayed first hop falls
                    # back to the direct link (stalling behind a full relay
                    # region would trade congestion for a livelock); anything
                    # else is set aside (it goes back to the head of its
                    # priority class below) while we keep trying lower-
                    # priority areas: one of THEIR commits may be what frees
                    # the blocked destination — breaking here would let a
                    # high-priority request to a full region starve the very
                    # migrations that could unblock it (livelock).
                    if area.final_dst >= 0 and area.final_dst != area.dst_region:
                        area.dst_region = area.final_dst
                        area.final_dst = -1
                        ctx.queue.appendleft(area)
                    else:
                        blocked.append(area)
                    continue
                if ctx.active and ctx.active[-1] is area:
                    # Charge the per-link epoch-open budget only for a real
                    # open: the out-of-slots halving path requeues without
                    # opening, and forced escalations are budget-exempt.
                    self.budget.charge_open(tb, area)
                continue
            break
        for area in reversed(congested):
            ctx.queue.appendleft(area)
        for area in reversed(blocked):
            ctx.queue.appendleft(area)
        return opened, forced, zeros, plan, run_plan

    def quarantined_slots(self) -> np.ndarray:
        """Copy of the current force-freed slot quarantine: ``(region, slot)``
        rows held back until this tick's device batches dispatch.  Empty
        between ticks; exposed (read-only) for pipeline introspection."""
        if not self._freed:
            return np.zeros((0, 2), dtype=np.int32)
        return np.concatenate([f.copy() for f in self._freed]).astype(np.int32)

    def _next_copyable(self, skipped: set | None = None) -> Area | None:
        for a in self.ctx.active:
            if a.copied < len(a) and (skipped is None or id(a) not in skipped):
                return a
        return None

    # -- epoch open --------------------------------------------------------

    def _open_epoch(
        self,
        area: Area,
        opened: list[Area],
        forced: list[Area],
        zeros: list[Area],
    ) -> bool:
        ctx = self.ctx
        cfg = ctx.cfg
        if area.huge:
            return self._open_epoch_huge(area, opened)
        if (
            area.attempts >= cfg.max_attempts_before_force
            and area.final_dst >= 0
            and area.final_dst != area.dst_region
        ):
            # Escalation overrides routing: the atomic force program has no
            # race window for the relay to shrink, so the second copy would
            # be pure waste — and a force to the relay could share a batched
            # force program with its own re-queued second hop (duplicate
            # scatter lanes, undefined table order).  Force straight to the
            # final destination instead.
            area.dst_region = area.final_dst
            area.final_dst = -1
        slots = ctx.alloc(area.dst_region, len(area))
        if slots is None:
            # Not enough pooled slots for the whole area right now.  If the
            # destination has *some* space, split and make progress with the
            # smaller half; otherwise wait for commits to free slots.
            if len(area) > 1 and len(ctx.free[area.dst_region]) > 0:
                mid = len(area) // 2
                a = Area(
                    area.block_ids[:mid],
                    area.src_region,
                    area.dst_region,
                    area.attempts,
                    request_id=area.request_id,
                    priority=area.priority,
                    final_dst=area.final_dst,
                    fresh_alloc=area.fresh_alloc,
                )
                b = Area(
                    area.block_ids[mid:],
                    area.src_region,
                    area.dst_region,
                    area.attempts,
                    request_id=area.request_id,
                    priority=area.priority,
                    final_dst=area.final_dst,
                    fresh_alloc=area.fresh_alloc,
                )
                ctx.queue.appendleft(b)
                ctx.queue.appendleft(a)
                return True
            return False  # caller re-queues (tick sets it aside, tries others)
        area.dst_slots = slots
        area.copied = 0
        if area.fresh_alloc:
            # Fresh-destination policies (move_pages()/autonuma analogues)
            # pay the kernel's zero-fill pass before their copy/force lands:
            # one zero phase per tick, sequenced before the force/copy phases.
            zeros.append(area)
        if area.attempts >= cfg.max_attempts_before_force:
            # Write-through escalation: fused copy+flip, cannot be dirtied.
            # Deliberately exempt from the per-link budgets (escalation must
            # terminate), but its traffic is still accounted to the link.
            # (Never a relay hop here — escalation converted it to direct
            # above — so the per-block count is exact, not doubled.)
            ctx.count("bytes_copied", len(area) * ctx.pool_cfg.block_bytes)
            ctx.count("blocks_forced", len(area), rid=area.request_id)
            self.budget.charge_link(area.src_region, area.dst_region, len(area))
            ctx.telemetry.request_phase(
                area.request_id,
                "EPOCH_OPEN",
                n=len(area),
                attempts=area.attempts,
                forced=True,
            )
            forced.append(area)  # device dispatch at end of tick
            self._finalize_success(area)
            return True
        ctx.telemetry.request_phase(
            area.request_id, "EPOCH_OPEN", n=len(area), attempts=area.attempts
        )
        opened.append(area)  # begin dispatched at end of tick, before copies
        ctx.active.append(area)
        return True

    def _open_epoch_huge(self, area: Area, opened: list[Area]) -> bool:
        """Open a huge area's epoch: reserve one aligned run at the destination.

        If the destination has >= G free slots but no contiguous run
        (fragmentation), or the pipeline is empty and can never free one, the
        huge block demotes and retries at small granularity — the second half
        of the paper's §4.2 rule.
        """
        ctx = self.ctx
        g = int(area.block_ids[0]) // ctx.pool_cfg.huge_factor
        start = ctx.free[area.dst_region].take_run()
        if start is None:
            fragmented = len(ctx.free[area.dst_region]) >= ctx.pool_cfg.huge_factor
            stalled = not ctx.active and not ctx.pending
            if fragmented or stalled:
                ctx.demote_group(g)
                ctx.queue.extend(
                    demote_area(area, ctx.cfg.reduction_factor, ctx.cfg.min_area_blocks)
                )
                return True
            return False  # caller re-queues (tick sets it aside, tries others)
        area.dst_slots = start + np.arange(ctx.pool_cfg.huge_factor, dtype=np.int32)
        area.copied = 0
        ctx.telemetry.request_phase(
            area.request_id, "EPOCH_OPEN", n=len(area), attempts=area.attempts, huge=True
        )
        opened.append(area)  # members share the tick's begin phase
        ctx.active.append(area)
        return True

    def _finalize_success(self, area: Area) -> None:
        # Force path: all blocks flipped on device; mirror and free sources.
        # Never a relay hop (escalation forces direct to the final
        # destination), so the credit is always terminal.  The force itself
        # runs on device at end of tick, so the freed source slots are
        # quarantined (self._freed) instead of released: handing one out to
        # a later open this tick would let that area's zero/force/copy write
        # the slot before this force has read it.
        ctx = self.ctx
        ids = area.block_ids
        self._freed.append(ctx.table[ids].copy())
        ctx.table[ids, REGION] = area.dst_region
        ctx.table[ids, SLOT] = area.dst_slots
        ctx.migrating[ids] = False
        ctx.note_migrated(ids)
        self.accounting.credit(area, forced=len(area))

    # -- access-heat plane (closed-loop tiering) ----------------------------

    def _pop_heat(self) -> tuple[np.ndarray, np.ndarray]:
        """Pop and flatten the tick's pending heat samples (ids, weights)."""
        ctx = self.ctx
        if ctx.heat is None or not ctx.heat_pending:
            return np.zeros(0, np.int32), np.zeros(0, np.float32)
        samples, ctx.heat_pending = ctx.heat_pending, []
        ids = np.concatenate([s for s, _ in samples]).astype(np.int32, copy=False)
        w = np.concatenate(
            [np.full(len(s), wt, np.float32) for s, wt in samples]
        )
        return ids, w

    def _flush_heat(self) -> None:
        """Batched: fold the tick's heat samples as their own program."""
        ctx = self.ctx
        ids, w = self._pop_heat()
        n = len(ids)
        if not n:
            return
        bucket = self._megastep_bucket(n)
        ids = self._pad_sentinel(ids, bucket, int(ctx.heat.shape[0]))
        hw = np.zeros(bucket, np.float32)
        hw[:n] = w
        ctx.heat = migrator.heat_update(
            ctx.heat,
            jax.numpy.asarray(ids),
            jax.numpy.asarray(hw),
            ctx.cfg.tier_heat_decay,
        )
        ctx.count("dispatches", 1, program="heat_update")

    # -- megastep dispatch (one program per tick) ---------------------------

    def _warm_megastep(self) -> None:
        """Ahead-of-time compile the steady-state megastep variants.

        The budget-floored shared bucket fixes every steady-state operand
        shape before any workload runs, so the drain-loop signatures —
        ``(begin, copy)`` on opening ticks, ``(commit, begin, copy)`` at
        steady state, ``(commit,)`` on the tail — can compile at pool-attach
        time.  Each warm call is a semantic no-op: per-block operands are
        all OUT-OF-BOUNDS sentinels (scatters dropped, gather results
        unread) and copy lanes are slot-0 self-copies.  The operands go
        through the same packing as a real tick's, so the warmed variants
        are exactly those the steady state calls.  Runs inside driver
        construction, before the jit-miss baseline snapshot, so warmed
        compiles never count against ``MigrationStats.jit_cache_misses``.
        """
        for segments, heat_w in self._warm_operands().values():
            self._megastep(*migrator.pack_operands(segments), heat_w)

    def _warm_operands(self) -> dict[tuple[str, ...], tuple[dict, np.ndarray | None]]:
        """Warm signature (phases present) -> (no-op segments, heat weights)."""
        ctx = self.ctx
        pc = ctx.pool_cfg
        G = pc.huge_factor
        B = self._megastep_bucket(0)
        n_blocks = len(ctx.table)
        sent = np.full(B, n_blocks, np.int32)  # OOB block ids: all no-op
        self_copy = np.zeros(B, np.int32)
        gb = bucket_size(max(1, ctx.cfg.budget_blocks_per_tick // G), ctx.cfg.bucket_growth)
        r_self = np.zeros(gb, np.int32)
        phases = {
            "commit": {
                "commit_ids": sent,
                "commit_regions": np.full(B, pc.n_regions, np.int32),
                "commit_slots": np.full(B, pc.slots_per_region, np.int32),
            },
            "begin": {"begin_ids": sent},
            "copy": {"copy_src": self_copy, "copy_dst": self_copy},
            "groups": {
                "grp_members": np.full(gb * G, n_blocks, np.int32),  # OOB member ids
                "grp_regions": np.full(gb, pc.n_regions, np.int32),
                "grp_starts": np.full(gb, pc.slots_per_region, np.int32),
            },
            "runs": {"run_src": r_self, "run_dst": r_self},
        }
        signatures = [
            ("commit",),
            ("begin", "copy"),
            ("commit", "begin", "copy"),
        ]
        if ctx.heat is not None:
            # Tiering on: a read workload rides the heat phase on every
            # nonempty tick, including read-only ticks (heat alone).  OOB
            # heat ids: no lane matches, and heat is all zeros at
            # construction, so the warmed decay pass is a value no-op too.
            phases["heat"] = {"heat_ids": np.full(B, int(ctx.heat.shape[0]), np.int32)}
            signatures += [
                ("heat",),
                ("commit", "heat"),
                ("begin", "copy", "heat"),
                ("commit", "begin", "copy", "heat"),
            ]
        if G > 1:
            # Two-tier pool: the run-copy / group-commit tick shapes, at
            # their own floored bucket (budget / G groups per tick).
            signatures += [
                ("groups",),
                ("begin", "runs"),
                ("groups", "begin", "runs"),
                ("groups", "begin", "copy"),
            ]
        out = {}
        for sig in signatures:
            segments = {k: v for phase in sig for k, v in phases[phase].items()}
            out[sig] = (segments, np.zeros(B, np.float32) if "heat" in sig else None)
        return out

    def _megastep(
        self, packed: np.ndarray, layout: tuple[int, ...], heat_w: np.ndarray | None
    ) -> tuple[jax.Array, jax.Array]:
        """Fire the megastep on packed operands; returns the verdict futures.

        The packed numpy vector goes straight into the jitted call, which
        transfers it once.  Without a heat phase no heat buffer is passed
        (the donated plane stays where it is); with one, the plane is donated
        and the float32 weights are the tick's second transfer.
        """
        ctx = self.ctx
        heat_in = None if heat_w is None else ctx.heat
        ctx.state, verdict_small, verdict_groups, heat_out = migrator.megastep(
            ctx.state,
            packed,
            heat_in,
            heat_w,
            layout=layout,
            group=ctx.pool_cfg.huge_factor,
            heat_decay=ctx.cfg.tier_heat_decay,
        )
        if heat_w is not None:
            ctx.heat = heat_out
        return verdict_small, verdict_groups

    def _megastep_bucket(self, *lengths: int) -> int:
        """Shared bucket for every per-block megastep operand.

        Floored at the steady-state tick budget so a drain's every tick —
        and every retry-storm tick, whose fragmented batches are no longer
        than the budget — rounds up to the SAME bucket: after warmup one
        compiled variant serves the whole run.
        """
        ctx = self.ctx
        floor = max(1, min(ctx.cfg.budget_blocks_per_tick, len(ctx.table)))
        return bucket_size(max(max(lengths), floor), ctx.cfg.bucket_growth)

    @staticmethod
    def _pad_sentinel(arr: np.ndarray, bucket: int, sentinel: int) -> np.ndarray:
        out = np.full(bucket, sentinel, dtype=np.int32)
        out[: len(arr)] = arr
        return out

    def _dispatch_megastep(
        self,
        opened: list[Area],
        zeros: list[Area],
        forced: list[Area],
        plan: list[tuple[Area, np.ndarray, np.ndarray]],
        run_plan: list[Area],
    ) -> None:
        """Assemble and fire the tick's single device program.

        Every index vector is packed into ONE int32 array
        (:func:`~repro.core.migrator.pack_operands`), sent in one transfer
        and sliced inside the program at static offsets.  An EMPTY phase
        takes zero lanes and compiles away entirely (trace-time
        ``if x.shape[0]`` guards in the program), so a quiet drain never
        pays padded force-lane payload gathers and the commit-only final
        tick compiles a lean tail variant.  A NONEMPTY
        phase pads to the shared budget-floored bucket with OUT-OF-BOUNDS
        sentinels (block ids -> N, regions -> R, slots -> S, flat ids ->
        R*S): JAX drops out-of-bounds scatter rows and clamps out-of-bounds
        gather indices, so a padded lane performs no state update and its
        garbage verdict lane is never read (the host slices verdicts by real
        offsets).  One bucket per phase keeps the variant space to
        phases-present x B rather than a cross product of lengths.  The
        kernel copy operands instead replicate lane 0 — Pallas
        scalar-prefetched index maps must stay in bounds — so padded copy
        lanes re-copy a real lane (idempotent).  An idle tick — nothing
        staged, nothing scheduled — dispatches nothing at all.
        """
        ctx = self.ctx
        small, huge = self._staged_small, self._staged_huge
        self._staged_small, self._staged_huge = [], []
        with ctx.telemetry.stage("dispatch.operands"):
            heat_ids, heat_w = self._pop_heat()
            if not (
                small
                or huge
                or opened
                or zeros
                or forced
                or plan
                or run_plan
                or len(heat_ids)
            ):
                return
            pc = ctx.pool_cfg
            S = pc.slots_per_region
            n_blocks = len(ctx.table)
            G = pc.huge_factor

            def cat(parts: list[np.ndarray]) -> np.ndarray:
                if not parts:
                    return np.zeros(0, np.int32)
                return np.concatenate(parts).astype(np.int32, copy=False)

            commit_ids = cat([a.block_ids for a in small])
            commit_regions = cat([np.full(len(a), a.dst_region, np.int32) for a in small])
            commit_slots = cat([a.dst_slots for a in small])
            offsets = np.cumsum([0] + [len(a) for a in small])
            begin_ids = cat([a.block_ids for a in opened])
            zero_flat = cat([a.dst_region * S + a.dst_slots for a in zeros])
            force_ids = cat([a.block_ids for a in forced])
            force_regions = cat([np.full(len(a), a.dst_region, np.int32) for a in forced])
            force_slots = cat([a.dst_slots for a in forced])
            # Copy plan: flat slot ids from the exact host mirror — table entries
            # of in-flight blocks cannot change until their commit, which this
            # driver issues (and this tick's commits target disjoint blocks).
            copy_ids = cat([ids for _, ids, _ in plan])
            copy_regions = cat(
                [np.full(len(c), a.dst_region, np.int32) for a, c, _ in plan]
            )
            copy_slots = cat([s for _, _, s in plan])
            copy_src = (ctx.table[copy_ids, REGION] * S + ctx.table[copy_ids, SLOT]).astype(
                np.int32
            )
            copy_dst = (copy_regions * S + copy_slots).astype(np.int32)
            if len(copy_ids):
                ctx.count("bytes_copied", len(copy_ids) * pc.block_bytes)

            B = self._megastep_bucket(
                len(commit_ids),
                len(begin_ids),
                len(zero_flat),
                len(force_ids),
                len(copy_src),
            )
            pad = self._pad_sentinel
            if len(commit_ids):
                commit_ids = pad(commit_ids, B, n_blocks)
                commit_regions = pad(commit_regions, B, pc.n_regions)
                commit_slots = pad(commit_slots, B, S)
            if len(begin_ids):
                begin_ids = pad(begin_ids, B, n_blocks)
            if len(zero_flat):
                zero_flat = pad(zero_flat, B, pc.n_regions * S)
            if len(force_ids):
                force_ids = pad(force_ids, B, n_blocks)
                force_regions = pad(force_regions, B, pc.n_regions)
                force_slots = pad(force_slots, B, S)
            if len(copy_src):
                copy_src, copy_dst = pad_to_bucket(B, copy_src, copy_dst)

            # Huge-tier buckets are floored at the tick's huge capacity
            # (budget / G groups), mirroring the per-block floor: every
            # group-commit and run-copy tick shares one compiled variant.
            huge_floor = max(1, ctx.cfg.budget_blocks_per_tick // G)
            k = len(huge)
            if k:
                kb = bucket_size(max(k, huge_floor), ctx.cfg.bucket_growth)
                members = np.concatenate([a.block_ids for a in huge]).reshape(k, G)
                members = np.concatenate(
                    [members, np.repeat(members[:1], kb - k, axis=0)]
                )
                grp_members = members.reshape(-1).astype(np.int32)
                grp_regions, grp_starts = pad_to_bucket(
                    kb,
                    np.asarray([a.dst_region for a in huge], np.int32),
                    np.asarray([a.dst_slots[0] for a in huge], np.int32),
                )
            else:
                grp_members = grp_regions = grp_starts = np.zeros(0, np.int32)
            if run_plan:
                firsts = np.asarray([a.block_ids[0] for a in run_plan])
                run_src = (
                    ctx.table[firsts, REGION] * S + ctx.table[firsts, SLOT]
                ).astype(np.int32)
                run_dst = np.asarray(
                    [a.dst_region * S + a.dst_slots[0] for a in run_plan], np.int32
                )
                rb = bucket_size(max(len(run_plan), huge_floor), ctx.cfg.bucket_growth)
                run_src, run_dst = pad_to_bucket(rb, run_src, run_dst)
                nbytes = len(run_plan) * G * pc.block_bytes
                ctx.count("bytes_copied", nbytes)
                ctx.count("bytes_copied_huge", nbytes)
            else:
                run_src = run_dst = np.zeros(0, np.int32)

            # Heat samples pad at their OWN bucket (sentinel = heat-plane length,
            # which both paths drop) so a read-heavy tick never inflates the
            # shared per-block bucket — the heat batch length tracks the access
            # rate, not the migration budget.
            n_heat = len(heat_ids)
            hw = None
            if n_heat:
                hb = self._megastep_bucket(n_heat)
                heat_ids = pad(heat_ids, hb, int(ctx.heat.shape[0]))
                hw = np.zeros(hb, np.float32)
                hw[:n_heat] = heat_w
            packed, layout = migrator.pack_operands(
                {
                    "commit_ids": commit_ids,
                    "commit_regions": commit_regions,
                    "commit_slots": commit_slots,
                    "grp_members": grp_members,
                    "grp_regions": grp_regions,
                    "grp_starts": grp_starts,
                    "begin_ids": begin_ids,
                    "zero_flat": zero_flat,
                    "force_ids": force_ids,
                    "force_regions": force_regions,
                    "force_slots": force_slots,
                    "copy_src": copy_src,
                    "copy_dst": copy_dst,
                    "run_src": run_src,
                    "run_dst": run_dst,
                    "heat_ids": heat_ids,
                }
            )
        with ctx.telemetry.stage("dispatch.enqueue"):
            verdict_small, verdict_groups = self._megastep(packed, layout, hw)
        ctx.count("dispatches", 1, program="megastep")
        ctx.count("h2d_transfers", 1 if hw is None else 2)
        for a in small + huge:
            ctx.active.remove(a)
        if small:
            ctx.pending.append(CommitBatch(small, offsets, verdict_small))
        if huge:
            ctx.pending.append(CommitBatch(huge, np.arange(k + 1), verdict_groups))

    # -- batched dispatch (ppermute backend) --------------------------------

    def _pad(self, *arrays: np.ndarray) -> tuple[np.ndarray, ...]:
        return pad_to_bucket(
            bucket_size(len(arrays[0]), self.ctx.cfg.bucket_growth), *arrays
        )

    def _dispatch_zero_batch(self, zeros: list[Area]) -> None:
        """One zero-fill program per destination region covers every
        fresh-destination area opened this tick — escalated and epoch alike
        (dst_region is a static program argument)."""
        if not zeros:
            return
        ctx = self.ctx
        by_region: dict[int, list[np.ndarray]] = {}
        for a in zeros:
            by_region.setdefault(int(a.dst_region), []).append(a.dst_slots)
        for region, slot_lists in by_region.items():
            (slots,) = self._pad(np.concatenate(slot_lists))
            ctx.state = migrator.zero_fill(ctx.state, jax.numpy.asarray(slots), region)
            ctx.count("dispatches", 1, program="zero_fill")

    def _dispatch_begin_batch(self, opened: list[Area]) -> None:
        if not opened:
            return
        ctx = self.ctx
        (ids,) = self._pad(np.concatenate([a.block_ids for a in opened]))
        ctx.state = migrator.begin_areas(ctx.state, jax.numpy.asarray(ids))
        ctx.count("dispatches", 1, program="begin_areas")

    def _dispatch_force_batch(self, forced: list[Area]) -> None:
        if not forced:
            return
        ctx = self.ctx
        ids = np.concatenate([a.block_ids for a in forced])
        regions = np.concatenate(
            [np.full(len(a), a.dst_region, np.int32) for a in forced]
        )
        slots = np.concatenate([a.dst_slots for a in forced])
        ids, regions, slots = self._pad(ids, regions, slots)
        ctx.state = migrator.force_areas(
            ctx.state,
            jax.numpy.asarray(ids),
            jax.numpy.asarray(regions),
            jax.numpy.asarray(slots),
        )
        ctx.count("dispatches", 1, program="force_areas")

    def _dispatch_copy_batch(
        self, plan: list[tuple[Area, np.ndarray, np.ndarray]]
    ) -> None:
        if not plan:
            return
        ctx = self.ctx
        if ctx.mesh is None or ctx.cfg.axis_name is None:
            raise ValueError("ppermute backend requires mesh and axis_name")
        n_blocks = sum(len(ids) for _, ids, _ in plan)
        ctx.count("bytes_copied", n_blocks * ctx.pool_cfg.block_bytes)
        # One point-to-point program per (src, dst) region pair this tick;
        # areas are single-source so chunks group cleanly.
        pairs: dict[tuple[int, int], list[tuple[np.ndarray, np.ndarray]]] = {}
        for area, ids, slots in plan:
            pairs.setdefault((area.src_region, area.dst_region), []).append(
                (ctx.table[ids, SLOT], slots)
            )
        for (src, dst), chunks in pairs.items():
            src_slots = np.concatenate([c[0] for c in chunks])
            dst_slots = np.concatenate([c[1] for c in chunks])
            src_slots, dst_slots = self._pad(src_slots, dst_slots)
            ctx.state = migrator.fused_copy_ppermute(
                ctx.state,
                jax.numpy.asarray(src_slots),
                jax.numpy.asarray(dst_slots),
                int(src),
                int(dst),
                ctx.cfg.axis_name,
                ctx.mesh,
            )
            ctx.count("dispatches", 1, program="fused_copy_ppermute")

    def _dispatch_commit_batch(self, ready: list[Area]) -> None:
        if not ready:
            return
        ctx = self.ctx
        ids = np.concatenate([a.block_ids for a in ready])
        regions = np.concatenate(
            [np.full(len(a), a.dst_region, np.int32) for a in ready]
        )
        slots = np.concatenate([a.dst_slots for a in ready])
        offsets = np.cumsum([0] + [len(a) for a in ready])
        p_ids, p_regions, p_slots = self._pad(ids, regions, slots)
        ctx.state, verdict = migrator.commit_areas(
            ctx.state,
            jax.numpy.asarray(p_ids),
            jax.numpy.asarray(p_regions),
            jax.numpy.asarray(p_slots),
        )
        ctx.count("dispatches", 1, program="commit_areas")
        for a in ready:
            ctx.active.remove(a)
        ctx.pending.append(CommitBatch(ready, offsets, verdict))

    # -- tier transitions (two-tier pool) ----------------------------------

    def promote_candidates(self, limit: int | None = None) -> list[int]:
        """Groups currently eligible for promotion (aligned, resident, cold)."""
        ctx = self.ctx
        if ctx.tiers is None:
            return []
        out = ctx.promotion.candidates(
            ctx.tiers, ctx.table, ctx.migrating, ctx.last_write, ctx.stats.ticks
        )
        return out[:limit] if limit is not None else out

    def promote_group(self, g: int) -> bool:
        """Coalesce group ``g``'s G small blocks into one huge block.

        Requires the policy's aligned/fully-resident/cold checks and a free
        run in the group's region; the compaction copy+remap goes through the
        atomic force program, so no epoch (and no race window) is needed.
        Returns False (no state change) when ineligible or out of runs.
        """
        ctx = self.ctx
        if ctx.tiers is None:
            return False
        if not ctx.promotion.eligible(
            g, ctx.tiers, ctx.table, ctx.migrating, ctx.last_write, ctx.stats.ticks
        ):
            return False
        members = ctx.tiers.members(g)
        region = int(ctx.table[members[0], REGION])
        start = ctx.free[region].take_run()
        if start is None:
            return False
        G = ctx.pool_cfg.huge_factor
        dst_slots = start + np.arange(G, dtype=np.int32)
        ctx.state = migrator.force_areas(
            ctx.state,
            jax.numpy.asarray(members),
            jax.numpy.asarray(np.full(G, region, np.int32)),
            jax.numpy.asarray(dst_slots),
        )
        ctx.count("dispatches", 1, program="force_areas")
        ctx.count("bytes_copied", G * ctx.pool_cfg.block_bytes)
        # take_run left the destination live as one huge allocation; the old
        # scattered member slots free individually and coalesce.
        ctx.free[region].put(ctx.table[members, SLOT])
        ctx.table[members, SLOT] = dst_slots
        ctx.tiers.promote(g, region, start)
        ctx.count("promotions", 1, group=g)
        return True

    def adopt_huge(self, group_ids) -> int:
        """Zero-copy promotion of groups whose members already sit on aligned
        contiguous runs (e.g. straight out of ``init_state``'s dense
        placement).  Pure host metadata; returns the number adopted.
        """
        ctx = self.ctx
        if ctx.tiers is None:
            return 0
        G = ctx.pool_cfg.huge_factor
        adopted = 0
        for g in np.asarray(group_ids, dtype=np.int64):
            g = int(g)
            members = ctx.tiers.members(g)
            if ctx.tiers.tier[g] or ctx.migrating[members].any():
                continue
            region = ctx.table[members, REGION]
            start = int(ctx.table[members[0], SLOT])
            contiguous = (
                (region == region[0]).all()
                and start % G == 0
                and (ctx.table[members, SLOT] == start + np.arange(G)).all()
            )
            if not contiguous:
                continue
            ctx.free[int(region[0])].merge_allocated(start)
            ctx.tiers.promote(g, int(region[0]), start)
            adopted += 1
        return adopted
