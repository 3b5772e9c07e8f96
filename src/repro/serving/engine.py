"""Paged-KV serving engine on a leap pool: decode reads through the block
table, appends mark in-flight blocks dirty, and KV blocks leap-migrate
between regions *while decoding continues* — the serving-side integration
of the paper's technique (DESIGN.md §4).

One page = one token-range across ALL layers: payload
``[L, 2, BLK, kv_heads * head_dim]`` (so migrating a sequence is one area).
The heads share the minor dim so a page is lane-dense on TPU whatever the
head width: a 64-wide minor dim would be padded to 128 lanes in HBM.  The
decode hot loop uses ``repro.kernels.ops.paged_decode_partial`` (Pallas on
TPU, oracle elsewhere) directly on the pool, one layer per call.  Supported
stacks: uniform global-attention patterns ("attn"/"moe" kinds);
window/recurrent stacks serve via the contiguous cache path in
``launch/serve.py``.

Regions: on a mesh, pool dim 0 shards over the data axis and each region
serves its resident sequences; on one device (tests/benches) regions are
logical rows — identical control flow.

Tracing: ``admit``, ``decode`` and ``rebalance`` run inside the pool's
telemetry stages ``serve.admit``, ``serve.decode`` and ``serve.rebalance``
(profiler spans ``leap.serve.*``, on whether telemetry is on or off), and
:class:`ServeStats` counts steps, tokens, the KV pages the paged kernel
reads and the chunks it copies them in.  The decode step and the prefill
are the XLA programs ``jit_paged_decode_step`` and ``jit_paged_prefill``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import LeapHandle, Move
from repro.configs.base import ModelConfig
from repro.core import LeapConfig, MigrationDriver, PoolConfig, init_state
from repro.core.state import REGION, SLOT, flat_pool_view
from repro.kernels import ops, paged_attn
from repro.models import lm
from repro.models.attention import _project_qkv
from repro.models.blocks import residual_add
from repro.models.common import mlp_forward, rms_norm
from repro.models.moe import moe_ffn
from repro.obs.metrics import LATENCY_TICK_BUCKETS, Histogram


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    block_tokens: int = 16
    max_blocks_per_seq: int = 64
    n_regions: int = 2
    slots_per_region: int = 256
    leap: LeapConfig = dataclasses.field(default_factory=LeapConfig)
    # Optional NumaTopology over the KV regions: admission fallback prefers
    # regions near the sequence's home (cheap decode reads, cheap later
    # rebalance) and the driver schedules migrations link-aware (§7).
    topology: object = None
    # Two-tier KV pool: G small pages per huge block (1 = small only).  With
    # G > 1 logical page ids are handed to sequences in aligned groups of G,
    # so a long sequence's KV naturally forms promotable runs; decode
    # auto-promotes every complete group behind the append frontier.
    huge_factor: int = 1
    auto_promote: bool = True
    # Eager mode also promotes the group holding the append frontier once all
    # its ids belong to the sequence: coalesces sooner, at the price of decode
    # appends dirtying an in-flight huge block — which is exactly what the
    # driver's §4.2 demotion rule is for (promote eagerly, demote under
    # pressure).  Off by default: promoted KV stays cold by construction.
    promote_eager: bool = False
    # Migration scheduler policy for the KV pool's driver: "leap" (default,
    # reliable async epochs), "sync" (move_pages()-style forced moves), or a
    # SchedulerPolicy instance — the repro.core.pipeline seam, selectable
    # per deployment so rebalance traffic can trade race-freedom for pacing.
    scheduler: object = "leap"


@dataclasses.dataclass
class ServeStats:
    """Serving counters (each also a ``serve.<name>`` telemetry counter)."""

    decode_steps: int = 0
    tokens_decoded: int = 0
    tokens_prefilled: int = 0
    # pages the paged-attention kernel reads: per step and sequence
    # ceil(tokens attended / block_tokens), each page once per layer
    kv_pages_read: int = 0
    # chunks of those pages the kernel copies, each layer's counted: per
    # step, sequence and layer ceil(pages / P), P = paged_attn.chunk_pages
    kv_chunks_read: int = 0


@dataclasses.dataclass
class Sequence:
    sid: int
    region: int
    length: int
    block_ids: list[int]  # logical leap block ids, in order
    tokens: list[int]
    tenant: str = "default"  # serving class (SLO/metrics attribution)
    promoted: set = dataclasses.field(default_factory=set)  # huge group ids


class PagedEngine:
    """Batched decode over a migration-managed paged KV cache."""

    def __init__(self, cfg: ModelConfig, params, pcfg: PagedConfig):
        for kind in cfg.layer_pattern + cfg.tail_pattern:
            if kind not in ("attn", "moe"):
                raise ValueError(
                    f"PagedEngine supports uniform global-attention stacks; "
                    f"{cfg.name} has kind {kind!r} (serve via contiguous path)"
                )
        if cfg.tail_pattern:
            raise ValueError("PagedEngine expects a pure periodic stack")
        self.cfg = cfg
        self.params = params
        self.pcfg = pcfg
        payload = (cfg.n_layers, 2, pcfg.block_tokens, cfg.n_kv_heads * cfg.head_dim)
        G = pcfg.huge_factor
        self.pool_cfg = PoolConfig(
            pcfg.n_regions,
            pcfg.slots_per_region,
            payload,
            cfg.dtype(),
            huge_factor=G,
            topology=pcfg.topology,
        )
        # Pages occupy half the physical slots; the other half is the pooled
        # migration headroom (the paper's "migration into pooled memory"
        # requires pre-faulted destination capacity).  With a huge tier, the
        # per-region page count rounds down to whole groups so no aligned
        # logical group straddles a region.
        pages_per_region = (pcfg.slots_per_region // 2 // G) * G
        n_blocks = pcfg.n_regions * pages_per_region
        placement = np.repeat(np.arange(pcfg.n_regions), pages_per_region)
        state = init_state(self.pool_cfg, n_blocks, placement.astype(np.int32))
        self.driver = MigrationDriver(
            state, self.pool_cfg, pcfg.leap, scheduler=pcfg.scheduler
        )
        # The engine drives migration exclusively through the handle-based
        # session API; the sealed facade is its only placement view.
        self.session = self.driver.default_session()
        self.facade = self.session.facade
        if G > 1:
            n_groups = n_blocks // G
            groups_per_region = pages_per_region // G
            # Group-aligned logical id pool: a sequence draws whole groups of
            # G ids at a time, spending them block by block, so its KV forms
            # promotable aligned runs as it grows.
            self._group_free: list[list[int]] = [
                list(range(g * G, (g + 1) * G)) for g in range(n_groups)
            ]
            self._free_groups: list[list[int]] = [
                list(range(r * groups_per_region, (r + 1) * groups_per_region))
                for r in range(pcfg.n_regions)
            ]
            self._partial: set[int] = set()  # groups with some (not all) ids free
            self._seq_spare: dict[int, list[int]] = {}  # sid -> reserved unused ids
        else:
            self._free_blocks: list[list[int]] = [
                list(range(r * pages_per_region, (r + 1) * pages_per_region))
                for r in range(pcfg.n_regions)
            ]
        self.n_pages = n_blocks
        self.seqs: dict[int, Sequence] = {}
        self._next_sid = 0
        # sid -> the handle of its latest rebalance (latency attribution)
        self._rebalance_handles: dict[int, LeapHandle] = {}
        # The decode step (``decode_step_program``) compiles once per decode
        # batch size — callers that vary batch size should chunk to powers
        # of two (repro.load does) to bound the compile count — and the
        # prefill (``prefill_program``) once per prompt length.
        self._decode_shapes: set[int] = set()  # observed decode batch sizes
        # pages per chunk of the paged kernel's walk (kv_chunks_read)
        self._chunk_pages = paged_attn.chunk_pages(
            pcfg.block_tokens, payload[-1], cfg.dtype(), pcfg.max_blocks_per_seq
        )
        self.stats = ServeStats()
        # logits of the latest admit ([1, V]) or decode ([B, V]), on the device
        self.last_logits = None
        # Per-tenant serving metrics: token-latency histogram (modeled units
        # supplied by the caller via observe_tokens) and migration bytes
        # attributed on rebalance completion.  Exposed through telemetry().
        self._tenant_lat: dict[str, Histogram] = {}
        self._tenant_mig_bytes: dict[str, int] = {}
        self._tenant_tokens: dict[str, int] = {}

    # -- admission ---------------------------------------------------------------

    def _alloc_order(self, region: int) -> list[int]:
        """Allocation fallback order: the home region first, then — with a
        topology — the others nearest-first (a page that cannot live at home
        should at least sit one cheap link away), else index order."""
        topo = self.pool_cfg.topology
        if topo is not None:
            return [region] + topo.nearest(region)
        return [region] + [x for x in range(self.pcfg.n_regions) if x != region]

    def _alloc_block(self, region: int, sid: int | None = None) -> int:
        if self.pcfg.huge_factor == 1:
            for r in self._alloc_order(region):
                if self._free_blocks[r]:
                    return self._free_blocks[r].pop()
            raise RuntimeError("KV pool exhausted")
        # Tiered pool: spend the sequence's reserved group first, then break a
        # fresh aligned group, then scavenge loose ids from partial groups.
        spare = self._seq_spare.get(sid)
        if spare:
            return spare.pop(0)
        for r in self._alloc_order(region):
            if self._free_groups[r]:
                g = self._free_groups[r].pop()
                ids = sorted(self._group_free[g])
                self._group_free[g] = []
                if sid is not None:
                    self._seq_spare.setdefault(sid, []).extend(ids[1:])
                else:
                    self._partial.add(g)
                    self._group_free[g] = ids[1:]
                return ids[0]
        for g in sorted(self._partial):
            ids = self._group_free[g]
            if ids:
                b = ids.pop()
                if not ids:
                    self._partial.discard(g)
                return b
        raise RuntimeError("KV pool exhausted")

    def _return_block(self, b: int) -> None:
        """Release one logical id back to the group-aligned pool."""
        G = self.pcfg.huge_factor
        g = b // G
        ids = self._group_free[g]
        ids.append(b)
        if len(ids) == G:
            self._partial.discard(g)
            region = int(self.facade.region_of(g * G))
            self._free_groups[region].append(g)
        else:
            self._partial.add(g)

    def admit(self, prompt: np.ndarray, region: int = 0, tenant: str = "default") -> int:
        """Prefill a prompt, install its pages, and emit the first generated
        token from the prefill logits (``seqs[sid].tokens[-1]``).  Subsequent
        tokens come from ``decode()``, which processes the latest generated
        token at position ``length``.  ``tenant`` labels the sequence's
        serving class for per-tenant metrics and SLO attribution."""
        cfg, blk = self.cfg, self.pcfg.block_tokens
        s = len(prompt)
        n_blocks = (s + blk - 1) // blk
        if n_blocks > self.pcfg.max_blocks_per_seq:
            raise ValueError(
                f"a {s}-token prompt needs {n_blocks} pages; max_blocks_per_seq is "
                f"{self.pcfg.max_blocks_per_seq}"
            )
        with self.driver.telemetry.stage("serve.admit"):
            toks = jnp.asarray(prompt)[None]
            logits, cache = prefill_program(self.params, toks, cfg=cfg, max_len=s)
            self.last_logits = logits
            first_tok = int(jnp.argmax(logits, -1)[0])
            sid = self._next_sid
            self._next_sid += 1
            seq = Sequence(
                sid, region, s, [], list(map(int, prompt)) + [first_tok], tenant=tenant
            )
            seq.block_ids = [self._alloc_block(region, sid) for _ in range(n_blocks)]
            # contiguous cache -> pages, installed with one write
            self.driver.write(
                jnp.asarray(seq.block_ids, jnp.int32), _cache_pages(cache, cfg, blk)
            )
            self.seqs[sid] = seq
            self._count("tokens_prefilled", s)
        return sid

    def _count(self, name: str, n: int) -> None:
        setattr(self.stats, name, getattr(self.stats, name) + n)
        self.driver.telemetry.count("serve." + name, n)

    def release(self, sid: int) -> None:
        seq = self.seqs.pop(sid)
        if self.pcfg.huge_factor == 1:
            regions = self.facade.region_of(np.asarray(seq.block_ids, np.int64))
            for b, r in zip(seq.block_ids, regions):
                self._free_blocks[int(r)].append(b)
            return
        for b in seq.block_ids + self._seq_spare.pop(sid, []):
            self._return_block(b)

    # -- decode -------------------------------------------------------------------

    def _tables(self, sids):
        maxb = self.pcfg.max_blocks_per_seq
        tab = np.zeros((len(sids), maxb), np.int32)
        lens = np.zeros((len(sids),), np.int32)
        for i, sid in enumerate(sids):
            seq = self.seqs[sid]
            tab[i, : len(seq.block_ids)] = seq.block_ids
            lens[i] = seq.length
        return tab, lens

    def decode(self, sids: list[int], greedy: bool = True) -> list[int]:
        """One token for each sequence in ``sids``; appends in place."""
        blk = self.pcfg.block_tokens
        with self.driver.telemetry.stage("serve.decode"):
            # allocate next block where needed, BEFORE the step
            for sid in sids:
                seq = self.seqs[sid]
                if seq.length % blk == 0 and seq.length // blk >= len(seq.block_ids):
                    if len(seq.block_ids) == self.pcfg.max_blocks_per_seq:
                        raise ValueError(
                            f"sequence {sid} outgrows max_blocks_per_seq "
                            f"({self.pcfg.max_blocks_per_seq} pages)"
                        )
                    seq.block_ids.append(self._alloc_block(seq.region, sid))
                self._maybe_promote(seq)
            tables, lens = self._tables(sids)
            if self.driver.ctx.heat is not None:
                # attention reads every page behind the frontier: feed the whole
                # working set into the heat plane (folds into this tick's
                # megastep — no extra dispatch, see DESIGN.md §13)
                self.driver.note_reads(
                    np.concatenate(
                        [np.asarray(self.seqs[s].block_ids, np.int32) for s in sids]
                    )
                )
            self._decode_shapes.add(len(sids))
            logits, self.driver.state = decode_step_program(
                self.params, self.driver.state, jnp.asarray(tables), jnp.asarray(lens),
                self._last_tokens(sids), cfg=self.cfg, blk=blk,
            )
            self.last_logits = logits
            out = np.asarray(jnp.argmax(logits, -1))
            for i, sid in enumerate(sids):
                seq = self.seqs[sid]
                seq.tokens.append(int(out[i]))
                seq.length += 1
            self._count("decode_steps", 1)
            self._count("tokens_decoded", len(sids))
            # the kernel attends over the cached tokens and the new one
            held = lens // blk + 1
            self._count("kv_pages_read", int(np.sum(held)))
            chunks = -(-held // self._chunk_pages)
            self._count("kv_chunks_read", int(np.sum(chunks)) * self.cfg.n_layers)
        return [int(t) for t in out]

    def _last_tokens(self, sids):
        return jnp.asarray([[self.seqs[s].tokens[-1]] for s in sids], jnp.int32)

    def lower_decode(self, sids: list[int]):
        """The decode step for ``sids`` lowered on the arguments
        :meth:`decode` passes (``.compile().as_text()`` shows which kernels
        the compiled step runs)."""
        tables, lens = self._tables(sids)
        return decode_step_program.lower(
            self.params, self.driver.state, jnp.asarray(tables), jnp.asarray(lens),
            self._last_tokens(sids), cfg=self.cfg, blk=self.pcfg.block_tokens,
        )

    # -- tier promotion -----------------------------------------------------------

    def _maybe_promote(self, seq: Sequence) -> None:
        """Promote the sequence's complete aligned groups to huge blocks.

        A group is promotable once every member belongs to this sequence and
        sits strictly behind the append frontier (decode only ever writes the
        last block, so promoted KV is cold by construction); the driver
        re-checks residency/coldness and allocates the contiguous run.
        """
        G = self.pcfg.huge_factor
        if G == 1 or not self.pcfg.auto_promote:
            return
        pool = seq.block_ids if self.pcfg.promote_eager else seq.block_ids[:-1]
        if len(pool) < G:
            return
        ids = np.asarray(pool, np.int64)
        groups, counts = np.unique(ids // G, return_counts=True)
        for g, c in zip(groups, counts):
            g = int(g)
            if c != G or g in seq.promoted:
                continue
            if self.driver.tiers.tier[g] or self.driver.promote_group(g):
                # already huge (e.g. a group recycled from a released
                # sequence) or promoted now — either way, stop retrying it
                seq.promoted.add(g)

    # -- migration ------------------------------------------------------------------

    def decide(self, facade) -> list[Move]:
        """:class:`repro.api.PlacementPolicy`: sequence affinity as moves.

        Every live sequence's KV pages should sit on its declared home
        region; any page observed elsewhere (admission fallback, a stale
        rebalance) yields one move tagged with the sequence id.  Policy only
        — the session owns the mechanism (``session.apply(engine)``).
        """
        moves = []
        for sid, seq in self.seqs.items():
            if not seq.block_ids:
                continue
            ids = np.asarray(seq.block_ids, np.int32)
            if (facade.region_of(ids) != seq.region).any():
                moves.append(Move(ids, seq.region, tag=sid))
        return moves

    def rebalance(self, sid: int, dst_region: int) -> LeapHandle:
        """Leap-migrate a live sequence's pages to another region.

        Declares the sequence's new home and lets the engine's own placement
        policy (:meth:`decide`) drive the session; returns the
        :class:`LeapHandle` tracking this sequence's move (``.requested`` is
        the page count; decoding continues while it progresses).
        """
        seq = self.seqs[sid]
        seq.region = dst_region
        with self.driver.telemetry.stage("serve.rebalance"):
            # Strict-home policy: sequence affinity means the pages go to the
            # declared home or wait for capacity there — reroute=False so the
            # session never spills them to neighbouring regions, and the single
            # returned handle tracks the whole sequence move.
            handle = None
            for h in self.session.apply(self, reroute=False):
                if h.tag == sid:
                    handle = h
                    break
            if handle is None:
                # Every page already home: issue a vacuous (instantly-complete)
                # handle so callers always get a future to wait on.
                handle = self.session.leap(
                    np.asarray(seq.block_ids, np.int32), dst_region, tag=sid
                )
        self._rebalance_handles[sid] = handle
        tenant = seq.tenant
        handle.on_done(lambda h: self._account_migration(tenant, h))
        return handle

    def _account_migration(self, tenant: str, handle: LeapHandle) -> None:
        """Attribute a resolved rebalance's moved bytes to its tenant."""
        p = handle.progress()
        moved = (p.committed + p.forced) * self.pool_cfg.block_bytes
        self._tenant_mig_bytes[tenant] = (
            self._tenant_mig_bytes.get(tenant, 0) + moved
        )

    def rebalance_handles(self) -> list:
        """The latest rebalance handle per sequence (live and resolved) —
        what a chaos cancel-storm or a drain supervisor operates on."""
        return list(self._rebalance_handles.values())

    def rebalance_latency(self, sid: int):
        """Latency breakdown of ``sid``'s latest :meth:`rebalance` (a
        :class:`repro.obs.LatencyBreakdown`), or None when the sequence was
        never rebalanced or telemetry is off.  Released sequences keep their
        last attribution until the engine is dropped."""
        handle = self._rebalance_handles.get(sid)
        return handle.latency() if handle is not None else None

    # -- tenants / capacity ---------------------------------------------------------

    def observe_tokens(self, tenant: str, latencies) -> None:
        """Record per-token latencies (caller-chosen units — the load
        generator feeds modeled time units) into the tenant's histogram."""
        hist = self._tenant_lat.get(tenant)
        if hist is None:
            hist = self._tenant_lat[tenant] = Histogram(LATENCY_TICK_BUCKETS)
        vals = np.atleast_1d(np.asarray(latencies, np.float64))
        for v in vals:
            hist.observe(v)
        self._tenant_tokens[tenant] = self._tenant_tokens.get(tenant, 0) + len(vals)

    def tenant_stats(self) -> dict:
        """Per-tenant snapshot: tokens observed, migration bytes, latency
        histogram dict (empty entries omitted)."""
        out: dict[str, dict] = {}
        tenants = set(self._tenant_tokens) | set(self._tenant_mig_bytes)
        tenants.update(s.tenant for s in self.seqs.values())
        for t in sorted(tenants):
            hist = self._tenant_lat.get(t)
            out[t] = {
                "tokens": self._tenant_tokens.get(t, 0),
                "migration_bytes": self._tenant_mig_bytes.get(t, 0),
                "latency": hist.to_dict() if hist is not None else None,
            }
        return out

    def free_pages(self) -> int:
        """Logical pages a NEW sequence could allocate right now (per-sequence
        reserved spares excluded — they are spendable only by their owner)."""
        if self.pcfg.huge_factor == 1:
            return sum(len(f) for f in self._free_blocks)
        G = self.pcfg.huge_factor
        n = sum(len(g) for g in self._free_groups) * G
        n += sum(len(self._group_free[g]) for g in self._partial)
        return n

    def page_accounting(self) -> dict:
        """Page-closure snapshot: every logical page is exactly one of
        {held by a live sequence, reserved spare, free} —
        ``used + spare + free == total``.  Includes per-tenant held pages."""
        used = sum(len(s.block_ids) for s in self.seqs.values())
        spare = (
            0
            if self.pcfg.huge_factor == 1
            else sum(len(v) for v in self._seq_spare.values())
        )
        per_tenant: dict[str, int] = {}
        for s in self.seqs.values():
            per_tenant[s.tenant] = per_tenant.get(s.tenant, 0) + len(s.block_ids)
        return {
            "total": self.n_pages,
            "used": used,
            "spare": spare,
            "free": self.free_pages(),
            "per_tenant": per_tenant,
        }

    def _tenant_series(self, reg) -> None:
        """Extra-series hook: co-expose the tenant store in driver scrapes."""
        for t, hist in sorted(self._tenant_lat.items()):
            reg.histogram("leap_tenant_token_latency", hist, labels={"tenant": t})
        for t, nbytes in sorted(self._tenant_mig_bytes.items()):
            reg.counter(
                "leap_tenant_migration_bytes_total", nbytes, labels={"tenant": t}
            )
        for t, n in sorted(self._tenant_tokens.items()):
            reg.counter("leap_tenant_tokens_total", n, labels={"tenant": t})

    def telemetry(self):
        """The KV pool's :class:`repro.obs.TelemetryView` (same recorder the
        session exposes — decode-side rebalances land in the same timeline),
        extended with the engine's per-tenant series (token-latency
        histograms, migration-byte and token counters labeled ``tenant=``)."""
        return self.session.telemetry().with_extra(self._tenant_series)

    def tick(self) -> None:
        self.session.tick()

    def drain(self) -> bool:
        return self.session.drain()


def _cache_pages(cache, cfg: ModelConfig, blk: int):
    """lm prefill cache (batch 1, S tokens) -> pages ``[ceil(S/blk), L, 2,
    blk, KVH*hd]`` in layer order, zero-padded past the last token."""
    per = len(cfg.layer_pattern)

    def layers(name):
        # period caches are [repeats, 1, S, KVH, hd] per pattern position;
        # layer rep*per + pos comes from position pos of repeat rep
        x = jnp.stack([cache["period"][p][name][:, 0] for p in range(per)], axis=1)
        return x.reshape((cfg.repeats * per,) + x.shape[2:])  # [L, S, KVH, hd]

    kv = jnp.stack([layers("k"), layers("v")], axis=1)  # [L, 2, S, KVH, hd]
    n_layers, _, s = kv.shape[:3]
    n_pages = -(-s // blk)
    kv = kv.reshape(n_layers, 2, s, -1)
    kv = jnp.pad(kv, ((0, 0), (0, 0), (0, n_pages * blk - s), (0, 0)))
    kv = kv.reshape(n_layers, 2, n_pages, blk, -1)
    return jnp.moveaxis(kv, 2, 0)


def paged_prefill(params, toks, *, cfg: ModelConfig, max_len: int):
    """Prefill of one prompt: the contiguous path's ``lm.prefill``."""
    return lm.prefill(params, toks, cfg, max_len)


#: The prefill program, one compile per prompt length.
prefill_program = jax.jit(paged_prefill, static_argnames=("cfg", "max_len"))


def paged_decode_step(params, state, tables, lens, toks, *, cfg: ModelConfig, blk: int):
    """One decode token through paged attention for every layer.

    Each layer appends its new K/V into the pool in place, then attends over
    the pool itself (the kernel indexes the layer; no per-layer copy of the
    pool is made).  The appended blocks are marked dirty when in flight.
    """
    b = toks.shape[0]
    x = lm.embed_tokens(params, toks, cfg)
    pos = lens  # per-sequence position (tokens cached so far)
    s_per = state.pool.shape[1]
    flat_tables = state.table[tables.reshape(-1)]  # [(B*MAXB), 2]
    flat = (flat_tables[:, 0] * s_per + flat_tables[:, 1]).reshape(tables.shape)
    pool = flat_pool_view(state.pool)  # [R*S, L, 2, BLK, KVH*hd]
    append_block = tables[jnp.arange(b), lens // blk]
    loc = state.table[append_block]
    append_slot = loc[:, REGION] * s_per + loc[:, SLOT]
    offset = lens % blk
    at_offset = (jnp.arange(blk)[None, :] == offset[:, None])[:, None, :, None]

    period = cfg.layer_pattern
    # layers unrolled (engine/demo path; the dry-run path scans)
    li = 0
    stacked = params["period"]
    for rep in range(cfg.repeats):
        for p_i, kind in enumerate(period):
            lp = jax.tree.map(lambda t: t[rep], stacked[p_i])
            h = rms_norm(x, lp["norm1"], cfg.norm_eps)
            q, k, v = _project_qkv(h, lp["attn"], cfg, pos[:, None])
            # Rewrite the whole [2, BLK, W] tile holding the new token: a
            # scatter of single rows would make XLA re-lay the pool out (and
            # copy it) around every layer's kernel call.
            kv = jnp.stack([k[:, 0], v[:, 0]], axis=1).reshape(b, 2, 1, -1)
            tile = pool[append_slot, li]  # [B, 2, BLK, W]
            tile = jnp.where(at_offset, kv.astype(pool.dtype), tile)
            pool = pool.at[append_slot, li].set(tile)
            out, _, _ = ops.paged_decode_partial(
                q[:, 0],
                pool,
                flat,
                lens + 1,
                kv_heads=cfg.n_kv_heads,
                layer=li,
                softcap=cfg.attn_softcap,
                scale=cfg.attn_scale,
            )
            y = out.reshape(b, 1, -1) @ lp["attn"]["wo"]
            x = residual_add(x, y, cfg)
            h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if kind == "moe":
                y2, _ = moe_ffn(h2, lp["moe"], cfg)
            else:
                y2 = mlp_forward(h2, lp["mlp"], cfg.mlp_kind)
            x = residual_add(x, y2, cfg)
            li += 1
    logits = lm.lm_logits(params, x, cfg)[:, 0]
    dirty = state.dirty.at[append_block].set(
        state.dirty[append_block] | state.in_flight[append_block]
    )
    state = dataclasses.replace(
        state, pool=pool.reshape(state.pool.shape), dirty=dirty
    )
    return logits, state


#: The decode step program: donates the KV state so appends stay in place.
decode_step_program = jax.jit(
    paged_decode_step, static_argnames=("cfg", "blk"), donate_argnums=(1,)
)
