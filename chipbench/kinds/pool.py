"""A block pool whose resident blocks leap between regions under foreground writes.

The system under test is ``repro.core``: the window drives
``MigrationDriver.write()`` (a burst of block writes, synced) and
``LeapSession.tick()`` in turn, and starts the next leap of every block
group back the other way as soon as the previous ones resolve.

Configuration keys: ``n_regions``, ``slots_per_region``, ``block_shape``,
``dtype``, ``resident_blocks`` (blocks that start in each region),
``region_axis`` (a mesh axis with one region per chip, or null), ``leap``
(``LeapConfig`` fields).  Mix keys: ``burst_blocks``, ``hot_share``,
``hot_fraction``, ``leaps`` (``[src, dst]`` pairs run at once, then back).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.reference.blocks import block_values, count_bad_blocks
from repro.core import LeapConfig, MigrationDriver, PoolConfig, init_state

GIB = float(1 << 30)


class Cell:
    def __init__(self, cfg, mix, seed, devices, spans, log=print):
        self.cfg, self.mix, self.seed, self.devices = cfg, mix, seed, devices
        self.spans, self.log = spans, log
        self.seed32 = np.uint32(seed % 2**32)
        self.shape = tuple(cfg["block_shape"])

    # -- set-up -----------------------------------------------------------------

    def setup(self) -> None:
        cfg = self.cfg
        axis = cfg.get("region_axis")
        mesh = None
        if axis:
            mesh = jax.make_mesh((cfg["n_regions"],), (axis,), devices=self.devices,
                                 axis_types=(jax.sharding.AxisType.Auto,))
        pool_cfg = PoolConfig(cfg["n_regions"], cfg["slots_per_region"], self.shape,
                              jnp.dtype(cfg["dtype"]), region_axis=axis)
        self.block_bytes = pool_cfg.block_bytes
        home = np.concatenate([np.full(n, r, np.int32)
                               for r, n in enumerate(cfg["resident_blocks"])])
        self.n_blocks = len(home)
        leap_cfg = LeapConfig(**cfg.get("leap", {}))
        self.driver = MigrationDriver(init_state(pool_cfg, self.n_blocks, home, mesh=mesh),
                                      pool_cfg, leap_cfg, mesh=mesh)
        self.session = self.driver.default_session()
        self.versions = np.zeros(self.n_blocks, np.int32)
        self.groups = [np.flatnonzero(home == src).astype(np.int32)
                       for src, _ in self.mix["leaps"]]
        self.where = [src for src, _ in self.mix["leaps"]]
        self.handles: list = []
        self.live: list = []
        self.tables: list = []
        for lo in range(0, self.n_blocks, 512):
            ids = np.arange(lo, min(lo + 512, self.n_blocks), dtype=np.int32)
            self.driver.write(ids, self._values(ids))
        self.bursts = traffic.write_bursts(self.mix, self.seed, self.n_blocks)
        # Warm the programs of full leaps: one leap of each group there and
        # one back, under the same write bursts.
        for _ in range(2):
            self._start_leaps()
            while not all(h.done for h in self.live):
                self._step(restart=False)
        # The last ticks of a leap, and its dirty retries, batch fewer blocks
        # than the budget; where the batch shape follows the count (one
        # program per padded size and region pair), the full leaps above meet
        # only the sizes their seed happens to leave.  So leap the first
        # 1, g, g**2, ... blocks of each group there and back, one group at a
        # time, for every padded size g**k up to the tick's budget.
        size = 1
        while size <= leap_cfg.budget_blocks_per_tick:
            for g, (src, dst) in enumerate(self.mix["leaps"]):
                ids = self.groups[g][:size]
                for to in (dst, src):
                    h = self.session.leap(ids, dst_region=to)
                    self.handles.append((h, len(ids)))
                    while not h.done:
                        self._step(restart=False)
            size *= leap_cfg.bucket_growth
        jax.block_until_ready(self.driver.state)

    def _values(self, ids):
        return block_values(self.seed32, jnp.asarray(ids), jnp.asarray(self.versions[ids]),
                            self.shape)

    def _start_leaps(self) -> None:
        # Every leap has resolved, so the host's block table and the device's
        # are equal here.  Keep both for check(); the device's as a copy on
        # the device, which nothing waits for.  A step that leaves the device
        # unchanged then shows at every boundary, even where the blocks'
        # round trips bring the final tables back to where they started.
        self.tables.append((self.driver.host_table(), jnp.copy(self.driver.state.table)))
        self.live = []
        for g, (src, dst) in enumerate(self.mix["leaps"]):
            dst = dst if self.where[g] == src else src
            self.live.append(self.session.leap(self.groups[g], dst_region=dst))
            self.where[g] = dst
            self.handles.append((self.live[-1], len(self.groups[g])))

    def _step(self, restart: bool = True) -> float:
        """One foreground burst (issued, synced) then one tick, then the next
        leaps where the last ones resolved; returns the burst's latency in
        seconds."""
        ids = next(self.bursts)
        self.versions[ids] += 1
        t0 = time.perf_counter()
        with self.spans.span("write"):
            self.driver.write(ids, self._values(ids))
            jax.block_until_ready(self.driver.state.pool)
        dt = time.perf_counter() - t0
        with self.spans.span("tick"):
            self.session.tick()
        if restart and all(h.done for h in self.live):
            self._start_leaps()
        return dt

    # -- window -----------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        s0 = self.driver.stats.snapshot()
        lat = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            lat.append(self._step())
        jax.block_until_ready(self.driver.state)
        window_s = time.perf_counter() - t0
        s1 = self.driver.stats.snapshot()
        committed = (s1.blocks_migrated - s0.blocks_migrated) + (s1.blocks_forced - s0.blocks_forced)
        copied = s1.bytes_copied - s0.bytes_copied
        lat_ms = np.asarray(lat) * 1e3
        self.log(f"window {window_s:.6f} s: {len(lat)} bursts and ticks, {committed} blocks "
                 f"committed, {s1.dirty_rejections - s0.dirty_rejections} dirty rejections, "
                 f"{len(self.handles)} leaps so far")
        return {
            "attempted": len(lat),
            "e2e": {
                "migrate_gib_s": committed * self.block_bytes / window_s / GIB,
                "write_p95_ms": float(np.percentile(lat_ms, 95)),
            },
            "facts": {
                "ticks": s1.ticks - s0.ticks,
                "committed_blocks": committed,
                "block_bytes": self.block_bytes,
                "bytes_copied": copied,
                "useful_bytes": committed * self.block_bytes,
                "window_s": window_s,
            },
        }

    # -- check ------------------------------------------------------------------

    def check(self) -> dict:
        drained = self.session.drain()
        unbalanced = 0
        for h, n in self.handles:
            p = h.progress()
            unbalanced += not (h.done and p.committed + p.forced == p.requested == n)
        placement = self.driver.host_placement()
        misplaced = sum(int(np.count_nonzero(placement[g] != self.where[i]))
                        for i, g in enumerate(self.groups))
        self.tables.append((self.driver.host_table(), self.driver.state.table))
        mirror_bad = sum(not np.array_equal(host, np.asarray(dev)) for host, dev in self.tables)
        bad = count_bad_blocks(lambda ids: self.driver.read(ids, note=False), self.seed,
                               self.versions, self.shape)
        checks = [
            ("undrained", 0 if drained else 1, 0),
            ("leaps_unbalanced", unbalanced, 0),
            ("blocks_misplaced", misplaced, 0),
            ("mirror_mismatch", mirror_bad, 0),
            ("blocks_not_as_written", bad, 0),
        ]
        return {"checks": checks, "failed": bad}
