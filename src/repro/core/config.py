"""Tuning knobs of the migration engine (`LeapConfig`).

Extracted from ``core/driver.py`` when the driver decomposed into the staged
pipeline (``repro.core.pipeline``); ``from repro.core.driver import
LeapConfig`` keeps working through the driver's re-export shim.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LeapConfig:
    """Tuning knobs of the migration engine (paper defaults in comments)."""

    initial_area_blocks: int = 64  # "initial area size" (16MB sweet spot)
    reduction_factor: int = 2  # split factor on dirty retry
    min_area_blocks: int = 1
    budget_blocks_per_tick: int = 64  # async migration budget per tick/step
    max_attempts_before_force: int = 8  # write-through escalation (beyond paper)
    # Copy backend; it also fixes the dispatch generation (DESIGN.md §3, §12):
    # "xla" runs the megastep (the whole tick as ONE device program),
    # "ppermute" the batched programs, whose shard_map copies have static
    # (src, dst) endpoints and cannot fuse into one variant-stable program.
    backend: str = "xla"  # "xla" | "ppermute"
    axis_name: str | None = None  # region mesh axis (ppermute backend)
    # Ahead-of-time compile the megastep's steady-state variants at driver
    # construction (xla backend only; no-op otherwise).  Possible because
    # the budget-floored shared bucket fixes every steady-state operand shape
    # before any workload runs — moves XLA compiles off the migration path
    # entirely, so the first leap() pays no compile stall.  Off by default:
    # construction grows by a few hundred ms of compile time.
    warm_dispatch: bool = False
    bucket_growth: int = 4  # geometric padding factor for batch shapes
    # Two-tier pool knobs (active when PoolConfig.huge_factor > 1):
    demote_after_attempts: int = 2  # huge-commit rejections before demotion (§4.2)
    promote_cold_ticks: int = 0  # ticks since last write required to promote
    promote_per_tick: int = 0  # auto-promotions attempted per tick (0 = manual)
    # Topology-aware scheduling knobs (active when PoolConfig.topology is set):
    link_schedule: bool = True  # charge copies against per-link byte/dispatch budgets
    multi_hop: bool = True  # relay via an intermediate region when 2 hops are cheaper
    link_blocks_per_tick: int | None = None  # per-link block budget at bandwidth 1.0
    # (None: defaults to budget_blocks_per_tick — one full-speed link can
    # absorb the whole tick budget; slower links get proportionally less)
    # Closed-loop tiering (DESIGN.md §13): maintain a per-block exponentially
    # decayed access-heat plane on device, updated as an optional megastep
    # phase (trace-time skipped when off, so disabling tiering is bit-
    # identical to the tiering-less engine).  The heat plane feeds
    # repro.tiering.TieringPolicy's promotion/demotion watermarks.
    tiering: bool = False
    tier_heat_decay: float = 0.9  # per-update exponential decay of heat
    tier_write_weight: float = 1.0  # heat added per write (reads add 1.0)
    # A block re-migrated within this many ticks of its previous migration
    # counts as a ping-pong (MigrationStats.ping_pong_migrations) — the
    # quantity the tiering policy's hysteresis exists to suppress.
    tier_pingpong_window: int = 16
    # Telemetry (repro.obs): off by default — the pipeline then carries the
    # shared NullRecorder and pays only attribute lookups per tick.
    telemetry: bool = False
    telemetry_events: int = 65536  # event ring capacity (oldest evicted)
    telemetry_requests: int = 1024  # resolved request spans retained (LRU)

