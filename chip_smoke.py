"""Smoke run of the migration engine and the paged KV server on a TPU.

    python chip_smoke.py              # one chip: pool phase, serving phase
    python chip_smoke.py --chips 4    # four chips: region-per-chip ppermute path

One chip.  The pool phase is the paper's ``page_leap``: a 2-region pool of
512 KiB f32 blocks with 3 GiB resident in region 0 leaps to region 1 while
random blocks are rewritten between ticks; it runs again with 8-block huge
pages and the access-heat plane on.  The serving phase runs
``repro.launch.serve`` on granite_3_2b at its published widths, with its
four multipliers and all 40 layers, once undisturbed and once while two
sequences' KV pages migrate.

Four chips.  One region per chip; region 0's blocks leap to region 2 through
the ppermute backend, and the result is compared with the same seeded
schedule run on one chip with the xla backend.

Data and weights come from ``--seed`` and are made on the device; every
check runs on the device.  The last line of output is one JSON object
``{"ok": true, "device": {...}}``.  The script exits non-zero, printing no
such line, when JAX finds no TPU or when any check fails.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    LeapConfig,
    MigrationDriver,
    PoolConfig,
    init_state,
    migrator,
)
from repro.core.state import flat_pool_view  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.heat_scan import padded_heat_len  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.engine import PagedEngine  # noqa: E402

BLOCK = (128, 1024)  # 512 KiB of f32, lane-dense
GIB = 1 << 30


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- seeded block contents ------------------------------------------------------


@partial(jax.jit, static_argnames=("shape",))
def block_values(seed, ids, versions, shape):
    """Contents of blocks ``ids`` after ``versions`` writes: a hash of (seed,
    block, version, element) in [0, 1).  A stale or misplaced copy differs."""
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
def _fingerprints(blocks, want):
    """Per block: mismatching elements vs ``want``, and a position-weighted
    checksum of the bits (equal checksums across runs = equal contents)."""
    bits = jax.lax.bitcast_convert_type(blocks, jnp.uint32).reshape(len(blocks), -1)
    weights = jax.lax.iota(jnp.uint32, bits.shape[1]) * jnp.uint32(2) + jnp.uint32(1)
    mismatches = jnp.sum(blocks != want, axis=tuple(range(1, blocks.ndim)))
    return mismatches, jnp.sum(bits * weights, axis=1)


def verify_contents(driver, seed, versions, batch=512):
    """Read every block through the table and compare it on the device with
    the contents its write log implies.  Returns per-block checksums."""
    n = len(versions)
    sums = []
    bad = 0
    shape = driver.pool_cfg.block_shape
    for lo in range(0, n, batch):
        ids = np.arange(lo, min(lo + batch, n), dtype=np.int32)
        got = driver.read(ids, note=False)
        want = block_values(seed, jnp.asarray(ids), jnp.asarray(versions[ids]), shape)
        mism, fp = _fingerprints(got, want)
        bad += int(np.count_nonzero(np.asarray(mism)))
        sums.append(np.asarray(fp))
    check(bad == 0, f"{bad} blocks differ from their write log")
    return np.concatenate(sums)


# -- kernel presence ----------------------------------------------------------


class MegastepRecorder:
    """Keeps the abstract arguments of every megastep variant the drivers
    dispatch, so the compiled program can be inspected afterwards."""

    def __init__(self):
        self.calls: dict[tuple, tuple] = {}
        self._real = migrator.megastep

    def __enter__(self):
        def recording(*args, **kwargs):
            spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                ),
                args,
            )
            phases = tuple(bool(n) for n in kwargs["layout"])
            self.calls[phases] = (spec, kwargs)
            return self._real(*args, **kwargs)

        migrator.megastep = recording
        return self

    def __exit__(self, *exc):
        migrator.megastep = self._real

    def compiled_text(self, want_phase: str) -> str:
        """Compiled text of a recorded variant whose segment ``want_phase``
        (a name of ``migrator.MEGASTEP_SEGMENTS``) was non-empty."""
        i = migrator.MEGASTEP_SEGMENTS.index(want_phase)
        for phases, (spec, kwargs) in self.calls.items():
            if phases[i]:
                return self._real.lower(*spec, **kwargs).compile().as_text()
        raise SmokeFailure(f"no megastep ran with segment {want_phase} non-empty")


MEGA_COPY, MEGA_RUNS = "copy_src", "run_src"  # megastep segments


def check_kernel(text: str, what: str, expect_tpu: bool) -> None:
    if expect_tpu:
        check("tpu_custom_call" in text, f"{what}: no Pallas kernel in the compiled program")


# -- phases -------------------------------------------------------------------


def pool_phase(seed, n_blocks, *, huge_factor=1, burst=32, reads=256, expect_tpu=True):
    """Leap every block from region 0 to region 1 under write bursts."""
    tiering = huge_factor > 1
    cfg = PoolConfig(2, n_blocks, BLOCK, jnp.float32, huge_factor=huge_factor)
    leap = LeapConfig(tiering=tiering)
    driver = MigrationDriver(init_state(cfg, n_blocks, np.zeros(n_blocks)), cfg, leap)
    session = driver.default_session()
    rng = np.random.default_rng(seed)
    versions = np.zeros(n_blocks, np.int32)
    heat_batches, pending = [], []  # heat samples, grouped per tick

    def write(ids):
        driver.write(ids, block_values(seed, jnp.asarray(ids), jnp.asarray(versions[ids]), BLOCK))
        pending.append((ids, leap.tier_write_weight))

    for lo in range(0, n_blocks, 512):
        write(np.arange(lo, min(lo + 512, n_blocks), dtype=np.int32))
    if tiering:
        adopted = driver.adopt_huge(np.arange(n_blocks // huge_factor))
        check(adopted == n_blocks // huge_factor, f"adopted {adopted} huge groups")

    t0 = time.perf_counter()
    with MegastepRecorder() as rec:
        handle = session.leap(np.arange(n_blocks, dtype=np.int32), dst_region=1)
        check(handle.requested == n_blocks, f"leap enqueued {handle.requested}")
        ticks = 0
        while not handle.done:
            ids = rng.choice(n_blocks, burst, replace=False).astype(np.int32)
            versions[ids] += 1
            write(ids)
            if tiering:
                seen = rng.integers(0, n_blocks, reads).astype(np.int32)
                driver.note_reads(seen)
                pending.append((seen, 1.0))
            session.tick()
            heat_batches.append(pending)
            pending = []
            ticks += 1
            check(ticks < 50 * n_blocks, "leap did not finish")
        check(handle.wait(), "handle.wait() did not resolve")
        jax.block_until_ready(driver.state)
        wall = time.perf_counter() - t0
        text = rec.compiled_text(MEGA_RUNS if tiering else MEGA_COPY)
    check_kernel(text, "megastep", expect_tpu)

    p = handle.progress()
    check(p.committed + p.forced == p.requested == n_blocks, f"progress {p}")
    check(driver.verify_mirror(), "host table mirror != device table")
    check((driver.host_placement() == 1).all(), "blocks left outside region 1")
    check(driver.verify_tiers(), "tier tables inconsistent")
    verify_contents(driver, seed, versions)
    s = driver.stats
    if tiering:
        check(s.bytes_copied_huge > 0, "no huge block moved through copy_runs")
        heat = jnp.zeros((padded_heat_len(n_blocks),), jnp.float32)
        for batch in heat_batches:
            ids = np.concatenate([i for i, _ in batch])
            w = np.concatenate([np.full(len(i), wt, np.float32) for i, wt in batch])
            heat = ref.heat_scan_ref(heat, jnp.asarray(ids), jnp.asarray(w), leap.tier_heat_decay)
        want = np.asarray(heat)[:n_blocks]
        got = driver.heat_snapshot()
        check(np.allclose(got, want, rtol=1e-5, atol=1e-5),
              f"heat plane differs from heat_scan_ref: max |d| {np.abs(got - want).max()}")
    gb = n_blocks * cfg.block_bytes / GIB
    log(f"pool phase (huge_factor={huge_factor}): {gb:.2f} GiB leapt region 0 -> 1 "
        f"in {ticks} ticks, {wall:.1f} s host wall time incl. compilation; "
        f"committed={p.committed} forced={p.forced} dirty={s.dirty_rejections} "
        f"huge_bytes={s.bytes_copied_huge} demotions={s.demotions}")
    return {"ticks": ticks, "gib": gb}


def serving_phase(seed, *, smoke=False, prompt_lens=(1024, 128), requests=8, tokens=32,
                  expect_tpu=True):
    """granite_3_2b through repro.launch.serve: live KV migration must not
    change a single decoded token."""
    cfg = serve.model_config("granite_3_2b", smoke)
    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, seed))
    log(f"serving phase: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, weights built in "
        f"{time.perf_counter() - t0:.1f} s")
    pcfg = serve.paged_config(smoke, 2, max(prompt_lens) + tokens)
    eng = PagedEngine(cfg, params, pcfg)
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=prompt_lens[i % len(prompt_lens)])
        for i in range(requests)
    ]
    t0 = time.perf_counter()
    sids, _ = serve.serve(eng, prompts, tokens)
    base = [eng.seqs[s].tokens for s in sids]
    log(f"undisturbed run: {requests * tokens} tokens decoded, "
        f"{time.perf_counter() - t0:.1f} s host wall time incl. compilation")
    for s in sids:
        eng.release(s)

    with MegastepRecorder() as rec:
        t0 = time.perf_counter()
        sids, handles = serve.serve(eng, prompts, tokens, rebalance=(0, 1))
        check(eng.drain(), "KV migration did not drain")
        live = [eng.seqs[s].tokens for s in sids]
        log(f"live-migration run: {requests * tokens} tokens decoded, "
            f"{time.perf_counter() - t0:.1f} s host wall time")
        mega = rec.compiled_text(MEGA_COPY)
    check(live == base, "tokens under live KV migration differ from the undisturbed run")
    for i, h in enumerate(handles):
        p = h.progress()
        check(p.committed + p.forced == p.requested > 0, f"rebalance {i}: {p}")
        seq = eng.seqs[sids[i]]
        where = eng.facade.region_of(np.asarray(seq.block_ids, np.int32))
        check((where == seq.region).all(), f"sequence {i} not on region {seq.region}")
        log(f"rebalanced sequence {i}: {p.requested} pages to region {seq.region} "
            f"(committed={p.committed} forced={p.forced})")
    check(eng.driver.verify_mirror(), "host table mirror != device table")
    check_kernel(mega, "megastep", expect_tpu)
    check_kernel(eng.lower_decode(sids).compile().as_text(), "decode step", expect_tpu)

    # Pallas paged decode vs the oracle on the migrated pages.
    table = eng.driver.host_table()
    migrated = [eng.seqs[s] for s in sids[:2]]
    maxb = max(len(s.block_ids) for s in migrated)
    tabs = np.zeros((2, maxb), np.int32)
    for i, s in enumerate(migrated):
        loc = table[np.asarray(s.block_ids)]
        tabs[i, : len(loc)] = loc[:, 0] * pcfg.slots_per_region + loc[:, 1]
    lens = jnp.asarray([s.length for s in migrated], jnp.int32)
    q = jax.random.normal(jax.random.key(seed), (2, cfg.n_heads, cfg.head_dim), cfg.dtype())

    @partial(jax.jit, static_argnames=("impl",))
    def attend(pool, impl=None):  # the flat view is a bitcast only inside jit
        return ops.paged_decode_partial(
            q, flat_pool_view(pool), jnp.asarray(tabs), lens,
            kv_heads=cfg.n_kv_heads, layer=cfg.n_layers - 1, scale=cfg.attn_scale, impl=impl,
        )

    got = attend(eng.driver.state.pool)
    want = attend(eng.driver.state.pool, impl="ref")
    for g, w, name in zip(got, want, ("out", "m", "l")):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(np.allclose(g, w, rtol=2e-2, atol=2e-2),
              f"paged decode {name}: max |pallas - ref| {np.abs(g - w).max()}")
    log("paged decode on the migrated pool matches impl='ref'")
    return {"tokens": 2 * requests * tokens}


def four_chip_phase(seed, *, n_blocks=1024, slots=2048, burst=32, write_ticks=48,
                    expect_tpu=True):
    """Region-per-chip ppermute leap 0 -> 2, compared with one chip (xla)."""
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = jax.make_mesh((4,), ("region",), devices=devs[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    placement = np.zeros(n_blocks, np.int32)

    def run(cfg, leap, mesh):
        driver = MigrationDriver(init_state(cfg, n_blocks, placement, mesh=mesh),
                                 cfg, leap, mesh=mesh)
        session = driver.default_session()
        rng = np.random.default_rng(seed)
        versions = np.zeros(n_blocks, np.int32)

        def write(ids):
            vals = block_values(seed, jnp.asarray(ids), jnp.asarray(versions[ids]), BLOCK)
            driver.write(ids, vals)

        for lo in range(0, n_blocks, 512):
            write(np.arange(lo, min(lo + 512, n_blocks), dtype=np.int32))
        handle = session.leap(np.arange(n_blocks, dtype=np.int32), dst_region=2)
        for _ in range(write_ticks):  # the same write schedule for both runs
            ids = rng.choice(n_blocks, burst, replace=False).astype(np.int32)
            versions[ids] += 1
            write(ids)
            session.tick()
        check(handle.wait(), "leap 0 -> 2 did not resolve")
        p = handle.progress()
        check(p.committed + p.forced == p.requested == n_blocks, f"progress {p}")
        check(driver.verify_mirror(), "host table mirror != device table")
        check((driver.host_placement() == 2).all(), "blocks left outside region 2")
        return driver, verify_contents(driver, seed, versions), p

    cfg4 = PoolConfig(4, slots, BLOCK, jnp.float32, region_axis="region")
    leap4 = LeapConfig(backend="ppermute", axis_name="region")
    driver, sums4, p4 = run(cfg4, leap4, mesh)
    shard_bytes = slots * cfg4.block_bytes
    shards = driver.state.pool.addressable_shards
    check(sorted(s.device.id for s in shards) == sorted(d.id for d in devs[:4]),
          "pool shards are not one per chip")
    for d in devs[:4] if expect_tpu else ():
        in_use = d.memory_stats()["bytes_in_use"]
        check(shard_bytes <= in_use < 2 * shard_bytes,
              f"chip {d.id} holds {in_use} B, its shard is {shard_bytes} B")
    check(driver.stats.dispatches > 0, "no migration program ran")
    log(f"four chips: {n_blocks} blocks leapt region 0 -> 2 via ppermute "
        f"(committed={p4.committed} forced={p4.forced}); one {shard_bytes} B shard per chip")
    del driver, shards
    gc.collect()

    cfg1 = PoolConfig(4, slots, BLOCK, jnp.float32)
    driver, sums1, _ = run(cfg1, LeapConfig(), None)
    check(np.array_equal(sums1, sums4), "ppermute result differs from the one-chip xla run")
    log("four chips: contents equal the one-chip xla run of the same schedule")
    del driver
    gc.collect()


_COMPILE_S: list[float] = []  # backend compile durations, this process


def _note_compile(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S.append(seconds)


def progress(phase: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    log(f"after {phase}: backend compile {sum(_COMPILE_S):.1f} s in "
        f"{len(_COMPILE_S)} programs so far, "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devs[0].platform})", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_note_compile)
    log(f"device: {devs[0].device_kind} x {len(devs)}")
    try:
        if args.chips == 4:
            four_chip_phase(args.seed)
            progress("four-chip phase")
        else:
            pool_phase(args.seed, 6144)  # 3 GiB in region 0, 6 GiB pool
            progress("pool phase")
            gc.collect()
            pool_phase(args.seed, 6144, huge_factor=8)
            progress("huge-tier pool phase")
            gc.collect()
            serving_phase(args.seed)
            progress("serving phase")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
