"""Multi-device tests (8 host devices in a subprocess — the main test
process must keep seeing 1 device, so these run via ``subprocess``).

Covers: sharded leap state + ppermute copy backend correctness on a real
mesh, the batched dispatch generation (the ppermute backend's) against the
megastep on the same schedules, a sharded train step matching the
single-device step, and a mini dry-run (lower+compile with the production
sharding rules on 8 devices).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> str:
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        """
    ) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        # the child never reaches for an accelerator the parent may hold
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"), "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_ppermute_copy_backend_on_mesh():
    run_sub(
        """
        from repro.core import PoolConfig, init_state, leap_write, state_sharding
        from repro.core import migrator

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        cfg = PoolConfig(8, 4, (2, 16), region_axis="data")
        state = init_state(cfg, 16, np.repeat(np.arange(8), 2))
        sh = state_sharding(cfg, mesh)
        state = jax.tree.map(jax.device_put, state, sh)
        rng = np.random.default_rng(0)
        data = rng.standard_normal((16, 2, 16), dtype=np.float32)
        state = leap_write(state, jnp.arange(16), jnp.asarray(data))

        # blocks 0,1 live on region 0; copy them to region 5 slots 2,3
        ids = jnp.asarray([0, 1]); slots = jnp.asarray([2, 3])
        src_slots = jnp.asarray(np.asarray(state.table)[:2, 1])
        state = migrator.begin_areas(state, ids)
        state = migrator.fused_copy_ppermute(state, src_slots, slots, 0, 5, "data", mesh)
        state, verdict = migrator.commit_areas(state, ids, jnp.full(2, 5), slots)
        assert not np.asarray(verdict).any()
        table = np.asarray(state.table)
        assert table[0].tolist() == [5, 2] and table[1].tolist() == [5, 3]
        from repro.core import leap_read
        got = np.asarray(leap_read(state, ids))
        np.testing.assert_array_equal(got, data[:2])
        print("PPERMUTE_OK")
        """
    )


#: One schedule per case, each run twice on a region-sharded 8-region pool:
#: with ``LeapConfig()`` (xla backend: the megastep) and with the ppermute
#: backend (the batched programs).  The child prints one JSON line per case.
BATCHED_VS_MEGASTEP = """
import dataclasses, json
from repro.core import LeapConfig, MigrationDriver, PoolConfig, init_state

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
N = 32
# the MigrationStats counters that telemetry mirrors (tests/test_obs_drift.py)
MIRRORED = ("blocks_requested", "blocks_migrated", "blocks_forced", "blocks_cancelled",
            "bytes_copied", "dispatches", "h2d_transfers", "verdict_prefetches",
            "dirty_rejections", "splits",
            "huge_areas_committed", "demotions", "promotions", "bytes_copied_huge",
            "deferred_congested", "multi_hop_areas")
CASES = {
    # case: (huge_factor, scheduler, LeapConfig fields, write blocks/tick, reads/tick)
    "writes": (1, None, dict(max_attempts_before_force=3), 8, 0),
    "huge": (2, None, dict(), 2, 0),
    "tiering": (1, None, dict(tiering=True), 2, 4),
    "sync": (1, "sync", dict(), 0, 0),
    "drift": (1, None, dict(max_attempts_before_force=3, telemetry=True), 2, 0),
}

def run(case, backend):
    huge, scheduler, fields, n_writes, n_reads = CASES[case]
    cfg = PoolConfig(8, 2 * N, (4,), region_axis="data", huge_factor=huge)
    data = np.random.default_rng(3).normal(size=(N, 4)).astype(np.float32)
    state = init_state(cfg, N, np.zeros(N, np.int32), mesh=mesh)
    leap = LeapConfig(initial_area_blocks=8, budget_blocks_per_tick=8, **fields)
    if backend == "ppermute":
        leap = dataclasses.replace(leap, backend="ppermute", axis_name="data")
    drv = MigrationDriver(state, cfg, leap, scheduler=scheduler, mesh=mesh)
    if huge > 1:
        drv.adopt_huge(np.arange(N // huge))
    drv.write(jnp.arange(N), jnp.asarray(data))
    expected = data.copy()
    session = drv.default_session()
    session.leap(np.arange(N), 5)
    rng = np.random.default_rng(3)
    steps = 0
    while not drv.done and steps < 1000:
        drv.tick()
        # every program the tick dispatched has finished before the next
        # harvest, so both generations harvest each verdict on the same tick
        jax.block_until_ready(drv.state)
        if n_writes:
            ids = rng.choice(N, size=n_writes, replace=False)
            vals = rng.normal(size=(n_writes, 4)).astype(np.float32)
            drv.write(jnp.asarray(ids), jnp.asarray(vals))
            expected[ids] = vals
        if n_reads:
            drv.note_reads(rng.choice(N, size=n_reads))
        steps += 1
    assert session.drain()
    s = drv.stats
    out = {
        "payload": bool(np.array_equal(np.asarray(drv.read(np.arange(N))), expected)),
        "mirror": bool(drv.verify_mirror()),
        "placed": bool((drv.host_placement() == 5).all()),
        "closed": all(r.done and r.committed + r.forced + r.cancelled == r.requested
                      for r in drv.requests.values())
        and s.blocks_migrated + s.blocks_forced + s.blocks_cancelled == s.blocks_requested,
        "table": np.asarray(drv.state.table).tolist(),
        "pool": np.asarray(drv.state.pool).tolist(),
        "stats": {k: getattr(s, k) for k in MIRRORED},
    }
    if leap.tiering:
        out["heat"] = drv.heat_snapshot().tolist()
    if leap.telemetry:
        totals = drv.telemetry.counter_totals()
        out["drift"] = {k: [totals.get(k, 0), getattr(s, k)] for k in MIRRORED}
        out["dropped"] = drv.telemetry.dropped
        # one verdict.sync read per harvested commit batch
        out["harvested"] = sum(1 for ev in drv.telemetry.events()
                               if ev["kind"] == "stage" and ev["name"] == "verdict.sync")
    return out

for case in CASES:
    result = {"case": case, "megastep": run(case, "xla")}
    try:
        result["batched"] = run(case, "ppermute")
    except ValueError as e:  # the ppermute backend refuses a two-tier pool
        result["batched_refused"] = str(e)
    print("CASE " + json.dumps(result))
"""


@pytest.fixture(scope="module")
def batched_vs_megastep():
    out = run_sub(BATCHED_VS_MEGASTEP)
    cases = [json.loads(line[5:]) for line in out.splitlines() if line.startswith("CASE ")]
    return {c["case"]: c for c in cases}


@pytest.mark.parametrize("case", ["writes", "huge", "tiering", "sync", "drift"])
def test_batched_ppermute_matches_megastep(batched_vs_megastep, case):
    """The batched generation runs only on the ppermute backend: on the same
    schedule it must end where the megastep ends, bit for bit."""
    result = batched_vs_megastep[case]
    runs = [result["megastep"]] + ([result["batched"]] if "batched" in result else [])
    for run in runs:
        assert run["payload"], "expected payload not read back"
        assert run["mirror"] and run["placed"]
        assert run["closed"], "committed + forced + cancelled != requested"
    mega = result["megastep"]
    if case == "huge":
        # the huge tier runs under the megastep only: a two-tier pool on the
        # ppermute backend is refused at construction
        assert "two-tier pool requires the xla copy backend" in result["batched_refused"]
        assert mega["stats"]["huge_areas_committed"] > 0
        return
    batched = result["batched"]
    assert batched["table"] == mega["table"]
    assert batched["pool"] == mega["pool"]
    if case == "writes":
        assert mega["stats"]["dirty_rejections"] > 0 and mega["stats"]["blocks_forced"] > 0
        assert mega["stats"]["dispatches"] < batched["stats"]["dispatches"]
    if case == "tiering":
        assert batched["heat"] == mega["heat"] and max(mega["heat"]) > 0
    if case == "sync":
        assert mega["stats"]["blocks_forced"] == batched["stats"]["blocks_forced"] > 0
    if case == "drift":
        for run in (mega, batched):
            for key, (ring, stat) in run["drift"].items():
                assert ring == stat, (key, ring, stat)


@pytest.mark.parametrize("generation", ["megastep", "batched"])
def test_verdict_prefetched_per_harvested_batch_on_mesh(batched_vs_megastep, generation):
    """Both generations start every commit verdict's host copy at dispatch:
    on a region-sharded pool too, one prefetch per harvested batch."""
    run = batched_vs_megastep["drift"][generation]
    assert run["dropped"] == 0
    assert run["stats"]["verdict_prefetches"] == run["harvested"] > 0
    assert run["mirror"] and run["payload"]


def test_sharded_train_step_matches_single_device():
    run_sub(
        """
        import dataclasses
        from repro.configs.base import get_config
        from repro.configs.smoke import reduce
        from repro.distributed.sharding import make_ctx, param_shardings, use_ctx
        from repro.train.optimizer import OptimizerConfig
        from repro.train.train_step import TrainConfig, init_train_state, train_step
        from repro.train.train_step import TrainState

        cfg = dataclasses.replace(reduce(get_config("granite_3_2b")), n_layers=2)
        tcfg = TrainConfig(n_micro=2, optimizer=OptimizerConfig(peak_lr=1e-3))
        state = init_train_state(jax.random.key(0), cfg, tcfg)
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
        }
        # single device reference
        ref_state, ref_metrics = jax.jit(
            lambda s, b: train_step(s, b, cfg, tcfg)
        )(state, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = make_ctx(mesh)
        psh = param_shardings(state.params, mesh, ctx)
        osh = {"m": param_shardings(state.opt["m"], mesh, ctx),
               "v": param_shardings(state.opt["v"], mesh, ctx),
               "step": NamedSharding(mesh, P())}
        ssh = TrainState(params=psh, opt=osh)
        bsh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
        state2 = init_train_state(jax.random.key(0), cfg, tcfg)
        state2 = jax.device_put(state2, ssh)
        batch2 = jax.device_put(batch, bsh)
        with use_ctx(ctx), jax.set_mesh(mesh):
            got_state, got_metrics = jax.jit(
                lambda s, b: train_step(s, b, cfg, tcfg),
                in_shardings=(ssh, bsh),
            )(state2, batch2)
        assert abs(float(got_metrics["loss"]) - float(ref_metrics["loss"])) < 2e-4, (
            float(got_metrics["loss"]), float(ref_metrics["loss"]))
        for a, b in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(got_state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4)
        print("SHARDED_TRAIN_OK")
        """
    )


def test_mini_dryrun_decode_on_mesh():
    run_sub(
        """
        import dataclasses
        from repro.configs.base import get_config
        from repro.configs.smoke import reduce
        from repro.distributed.sharding import make_ctx, param_shardings, use_ctx
        from repro.models import lm

        cfg = dataclasses.replace(reduce(get_config("gemma2_27b")), n_layers=4)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = make_ctx(mesh)
        params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
        psh = param_shardings(params, mesh, ctx, inference=True)
        cache = jax.eval_shape(lambda: lm.init_cache(cfg, 8, 64))
        toks = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        with use_ctx(ctx), jax.set_mesh(mesh):
            compiled = jax.jit(
                lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg),
                in_shardings=(psh, None, None, None),
            ).lower(params, cache, toks, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        assert compiled.cost_analysis() is not None
        print("MINI_DRYRUN_OK")
        """
    )
