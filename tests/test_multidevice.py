"""Multi-device tests (8 host devices in a subprocess — the main test
process must keep seeing 1 device, so these run via ``subprocess``).

Covers: sharded leap state + ppermute copy backend correctness on a real
mesh, a sharded train step matching the single-device step, and a mini
dry-run (lower+compile with the production sharding rules on 8 devices).
"""

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
        state = migrator.begin_area(state, ids)
        state = migrator.copy_chunk_ppermute(state, ids, slots, 0, 5, "data", mesh)
        state, verdict = migrator.commit_area(state, ids, slots, dst_region=5)
        assert not np.asarray(verdict).any()
        table = np.asarray(state.table)
        assert table[0].tolist() == [5, 2] and table[1].tolist() == [5, 3]
        from repro.core import leap_read
        got = np.asarray(leap_read(state, ids))
        np.testing.assert_array_equal(got, data[:2])
        print("PPERMUTE_OK")
        """
    )


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
