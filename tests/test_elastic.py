"""Elasticity: a checkpoint written under one mesh restores onto a different
mesh (shrink/grow) bit-exactly — the restart path after node failure.

Runs in subprocesses (8 host devices) so the main process keeps 1 device.
"""

import os
import subprocess
import sys
import textwrap

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
        capture_output=True, text=True, timeout=600,
        # the child never reaches for an accelerator the parent may hold
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"), "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_checkpoint_restores_across_mesh_shapes(tmp_path):
    run_sub(
        f"""
        import dataclasses
        from repro.checkpoint import ckpt
        from repro.configs.base import get_config
        from repro.configs.smoke import reduce
        from repro.distributed.sharding import make_ctx, param_shardings
        from repro.train.optimizer import OptimizerConfig
        from repro.train.train_step import TrainConfig, TrainState, init_train_state

        cfg = dataclasses.replace(reduce(get_config("qwen2_7b")), n_layers=2)
        tcfg = TrainConfig(optimizer=OptimizerConfig())
        state = init_train_state(jax.random.key(0), cfg, tcfg)

        # save under an 8-way (4 data x 2 model) mesh
        mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                               axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx_a = make_ctx(mesh_a)
        sh_a = TrainState(
            params=param_shardings(state.params, mesh_a, ctx_a),
            opt={{"m": param_shardings(state.opt["m"], mesh_a, ctx_a),
                 "v": param_shardings(state.opt["v"], mesh_a, ctx_a),
                 "step": NamedSharding(mesh_a, P())}},
        )
        state_a = jax.device_put(state, sh_a)
        ckpt.save({str(tmp_path)!r}, 7, state_a)

        # restore onto a *different* mesh: 2 data x 4 model (elastic remesh)
        mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                               axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx_b = make_ctx(mesh_b)
        template = jax.eval_shape(lambda: init_train_state(jax.random.key(0), cfg, tcfg))
        host, step = ckpt.restore({str(tmp_path)!r}, template)
        assert step == 7
        sh_b = TrainState(
            params=param_shardings(host.params, mesh_b, ctx_b),
            opt={{"m": param_shardings(host.opt["m"], mesh_b, ctx_b),
                 "v": param_shardings(host.opt["v"], mesh_b, ctx_b),
                 "step": NamedSharding(mesh_b, P())}},
        )
        state_b = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s), host, sh_b
        )
        # bit-exact across the remesh
        for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the restored state steps fine on the new mesh
        from repro.train.train_step import train_step
        from repro.distributed.sharding import use_ctx
        rng = np.random.default_rng(0)
        batch = {{
            "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
        }}
        with use_ctx(ctx_b), jax.set_mesh(mesh_b):
            s2, metrics = jax.jit(lambda s, b: train_step(s, b, cfg, tcfg))(state_b, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("ELASTIC_OK")
        """
    )
