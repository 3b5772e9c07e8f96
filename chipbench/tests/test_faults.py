"""Whole runs with the timed path broken underneath: each must come out
``correct: false``.

The faults run the tiny cells of ``conftest`` on the CPU.  The control, the
engine with dirty tracking switched off so that a write racing a copy is
lost at the commit, runs there too, and at a cell's own size on the chip:

    PYTHONPATH=src:. python -m pytest chipbench/tests/test_faults.py -k control_without \
        --cell pool.leap_writes
"""

import dataclasses
import json
import os
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.driver as driver_mod
import repro.core.migrator as migrator
from chipbench import run
from conftest import ROOT
from repro.core.state import REGION, SLOT


@partial(jax.jit, donate_argnames=("state",))
def write_without_dirty_tracking(state, block_ids, values):
    """``leap_write`` without ``dirty |= in_flight``: the guarantee that no
    write is lost rests on that one line."""
    loc = state.table[block_ids]
    pool = state.pool.at[loc[:, REGION], loc[:, SLOT]].set(values.astype(state.pool.dtype))
    return dataclasses.replace(state, pool=pool)


def _run(root, workload, seed=21, seconds=1.0, on_chip=False):
    return run.run_cell(root, workload, seed, seconds, False, require_tpu=on_chip)


def test_sound_run_is_correct(tiny_root):
    assert _run(tiny_root, "pool.tiny")["correct"]


def test_pool_megastep_that_returns_its_state_unchanged(tiny_root, monkeypatch):
    real = migrator.megastep

    def unchanged(state, *args, **kwargs):
        # the step runs on a copy made through the host, which no program
        # can alias to the state it hands back
        out = real(jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), state), *args, **kwargs)
        return (state,) + tuple(out[1:])

    monkeypatch.setattr(migrator, "megastep", unchanged)
    out = _run(tiny_root, "pool.tiny")
    assert not out["correct"]


def test_pool_write_altered_where_it_is_made(tiny_root, monkeypatch):
    real = driver_mod.leap_write
    calls = {"n": 0}

    def altered(state, ids, values):
        calls["n"] += 1
        if calls["n"] > 40:  # every burst after the first few
            values = values.at[0, 0, 0].add(1.0)
        return real(state, ids, values)

    monkeypatch.setattr(driver_mod, "leap_write", altered)
    out = _run(tiny_root, "pool.tiny")
    assert not out["correct"]
    assert out["checks"]["blocks_not_as_written"]["value"] >= 1


@pytest.fixture
def control_cell(request, tiny_root):
    """(root, cell, on the chip, seconds): the cell named by ``--cell`` at its
    own size on the chip, else the tiny one-chip cell on the CPU."""
    cell = request.config.getoption("--cell")
    if cell:
        return ROOT, cell, True, 5.0
    return tiny_root, "pool.tiny", False, 1.0


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_without_dirty_tracking_loses_writes(control_cell, seed, monkeypatch):
    root, cell, on_chip, seconds = control_cell
    monkeypatch.setattr(driver_mod, "leap_write", write_without_dirty_tracking)
    out = _run(root, cell, seed, seconds, on_chip)
    print(json.dumps({"cell": cell, "seed": seed, "control": True, "correct": out["correct"],
                      "checks": out["checks"], "device": out["device"]}))
    assert not out["correct"]
    assert out["checks"]["blocks_not_as_written"]["value"] > 0


FOUR_DEVICES = """
import json, sys
from pathlib import Path
sys.path[:0] = [{tests!r}]
import conftest
import jax
import repro.core.driver as driver_mod
import repro.core.migrator as migrator
import test_faults
from chipbench import run
root = conftest.make_tiny_root(Path({tmp!r}))
if {fault!r} == "exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: x  # the exchange between chips left out
elif {fault!r} == "copy_unchanged":
    migrator.fused_copy_ppermute = lambda state, *a, **k: state  # the copy step does nothing
elif {fault!r} == "control":
    driver_mod.leap_write = test_faults.write_without_dirty_tracking
print(json.dumps(run.run_cell(root, "pool4.tiny", 9, 1.0, False, require_tpu=False)))
"""


@pytest.mark.parametrize("fault", [None, "exchange", "copy_unchanged", "control"])
def test_four_chip_faults(tmp_path, fault):
    """Four virtual devices: the ppermute leap is correct, and with its
    exchange between chips left out, its copy step doing nothing, or dirty
    tracking off it is not."""
    code = FOUR_DEVICES.format(tests=str(ROOT / "chipbench" / "tests"), tmp=str(tmp_path),
                               fault=fault)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault is None)
    if fault is None:  # a fresh process: set-up warmed every program the window runs
        assert "window_compiles=0 window_cache_loads=0" in out.stderr
    else:
        assert result["checks"]["blocks_not_as_written"]["value"] > 0
