"""The phases of ``chip_smoke.py`` at tiny sizes on the CPU.

On the CPU the kernels dispatch to their jnp oracles, so these runs check
the control flow and the result checks of the script, not the kernels
(tests/test_tpu_compile.py compiles those for a described v5e).
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("huge_factor", [1, 8])
def test_pool_phase(smoke, huge_factor):
    out = smoke.pool_phase(3, 64, huge_factor=huge_factor, burst=8, reads=16,
                           expect_tpu=False)
    assert out["ticks"] > 0


def test_serving_phase(smoke):
    out = smoke.serving_phase(1, smoke=True, prompt_lens=(13, 5), requests=4,
                              tokens=6, expect_tpu=False)
    assert out["tokens"] == 48


def test_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


def _run(code, env_extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_four_chip_phase_on_virtual_devices():
    out = _run(
        "import chip_smoke\n"
        "chip_smoke.four_chip_phase(2, n_blocks=32, slots=64, burst=4,"
        " write_ticks=6, expect_tpu=False)\n"
        "print('phase ok')\n",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "phase ok" in out.stdout


def test_script_refuses_to_run_without_a_tpu():
    out = _run("import chip_smoke, sys; sys.exit(chip_smoke.main([]))", {})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
