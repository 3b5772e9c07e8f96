"""Fixtures of the benchmark's CPU tests: a copy of the benchmark with tiny
configurations beside the real ones, so whole runs fit a test.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_POOL = dict(slots_per_region=64, block_shape=[8, 128], resident_blocks=[48, 0],
                 leap={"initial_area_blocks": 8, "budget_blocks_per_tick": 8})
TINY_POOL4 = dict(slots_per_region=32, block_shape=[8, 128], resident_blocks=[24, 24, 0, 0],
                  leap={"backend": "ppermute", "axis_name": "region",
                        "initial_area_blocks": 8, "budget_blocks_per_tick": 8})


def pytest_addoption(parser):
    parser.addoption("--cell", default=None,
                     help="run the control at this benchmark cell's own size on the chip")


def make_tiny_root(dst: Path) -> Path:
    """A benchmark root holding a copy of ``chipbench`` and tiny cells
    ``pool.tiny`` and (on four devices) ``pool4.tiny``."""
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cb = dst / "chipbench"

    def derive(src, out, **changes):
        d = json.loads((cb / src).read_text())
        d.update(changes)
        (cb / out).write_text(json.dumps(d))

    derive("configs/pool_6g_512k.json", "configs/tiny_pool.json", **TINY_POOL)
    derive("configs/pool4_region_per_chip.json", "configs/tiny_pool4.json", **TINY_POOL4)
    derive("traffic/leap_writes.json", "traffic/tiny_writes.json", burst_blocks=4)
    derive("traffic/leap_ici.json", "traffic/tiny_ici.json", burst_blocks=4)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] += [
        {"name": "tiny_pool", "source": "test", "file": "chipbench/configs/tiny_pool.json",
         "reduced": [], "why": "test"},
        {"name": "tiny_pool4", "source": "test", "file": "chipbench/configs/tiny_pool4.json",
         "reduced": [], "why": "test"},
    ]
    spec["workloads"] += [
        {"name": "pool.tiny", "config": "tiny_pool", "traffic": "tiny_writes", "chips": 1,
         "why": "test"},
        {"name": "pool4.tiny", "config": "tiny_pool4", "traffic": "tiny_ici", "chips": 4,
         "why": "test"},
    ]
    for m in spec["end_to_end"] + spec["per_layer"]:
        for real, tiny in (("pool.leap_writes", "pool.tiny"), ("pool4.leap_ici", "pool4.tiny")):
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench"))
