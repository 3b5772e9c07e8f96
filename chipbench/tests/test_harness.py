"""The harness finds configurations, mixes and metrics by name, and refuses
to measure without a chip."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import run
from conftest import ROOT, make_tiny_root


def test_new_config_mix_and_metric_run_as_added_files(tmp_path):
    root = make_tiny_root(tmp_path)
    cb = root / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    conf = json.loads((cb / "configs" / "tiny_pool.json").read_text())
    conf["resident_blocks"] = [16, 16]
    (cb / "configs" / "dummy_pool.json").write_text(json.dumps(conf))
    (cb / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"burst_blocks": 2, "hot_share": 0.5,
         "hot_fraction": 0.25, "leaps": [[0, 1], [1, 0]]}))
    (cb / "metrics" / "dummy_ticks.py").write_text(
        "def read(ctx):\n    return float(ctx.facts['ticks'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy_pool", "source": "test", "reduced": [], "why": "test",
                            "file": "chipbench/configs/dummy_pool.json"})
    spec["workloads"].append({"name": "dummy.cell", "config": "dummy_pool",
                              "traffic": "dummy_mix", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "migrate_gib_s":
            m["workloads"].append("dummy.cell")
    spec["per_layer"].append({"name": "dummy_ticks", "unit": "ticks", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "migrate_gib_s", "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    e2e = run.run_cell(root, "dummy.cell", 5, 0.5, False, require_tpu=False)
    assert e2e["correct"] and set(e2e["metrics"]) == {"migrate_gib_s", "setup_s"}
    assert e2e["metrics"]["migrate_gib_s"]["value"] > 0
    traced = run.run_cell(root, "dummy.cell", 5, 0.5, True, require_tpu=False)
    assert traced["correct"] and traced["metrics"]["dummy_ticks"]["value"] > 0
    assert list(traced)[-1] == "checks" and "window_s" in traced["device"]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_cell_of_the_benchmark_names_existing_files():
    spec = run.load_spec(ROOT)
    for w in spec["workloads"]:
        cell, cfg, mix = run.cell_files(ROOT, spec, w["name"])
        assert (ROOT / "chipbench" / "kinds" / f"{cfg['kind']}.py").exists()
    for m in spec["per_layer"]:
        assert callable(run.load_reader(ROOT, m["name"]))


def _bench(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "pool.leap_writes", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    out = _bench(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _bench(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout == ""
