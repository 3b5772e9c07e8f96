"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload pool.leap_writes --seed 7 --seconds 20 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix and per-layer metrics are files found by name:

    chipbench/configs/<file named by the configuration>
    chipbench/traffic/<traffic>.json
    chipbench/metrics/<metric name>.py       (a ``read(ctx)`` function)

and the configuration's ``kind`` names the module ``chipbench/kinds/<kind>.py``
that builds and drives the system under test.  Set-up (building data from
the seed, compiling or loading every program the cell uses) is
timed from process start; then the window runs for ``--seconds`` on the
host clock.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` records a profiler trace of the window and reports its per-layer
metrics.  After the window the output is checked against a plain reference;
each number compared is printed with its limit on the last lines of
standard error and under ``checks`` in the result line, which is the last
line of standard output.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402

from chipbench import peaks as peaks_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.spans import CompileCounter, Spans  # noqa: E402

HOST_LABELS = ("tick", "write", "verify")


class NoChip(RuntimeError):
    pass


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(root: Path, spec: dict, workload: str):
    """(workload entry, configuration dict, traffic mix dict)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, mix


def applies(metric: dict, workload: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_reader(root: Path, name: str):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric reader may read: the cell's counts and
    arithmetic (``facts``), the benchmark's host spans, and the reduced trace
    of the window on each chip."""

    def __init__(self, facts, spans, trace, lo, hi, peaks):
        self.facts, self.spans, self.trace = facts, spans, trace
        self.lo, self.hi, self.peaks = lo, hi, peaks

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def devices(self):
        return self.trace.devices


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, log=None) -> dict:
    """Build, warm, measure and check one cell; returns the result line."""
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    spec = load_spec(root)
    cell, cfg, mix = cell_files(root, spec, workload)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < cell["chips"]:
        raise NoChip(f"cell {workload} needs {cell['chips']} chips, JAX found {len(devs)}")
    devs = devs[: cell["chips"]]
    peaks = peaks_mod.peaks_for(devs[0].device_kind) if require_tpu else None

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the cache, so only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    spans = Spans()
    kind = importlib.import_module(f"chipbench.kinds.{cfg['kind']}")
    runner = kind.Cell(cfg, mix, seed, devs, spans, log=log)
    runner.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: {compiles.compiles} backend compiles "
        f"({compiles.compile_s:.1f} s), {compiles.cache_loads} programs from the cache")

    spans.reset()
    before = compiles.snapshot()
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only: the benchmark's annotations
        jax.profiler.start_trace(logdir, profiler_options=options)
    with spans.span("window"):
        measured = runner.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    after = compiles.snapshot()
    log(f"window_compiles={after[0] - before[0]} window_cache_loads={after[1] - before[1]} "
        f"(both should be 0)")

    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}

    metrics, breakdown = {}, None
    if not trace:
        values = dict(measured["e2e"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            if m["name"] in values and applies(m, workload):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        tr = trace_mod.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        lo, hi = trace_mod.window_of(tr)
        busy = [trace_mod.busy_ns(d, lo, hi) for d in tr.devices]
        for d, b in zip(tr.devices, busy):
            log(f"{d.name}: busy {b * 1e-9:.6f} s of {(hi - lo) * 1e-9:.6f} s")
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        ctx = Context(measured["facts"], spans, tr, lo, hi, peaks)
        reported = {m["name"] for m in spec["end_to_end"] if applies(m, workload)}
        for m in spec["per_layer"]:
            if applies(m, workload, reported):
                v = load_reader(root, m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr.devices:
            d0 = tr.devices[0]
            breakdown = {"device_ops": trace_mod.top_ops(d0, lo, hi),
                         "idle_gaps": trace_mod.idle_gaps(d0, tr.host, HOST_LABELS, lo, hi)}

    with spans.span("verify"):
        verdict = runner.check()
    del runner
    gc.collect()

    checks = {name: {"value": v, "limit": lim} for name, v, lim in verdict["checks"]}
    correct = all(v <= lim for _, v, lim in verdict["checks"])
    for name, v, lim in verdict["checks"]:
        log(f"check {name} {v} limit {lim}")
    out = {"correct": correct, "attempted": measured["attempted"],
           "failed": verdict["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
