"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV and persists each suite's rows as
machine-readable ``BENCH_<suite>.json`` next to the CSV stdout (so the perf
trajectory survives the run).  Run:

    PYTHONPATH=src python -m benchmarks.run [--only fig4_granularity,...]
    PYTHONPATH=src python -m benchmarks.run --only fig5_concurrent.run_huge

``--only`` accepts module names (every entry of that module) and/or specific
``module.function`` entries, comma-separated.
"""

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
import traceback

# Make `python -m benchmarks.run` work without the PYTHONPATH=src
# incantation: resolve the src/ layout ourselves when `repro` isn't already
# importable (an installed or PYTHONPATH'd copy wins).
if importlib.util.find_spec("repro") is None:  # pragma: no cover - env shim
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    )

SUITES = [
    ("fig1_local_remote", "run", {}),
    ("fig2_reshard_vs_copy", "run", {}),
    ("fig4_granularity", "run", {}),
    ("fig5_concurrent", "run", {}),
    ("fig5_concurrent", "run_huge", {}),
    ("fig6_sustained", "run", {}),
    ("fig7_hugepages", "run", {}),
    ("table2_overhead", "run", {}),
    ("fig8_tpch", "run", {}),
    ("fig9_dispatch", "run", {}),
    ("fig10_topology", "run", {}),
    ("fig11_tiering", "run", {}),
    ("serving_rebalance", "run", {}),
    ("serving_slo", "run", {}),
]


def suite_key(mod_name: str, fn_name: str) -> str:
    """Stable identifier for one SUITES entry: ``mod`` or ``mod.fn``."""
    return mod_name if fn_name == "run" else f"{mod_name}.{fn_name}"


def _selected(only: set | None, mod_name: str, fn_name: str) -> bool:
    if only is None:
        return True
    return mod_name in only or suite_key(mod_name, fn_name) in only


def _write_json(
    outdir: str, key: str, rows, elapsed_s: float, ok: bool, telemetry=None
) -> str:
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"BENCH_{key}.json")
    doc = {"suite": key, "ok": ok, "elapsed_s": elapsed_s, "rows": rows}
    if telemetry is not None:
        doc["telemetry"] = telemetry
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return path


def _write_trace(outdir: str, key: str, groups) -> str | None:
    """Write the suite's Perfetto-loadable trace; returns its path (None:
    nothing recorded, or the export failed — traces are best-effort)."""
    if not groups:
        return None
    from repro.obs import write_chrome_trace

    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"TRACE_{key}.json")
    try:
        write_chrome_trace(path, groups, other_data={"suite": key})
    except Exception:
        traceback.print_exc()
        return None
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        type=str,
        default=None,
        help="comma-separated modules (fig5_concurrent) and/or entries "
        "(fig5_concurrent.run_huge)",
    )
    ap.add_argument(
        "--outdir",
        type=str,
        default=".",
        help="directory for the BENCH_<suite>.json result files",
    )
    ap.add_argument(
        "--trace",
        action="store_true",
        help="record pipeline telemetry on every pool: writes a Perfetto-"
        "loadable TRACE_<suite>.json per suite and embeds a telemetry "
        "summary block in each BENCH_<suite>.json (timings under --trace "
        "are for inspection, not the regression gate)",
    )
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only is not None:
        known = {m for m, f, _ in SUITES} | {suite_key(m, f) for m, f, _ in SUITES}
        unknown = only - known
        if unknown:
            print(f"# unknown --only entries: {sorted(unknown)}", file=sys.stderr)
            print(f"# known: {sorted(known)}", file=sys.stderr)
            return 2

    from benchmarks import common

    print("name,us_per_call,derived")
    failures = 0
    ran = 0
    prev_tracing = common.TRACING
    common.TRACING = bool(args.trace)
    try:
        for mod_name, fn_name, kw in SUITES:
            if not _selected(only, mod_name, fn_name):
                continue
            ran += 1
            key = suite_key(mod_name, fn_name)
            start_row = len(common.ROWS)
            start_trace = len(common.TRACE_SESSIONS)
            t0 = time.time()
            ok = True
            try:
                mod = importlib.import_module(f"benchmarks.{mod_name}")
                getattr(mod, fn_name)(**kw)
                print(f"# {mod_name}.{fn_name} done in {time.time() - t0:.1f}s",
                      file=sys.stderr, flush=True)
            except Exception:
                failures += 1
                ok = False
                print(f"# {mod_name}.{fn_name} FAILED", file=sys.stderr)
                traceback.print_exc()
            telemetry = None
            if args.trace:
                groups = common.TRACE_SESSIONS[start_trace:]
                trace_path = _write_trace(args.outdir, key, groups)
                if groups:
                    from repro.obs import summarize

                    telemetry = summarize(groups)
                    telemetry["trace_file"] = trace_path
                if trace_path:
                    print(f"# wrote {trace_path}", file=sys.stderr, flush=True)
            path = _write_json(
                args.outdir, key, common.ROWS[start_row:], time.time() - t0, ok,
                telemetry=telemetry,
            )
            print(f"# wrote {path}", file=sys.stderr, flush=True)
    finally:
        common.TRACING = prev_tracing
        common.TRACE_SESSIONS.clear()
    if only is not None and ran == 0:
        print("# --only matched nothing", file=sys.stderr)
        return 2
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
