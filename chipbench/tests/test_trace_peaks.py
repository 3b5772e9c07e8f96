"""The trace reduction and the roofline arithmetic, on traces built by hand
and on a short trace recorded on a TPU v5e."""

import gzip
import json
from pathlib import Path

import pytest

from chipbench import flops, peaks, trace

MS = 1_000_000  # ns


def _dev(ops, modules=()):
    return trace.Device("/device:TPU:0", list(modules), list(ops))


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    d = _dev([("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS), ("c", 30 * MS, 5 * MS),
              ("d", 95 * MS, 10 * MS)])
    assert trace.busy_intervals(d, 0, 100 * MS) == [[0, 15 * MS], [30 * MS, 35 * MS],
                                                    [95 * MS, 100 * MS]]
    assert trace.busy_ns(d, 0, 100 * MS) == 25 * MS


def test_kernel_and_program_sums_count_only_matching_events_inside_the_window():
    d = _dev([("%leap_copy_blocks.1 = f32[8] custom-call(s32[4] %copy_src)", 10, 100),
              ("leap_copy_blocks.2", 200, 50),
              ("%fusion.3 = f32[8] fusion(f32[8] %leap_copy_blocks.1)", 300, 70),
              ("leap_copy_blocks.4", 5000, 1)],
             [("jit_megastep(12)", 0, 400), ("jit_other", 500, 9), ("jit_megastep(12)", 900, 60)])
    assert trace.op_time_ns(d, "leap_copy_blocks", 0, 1000) == (150.0, 2)
    assert trace.module_time_ns(d, "megastep", 0, 1000) == (460.0, 2)
    name, seconds = trace.top_ops(d, 0, 1000)[0]
    assert name == "leap_copy_blocks" and seconds == pytest.approx(150e-9)


def test_idle_gaps_are_labelled_by_the_host_span_that_covers_them_most():
    d = _dev([("x", 0, 10), ("y", 50, 10)])
    host = [("tick", 8, 30), ("write", 38, 10), ("window", 0, 100)]
    gaps = dict(map(tuple, trace.idle_gaps(d, host, ("tick", "write"), 0, 100)))
    assert gaps == {"tick": 40e-9, "other": 40e-9}


def test_roofline_share_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    nbytes = flops.copy_bytes(819e9 / 2)  # 819e9 bytes of traffic: 1 s at the HBM peak
    assert peaks.roofline_share(0.0, nbytes, 2.0, p) == pytest.approx(50.0)
    assert peaks.roofline_share(197e12, 0.0, 4.0, p) == pytest.approx(25.0)
    assert peaks.roofline_share(1.0, 1.0, 0.0, p) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


RECORDED = Path(__file__).parent / "data" / "pool_trace.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_pool_trace_has_the_program_and_kernel_names():
    with gzip.open(RECORDED, "rt") as f:
        data = json.load(f)
    dev = trace.Device(data["devices"][0]["name"],
                       [tuple(e) for e in data["devices"][0]["modules"]],
                       [tuple(e) for e in data["devices"][0]["ops"]])
    tr = trace.Trace([dev], [tuple(e) for e in data["host"]])
    lo = trace.window_of(tr)[0]
    hi = max(e[1] + e[2] for e in dev.ops)
    ns, n = trace.module_time_ns(dev, "megastep", lo, hi)
    assert n > 0 and ns > 0
    kns, kn = trace.op_time_ns(dev, "leap_copy_blocks", lo, hi)
    assert kn > 0 and 0 < kns < ns
    busy = trace.busy_ns(dev, lo, hi)
    assert 0 < busy < hi - lo
    assert trace.idle_gaps(dev, tr.host, ("tick", "write"), lo, hi)
