"""Reduction of a profiler trace to device busy time, kernel and program time.

A trace is read once into plain lists of events ``(name, start_ns, dur_ns)``:
per device the XLA modules (whole programs) and the XLA ops (the
operations inside them, Pallas kernels among them), and the host's
annotations (the benchmark's own spans).  Everything else works on those
lists, so the tests can build a trace by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

@dataclasses.dataclass
class Device:
    name: str
    modules: list  # events
    ops: list  # events


@dataclasses.dataclass
class Trace:
    devices: list  # [Device]
    host: list  # events: host annotations, every thread


_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def load(logdir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir`` (as written by
    ``jax.profiler.start_trace(logdir)``)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            devices.append(Device(plane.name, modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, host)


def window_of(trace: Trace, name: str = "window") -> tuple[float, float]:
    """(start_ns, end_ns) of the host span ``name`` that bounds the window."""
    spans = [e for e in trace.host if e[0] == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    _, start, dur = max(spans, key=lambda e: e[2])
    return float(start), float(start + dur)


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(dev: Device, lo: float, hi: float) -> list:
    """Intervals in which an operation ran on ``dev`` (modules where the
    trace has no op line)."""
    events = dev.ops or dev.modules
    return union(((s, s + d) for _, s, d in events), lo, hi)


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(dev, lo, hi))


def _inside(events, lo, hi):
    return [e for e in events if lo <= e[1] < hi]


def op_time_ns(dev: Device, pattern: str, lo: float, hi: float) -> tuple[float, int]:
    """Summed duration and count of ops whose own name (not their operands)
    matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in _inside(dev.ops, lo, hi) if rx.search(_stem(e[0]))]
    return float(sum(e[2] for e in hits)), len(hits)


def module_time_ns(dev: Device, pattern: str, lo: float, hi: float) -> tuple[float, int]:
    """Summed duration and count of whole programs whose name matches."""
    rx = re.compile(pattern)
    hits = [e for e in _inside(dev.modules, lo, hi) if rx.search(e[0])]
    return float(sum(e[2] for e in hits)), len(hits)


def _stem(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the op's HLO
    name without its text and its numeric suffix."""
    return re.sub(r"[.:]\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> list:
    """[[op name without its numeric suffix, seconds], ...], largest first."""
    acc: dict[str, float] = {}
    for name, _, d in _inside(dev.ops or dev.modules, lo, hi):
        acc[_stem(name)] = acc.get(_stem(name), 0.0) + d
    return [[k, v * 1e-9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: Device, host: list, labels, lo: float, hi: float, n: int = 10) -> list:
    """Device idle time, each gap attributed to the host span among
    ``labels`` that overlaps it most ("other" where none does).  Returns
    [[label, idle seconds summed over its gaps], ...], largest first."""
    busy = busy_intervals(dev, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    spans = sorted((s, s + d, name) for name, s, d in host if name in labels)
    acc: dict[str, float] = {}
    active, i = [], 0  # spans that start before the gap ends and may reach it
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][0] < g1:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > g0]
        best, label = 0.0, "other"
        for s, e, name in active:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, label = ov, name
        acc[label] = acc.get(label, 0.0) + (g1 - g0)
    return [[k, v * 1e-9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
