"""Reduce a JAX profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` a ``jax.profiler`` trace writes.  Device planes
are ``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds one event per
operation run on the device (named here by its HLO instruction,
``%fusion.3``) and the ``XLA Modules`` line one per program
(``jit_range_search(...)`` and so on).  Host threads are on ``/host:CPU``,
where the benchmark's own ``TraceAnnotation``s appear by name.  Host and
device events share one timeline.

The traced window is the benchmark's ``bench.window`` annotation.  Within
it:

* busy: the union of the device's op intervals (overlaps counted once),
  averaged over the chips used; idle is the rest of the window;
* module time: the summed device durations of each program's events;
* top ops: the device operations that took most time, summed by name;
* idle gaps: the longest stretches with no op on the device, each named
  by the benchmark annotation that covers most of it on the host.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
UNANNOTATED = "host:unannotated"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


@dataclass
class Trace:
    """Events as (name, start_ns, end_ns) tuples."""
    device_ops: dict = field(default_factory=dict)      # chip -> [events]
    device_modules: dict = field(default_factory=dict)  # chip -> [events]
    host: list = field(default_factory=list)            # annotations


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns))
                   for e in line.events]
            if m:
                chip = int(m.group(2))
                if line.name == "XLA Ops":
                    # "%fusion.3 = f32[...] fusion(...)" -> "%fusion.3"
                    tr.device_ops.setdefault(chip, []).extend(
                        (name.split(" = ")[0], s, e) for name, s, e in evs)
                elif line.name == "XLA Modules":
                    tr.device_modules.setdefault(chip, []).extend(evs)
            elif plane.name.startswith("/host:"):
                tr.host.extend(e for e in evs if e[0].startswith("bench."))
    return tr


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals; touching or overlapping ones join."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window(tr: Trace) -> tuple[float, float]:
    spans = [(s, e) for name, s, e in tr.host if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return spans[0]


def _module_name(name: str) -> str:
    """``jit_range_search(12)`` -> ``range_search``."""
    base = name.split("(")[0]
    return base[len("jit_"):] if base.startswith("jit_") else base


def reduce(tr: Trace, top: int = 10) -> dict:
    """The window's device numbers (seconds); None for a trace with no
    device plane (a CPU run)."""
    if not tr.device_ops:
        return None
    lo, hi = window(tr)
    win_s = (hi - lo) / 1e9
    chips = sorted(tr.device_ops)
    busy_per_chip, gaps_all = [], []
    for c in chips:
        busy = union(clip([(s, e) for _, s, e in tr.device_ops[c]], lo, hi))
        busy_per_chip.append(sum(e - s for s, e in busy) / 1e9)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    busy_s = sum(busy_per_chip) / len(chips)
    ops = defaultdict(float)
    for c in chips:
        for name, s, e in tr.device_ops[c]:
            if s >= lo and e <= hi:
                ops[name] += (e - s) / 1e9
    modules = defaultdict(float)
    module_calls = defaultdict(int)
    for c in sorted(tr.device_modules):
        for name, s, e in tr.device_modules[c]:
            if s >= lo and e <= hi:
                key = _module_name(name)
                modules[key] += (e - s) / 1e9 / len(chips)
                module_calls[key] += 1
    host = [(name, s, e) for name, s, e in tr.host if name != WINDOW]
    gaps = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]:
        cover = defaultdict(float)
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[name] += ov
        label = max(cover, key=cover.get) if cover else UNANNOTATED
        gaps.append([label, (e - s) / 1e9])
    return {
        "window_s": win_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / win_s),
        "modules": dict(modules),
        "module_calls": dict(module_calls),
        "top_ops": sorted(([k, v] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        "idle_gaps": gaps,
    }
