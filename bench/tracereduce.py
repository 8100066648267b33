"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a small dict of plain
lists, which is also the format of the recorded trace the tests read:

  device: [[line, name, start_ns, dur_ns], ...]  events on the chip's plane
  host:   [[name, start_ns, dur_ns], ...]         the benchmark's own spans

Everything below ``load`` works on that dict alone.
"""
from __future__ import annotations

import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Trace = Dict[str, list]


def load(trace_dir: str, device_plane: str = "/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData

    (path,) = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1:]
    data = ProfileData.from_file(str(path))
    device: list = []
    host: list = []
    for plane in data.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    device += [[line.name, short_name(ev.name),
                                int(ev.start_ns), int(ev.duration_ns)]
                               for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


def short_name(name: str) -> str:
    """An op's name without its HLO text: ``%while.20 = (...) while(...)``
    becomes ``while.20``."""
    return name.split(" = ", 1)[0].lstrip("%")


def window(trace: Trace) -> Tuple[int, int]:
    """(start_ns, end_ns) of the measured window's span."""
    (span,) = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    return span[1], span[1] + span[2]


def _merged(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_intervals(trace: Trace) -> List[Tuple[int, int]]:
    """Union of the intervals in which an op ran on the device, clipped to
    the window."""
    lo, hi = window(trace)
    spans = [(max(s, lo), min(s + d, hi)) for line, _, s, d in trace["device"]
             if line == OPS_LINE and s < hi and s + d > lo]
    return _merged(spans)


def busy_ns(trace: Trace) -> int:
    return sum(e - s for s, e in busy_intervals(trace))


def window_ns(trace: Trace) -> int:
    lo, hi = window(trace)
    return hi - lo


def events_ns(trace: Trace, line: str, contains: str) -> Tuple[int, int]:
    """(total duration, count) of the window's events on ``line`` whose name
    contains ``contains``."""
    lo, hi = window(trace)
    hits = [d for ln, name, s, d in trace["device"]
            if ln == line and contains in name and lo <= s < hi]
    return sum(hits), len(hits)


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` device ops that took the most time in the window, summed by
    name, in seconds."""
    lo, hi = window(trace)
    tot: Dict[str, int] = {}
    for line, name, s, d in trace["device"]:
        if line == OPS_LINE and lo <= s < hi:
            tot[name] = tot.get(name, 0) + d
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _innermost_span(trace: Trace, t: int) -> Optional[str]:
    """The latest-starting benchmark span open at ``t``, window excluded."""
    open_ = [(s, name) for name, s, d in trace["host"]
             if name != WINDOW_SPAN and s <= t < s + d]
    return max(open_)[1] if open_ else None


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` longest stretches of the window with no op on the device,
    each named by what the host was doing at its middle, in seconds."""
    lo, hi = window(trace)
    busy = busy_intervals(trace)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_innermost_span(trace, (s + e) // 2) or "outside any span",
             (e - s) / 1e9] for s, e in gaps[:n]]


def host_outside_ns(trace: Trace, outer: str,
                    inner: Sequence[str]) -> Tuple[int, int]:
    """(host ns inside ``outer`` spans but outside every ``inner`` span,
    number of ``outer`` spans), over the window."""
    lo, hi = window(trace)
    outers = [(s, s + d) for name, s, d in trace["host"]
              if name == outer and lo <= s < hi]
    inners = _merged([(s, s + d) for name, s, d in trace["host"]
                      if name in inner])
    total = 0
    for os_, oe in outers:
        covered = sum(max(0, min(e, oe) - max(s, os_)) for s, e in inners)
        total += (oe - os_) - covered
    return total, len(outers)
