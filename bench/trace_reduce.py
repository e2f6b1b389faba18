"""Reduce a profiler trace to device busy and idle time, time per device
program and per device operation, and idle gaps keyed by what the host
was doing.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes (read with
``jax.profiler.ProfileData``).  Device planes are the ``/device:``
planes; their ``XLA Ops`` line holds one event per executed operation
(kernels included) and their ``XLA Modules`` line one event per executed
program.  Host spans are the events named ``bench.*`` that the harness
writes with ``jax.profiler.TraceAnnotation``; ``bench.window`` bounds the
measured window and every other ``bench.*`` span names a host activity.

busy      union of the operation intervals inside the window, per chip,
          averaged over chips
idle gap  an interval of the window in which no operation runs on a chip,
          attributed to the innermost host span around its midpoint
op time   summed per short operation name (``op_name``); control-flow
          operations that enclose others (``while``) are left out of the
          sums, not out of busy time
events    on the first chip, each program run and each Pallas kernel
          that lies whole inside the window, in seconds from its start
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")
KERNEL = "tpu_custom_call"          # a Pallas (Mosaic) kernel launch
CONTAINERS = ("while", "conditional", "call")   # enclose other op events

Interval = Tuple[int, int]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                   # mean over chips
    n_chips: int
    modules_s: Dict[str, float] = field(default_factory=dict)  # all chips
    ops_s: Dict[str, float] = field(default_factory=dict)      # all chips
    gaps_by_host_s: Dict[str, float] = field(default_factory=dict)
    module_events: List[Tuple[str, float, float]] = field(
        default_factory=list)                       # (program, start, end)
    kernel_events: List[Tuple[float, float]] = field(default_factory=list)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps_by_host_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def module_time(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.modules_s.items() if rx.search(k))

    def op_time(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.ops_s.items() if rx.search(k))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(text: str) -> str:
    """Short name of a device operation event: the HLO instruction name
    without ``%`` and numeric suffixes, or ``tpu_custom_call`` for a
    Pallas kernel (the event text is the whole HLO instruction)."""
    if f'custom_call_target="{KERNEL}"' in text:
        return KERNEL
    m = _INSTR.match(text)
    return m.group(1) if m else text[:64]


def _events(plane, line_name: Optional[str]):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def _host_spans(planes) -> List[Tuple[int, int, str]]:
    spans = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for ev in _events(p, None):
            if ev.name.startswith(HOST_PREFIX):
                spans.append((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                              ev.name))
    return spans


def _activity(spans, t: int) -> str:
    best, width = "none", None
    for s, e, name in spans:
        if name != WINDOW_SPAN and s <= t < e and (width is None or e - s < width):
            best, width = name, e - s
    return best


def reduce_profile(profile) -> TraceSummary:
    """Summarize a ``jax.profiler.ProfileData`` of one traced window."""
    planes = list(profile.planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    devices = [p for p in planes if p.name.startswith("/device:")
               and any(l.name == OPS_LINE for l in p.lines)]
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    by_host: Dict[str, float] = {}
    module_events: List[Tuple[str, float, float]] = []
    kernel_events: List[Tuple[float, float]] = []
    busy_total = 0.0
    for chip, p in enumerate(devices):
        ivs = []
        for ev in _events(p, OPS_LINE):
            s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            if e <= lo or s >= hi:
                continue
            ivs.append((s, e))
            name = op_name(ev.name)
            if name in CONTAINERS:
                continue
            cs, ce = max(s, lo), min(e, hi)
            ops[name] = ops.get(name, 0.0) + (ce - cs) * 1e-9
            if chip == 0 and name == KERNEL and (cs, ce) == (s, e):
                kernel_events.append(((s - lo) * 1e-9, (e - lo) * 1e-9))
        for ev in _events(p, MODULES_LINE):
            s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            if e <= lo or s >= hi:
                continue
            name = _SUFFIX.sub("", ev.name)
            modules[name] = modules.get(name, 0.0) + \
                (min(e, hi) - max(s, lo)) * 1e-9
            if chip == 0 and lo <= s and e <= hi:
                module_events.append((name, (s - lo) * 1e-9,
                                      (e - lo) * 1e-9))
        busy = union(clip(ivs, lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e in gaps(busy, lo, hi):
            act = _activity(spans, (s + e) // 2)
            by_host[act] = by_host.get(act, 0.0) + (e - s) * 1e-9
    n = max(len(devices), 1)
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                        n_chips=len(devices), modules_s=modules, ops_s=ops,
                        gaps_by_host_s={k: v / n for k, v in by_host.items()},
                        module_events=sorted(module_events,
                                             key=lambda m: m[1]),
                        kernel_events=sorted(kernel_events))


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
