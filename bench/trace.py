"""Reduce a JAX profiler trace to what the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes are
the ``/device:TPU:<n>`` planes; on each, the ``XLA Ops`` line holds one
event per operation the chip ran (all lines but ``Steps`` and ``XLA
Modules`` where that line is missing), and the ``XLA Modules`` line one
event per program launch. Host planes (``/host:...``) hold the host's
spans, the benchmark's own ``bench/...`` annotations among them.

:func:`summarize` returns a :class:`TraceSummary`: per device the busy time
(the union of its operation intervals inside the window), the operations'
total times by name, the program launches by module name, and the idle
gaps between busy intervals, each named by the host span that covers most
of it. Readers in ``bench/metrics`` pick kernels and collectives out of
the operations by name.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float           # seconds
    dur: float             # seconds
    text: str = ""         # the event's string stats, for name matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceSummary:
    window: Interval
    devices: int
    busy_s: List[float]                       # per device
    ops: List[Event] = field(default_factory=list)      # device ops
    modules: List[Event] = field(default_factory=list)  # program launches
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    def op_seconds(self, pattern: str) -> float:
        """Total device seconds, over all devices, of the operations whose
        name or stats match ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        return sum(e.dur for e in self.ops
                   if rx.search(e.name) or rx.search(e.text))

    def module_events(self, pattern: str) -> List[Event]:
        rx = re.compile(pattern)
        return [e for e in self.modules if rx.search(e.name)]

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        total: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            total[e.name] += e.dur
        return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def union_seconds(intervals: Iterable[Interval]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_intervals(events: Iterable[Event], window: Interval
                   ) -> List[Interval]:
    lo, hi = window
    merged: List[List[float]] = []
    for s, e in sorted((max(ev.start, lo), min(ev.end, hi))
                       for ev in events):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def name_gap(gap: Interval, host: List[Event]) -> str:
    """The host span that best explains an idle gap: among the spans that
    cover at least half of it, the shortest; else the one that overlaps it
    most; else ``"(no host span)"``."""
    s, e = gap
    length = e - s
    best, best_key = "(no host span)", None
    for ev in host:
        ov = min(e, ev.end) - max(s, ev.start)
        if ov <= 0:
            continue
        key = (0, ev.dur) if ov >= 0.5 * length else (1, -ov)
        if best_key is None or key < best_key:
            best, best_key = ev.name, key
    return best


def _stat_text(ev) -> str:
    parts = []
    for st in getattr(ev, "stats", ()):
        try:
            _, value = st
        except (TypeError, ValueError):
            continue
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def load_events(path: str):
    """(device op lines, device module lines, host events) from an
    ``.xplane.pb`` file; each device entry is a list of :class:`Event`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    host: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                op_lines = [lines["XLA Ops"]]
            else:
                op_lines = [ln for name, ln in lines.items()
                            if name not in ("Steps", "XLA Modules")]
            ops.append([Event(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9, _stat_text(ev))
                        for ln in op_lines for ev in ln.events])
            modules.append([Event(ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9)
                            for ln in plane.lines if ln.name == "XLA Modules"
                            for ev in ln.events])
        elif plane.name.startswith("/host:"):
            host.extend(Event(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                        for ln in plane.lines for ev in ln.events)
    return ops, modules, host


def summarize(ops: List[List[Event]], modules: List[List[Event]],
              host: List[Event], window_span: str = "bench/window",
              devices: Optional[int] = None) -> TraceSummary:
    """Reduce per-device events to a :class:`TraceSummary` over the window
    that the host span named ``window_span`` covers (the whole trace where
    there is no such span). ``devices`` keeps the first that many device
    planes (the chips the cell uses)."""
    if devices is not None:
        ops, modules = ops[:devices], modules[:devices]
    spans = [e for e in host if e.name == window_span]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:
        every = [e for dev in ops for e in dev] + host
        window = (min(e.start for e in every), max(e.end for e in every))
    inside = lambda e: e.end > window[0] and e.start < window[1]
    busy, gaps, all_ops, all_mods = [], [], [], []
    host_inner = [e for e in host if e.name != window_span and inside(e)]
    for dev_ops, dev_mods in zip(ops, modules):
        dev_ops = [e for e in dev_ops if inside(e)]
        ivs = busy_intervals(dev_ops, window)
        busy.append(sum(e - s for s, e in ivs))
        gaps.extend(idle_gaps(ivs, window))
        all_ops.extend(dev_ops)
        all_mods.extend(e for e in dev_mods if inside(e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(name_gap(g, host_inner), g[1] - g[0]) for g in gaps[:10]]
    return TraceSummary(window, len(busy), busy, all_ops, all_mods, named)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
