"""The training feed's spans in a traced window, for the ``feed_*``,
``pool_*``, ``step_dispatch_ms`` and ``idle_on_*`` readers.

The program opens these spans on the host plane of the profiler's trace,
on the device operations' clock (``repro/core/pipeline.timed``):

* ``feed/pool_wait``: the prefetch thread waiting for its next item from
  the sampling service; ``feed/decode``, the ring decode and CRC check it
  runs meanwhile, nests inside;
* ``feed/assemble``: the prefetch thread assembling one iteration; with no
  sampling service ``feed/sample`` and ``feed/layout`` nest inside;
* ``feed/stages``: a zero-length span per delivered batch whose arguments
  ``sample_s``, ``layout_s`` and ``ship_s`` are the worker's stage seconds;
* ``step/dispatch``: the main thread handing one step to the device.

:class:`TraceSummary` keeps no host spans, so :func:`load` reads them again
from the newest ``.xplane.pb`` of the configuration's cells under
``bench/.cache/trace`` and holds to it only if its ``bench/window`` span is
the summary's window. A trace without
the spans (a program that has none) gives readers nothing to read.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from bench import trace as tr

TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache",
                      "trace")
STAGES = ("sample", "layout", "ship")
# the prefetch thread's own work: the ring decode and the assembly
OWN_WORK = ("feed/decode", "feed/assemble")
# stage spans of sampling run in the training process
IN_PROCESS = tuple(f"feed/{s}" for s in STAGES)
PROGRAM_SPAN = re.compile(r"^(feed|step)/")


@dataclass
class Span:
    name: str
    start: float
    end: float
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class FeedTrace:
    window: tr.Interval
    spans: List[Span]                # program spans inside the window
    busy: List[List[tr.Interval]]    # per device, merged, inside the window

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def seconds(self, name: str) -> float:
        return sum(s.dur for s in self.named(name))

    def nested_seconds(self, inner: Sequence[str], outer: str) -> float:
        """Seconds of the spans named in ``inner`` that lie inside a span
        named ``outer``."""
        outs = sorted((s.start, s.end) for s in self.named(outer))
        starts = [s for s, _ in outs]
        total = 0.0
        for s in self.spans:
            if s.name not in inner:
                continue
            k = bisect.bisect_right(starts, s.start) - 1
            if k >= 0 and s.end <= outs[k][1]:
                total += s.dur
        return total

    def stage_seconds(self, stage: str) -> float:
        """A sampling stage's seconds: the workers' (``feed/stages``
        arguments) and those run in this process (``feed/<stage>``)."""
        return (sum(float(s.args.get(f"{stage}_s", 0.0))
                    for s in self.named("feed/stages"))
                + self.seconds(f"feed/{stage}"))

    def own_work_seconds(self) -> float:
        """The prefetch thread's own work: decode and assembly, less the
        sampling stages that ran inside the assembly."""
        return (sum(self.seconds(n) for n in OWN_WORK)
                - self.nested_seconds(IN_PROCESS, "feed/assemble"))

    def pool_wait_self_seconds(self) -> float:
        """``feed/pool_wait`` less the work nested inside it."""
        return (self.seconds("feed/pool_wait")
                - self.nested_seconds(("feed/decode",) + IN_PROCESS,
                                      "feed/pool_wait"))

    def idle_seconds(self) -> float:
        return sum(tr.union_seconds(tr.idle_gaps(b, self.window))
                   for b in self.busy)

    def idle_inside(self, names: Sequence[str],
                    minus: Sequence[str] = ()) -> float:
        """Device-idle seconds, summed over devices, inside a span named
        in ``names`` and outside every span named in ``minus``."""
        inside = tr.busy_intervals(
            [s for s in self.spans if s.name in names], self.window)
        outside = tr.busy_intervals(
            [s for s in self.spans if s.name in minus], self.window)
        total = 0.0
        for b in self.busy:
            idle_in = _intersect(tr.idle_gaps(b, self.window), inside)
            total += (tr.union_seconds(idle_in)
                      - tr.union_seconds(_intersect(idle_in, outside)))
        return total


def _intersect(a: List[tr.Interval], b: List[tr.Interval]
               ) -> List[tr.Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def newest_xplane(root: str = TRACES, config: str = "*") -> Optional[str]:
    """The newest ``.xplane.pb`` of a cell of ``config``."""
    found = glob.glob(os.path.join(root, f"{config}.*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def from_events(ops: List[List[tr.Event]], host: List[Span],
                window: tr.Interval) -> FeedTrace:
    inside = [s for s in host if PROGRAM_SPAN.match(s.name)
              and s.start >= window[0] and s.start < window[1]]
    busy = [tr.busy_intervals(dev, window) for dev in ops]
    return FeedTrace(window, inside, busy)


def read_xplane(path: str, devices: int):
    """(per-device ops, host spans with their arguments, the
    ``bench/window`` interval or None) from one parse of an
    ``.xplane.pb``; device ops come from the lines ``bench/trace.py``
    reads."""
    from jax.profiler import ProfileData
    ops: List[List[tr.Event]] = []
    host: List[Span] = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            op_lines = ([lines["XLA Ops"]] if "XLA Ops" in lines else
                        [ln for name, ln in lines.items()
                         if name not in ("Steps", "XLA Modules")])
            ops.append([tr.Event(ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9)
                        for ln in op_lines for ev in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    start = ev.start_ns * 1e-9
                    end = start + ev.duration_ns * 1e-9
                    if ev.name == "bench/window":
                        window = (start, end)
                    elif PROGRAM_SPAN.match(ev.name):
                        host.append(Span(ev.name, start, end,
                                         dict(ev.stats)))
    return ops[:devices], host, window


_CACHE: Dict[tuple, Optional[FeedTrace]] = {}


def load(ctx) -> Optional[FeedTrace]:
    """The feed spans of the run that ``ctx["trace"]`` summarizes, or None
    when there is no trace, no file whose window matches, or no program
    span in it."""
    summary = ctx.get("trace")
    if summary is None:
        return None
    path = newest_xplane(config=ctx["config"]["name"])
    if path is None:
        return None
    key = (path, os.path.getmtime(path), summary.window, ctx["chips"])
    if key not in _CACHE:
        ops, host, window = read_xplane(path, int(ctx["chips"]))
        same = window is not None and all(
            abs(a - b) <= 1e-9 for a, b in zip(window, summary.window))
        ft = from_events(ops, host, window) if same else None
        _CACHE[key] = ft if ft is not None and ft.spans else None
    return _CACHE[key]
