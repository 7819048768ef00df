"""Reduce one profiler trace (``.xplane.pb``) to what the layer metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  On a TPU
the trace holds one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Modules`` has one event per executed program (``jit_<fn>(<hash>)``)
and whose line ``XLA Ops`` has one event per executed HLO op; and the
plane ``/host:CPU``, whose lines are host threads.  The harness's own
spans (``jax.profiler.TraceAnnotation``) are events on a host line, on
the same clock as the device events.

Busy time is the union of the op intervals of a chip; idle time is the
rest of the traced window.  Every idle gap is labelled by what the host
was doing: the innermost harness span around the gap's midpoint and the
programs that ran before and after it (``generate:prefill_step->pad``),
or ``in:<program>`` for a gap between ops of one program.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def module_name(event_name: str) -> str:
    """``jit_serve_step(1234)`` -> ``serve_step``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Span:
    name: str
    start: int
    end: int


@dataclass
class Device:
    modules: List[Tuple[str, int, int]] = field(default_factory=list)
    ops: List[Tuple[str, int, int]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Span]
    window: Tuple[int, int]

    # -- device time --------------------------------------------------
    def busy_intervals(self, dev: Device) -> List[Tuple[int, int]]:
        w0, w1 = self.window
        return [(max(s, w0), min(e, w1)) for s, e in
                _union((s, e) for _, s, e in dev.ops) if e > w0 and s < w1]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the chips traced."""
        tot = [sum(e - s for s, e in self.busy_intervals(d))
               for d in self.devices]
        return sum(tot) / len(tot) / 1e9

    def module_calls(self, name: str) -> List[Tuple[int, int]]:
        """``(start, end)`` of every run of program ``name`` that overlaps
        the window, on the first chip (the window holds whole calls; the
        device clock may lead the host's by a millisecond or so)."""
        w0, w1 = self.window
        return [(s, e) for m, s, e in self.devices[0].modules
                if m == name and e > w0 and s < w1]

    def module_seconds(self, name: str) -> Tuple[float, int]:
        """Total device seconds of program ``name`` and its run count."""
        calls = self.module_calls(name)
        return sum(e - s for s, e in calls) / 1e9, len(calls)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ops of the first chip with the most self time (an op's
        time less that of the ops nested in it, as a ``while`` holds its
        body), summed over their runs in the window."""
        w0, w1 = self.window
        tot: Dict[str, int] = defaultdict(int)
        stack: List[Tuple[str, int]] = []      # (name, end) of open ops
        for name, s, e in sorted(self.devices[0].ops,
                                 key=lambda o: (o[1], -o[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            if s >= w0 and e <= w1:
                tot[name] += e - s
                if stack:
                    tot[stack[-1][0]] -= e - s
            stack.append((name, e))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / 1e9) for k, v in best]

    # -- idle gaps ----------------------------------------------------
    def gaps(self) -> List[Tuple[str, int, int]]:
        """Every idle interval of the first chip in the window, cut where a
        host span starts or ends, each piece labelled."""
        dev = self.devices[0]
        busy = self.busy_intervals(dev)
        w0, w1 = self.window
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        mods = sorted((s, e, m) for m, s, e in dev.modules)
        cuts = sorted({t for sp in self.spans for t in (sp.start, sp.end)})
        out = []
        for g0, g1 in zip(edges[::2], edges[1::2]):
            pts = [g0] + [t for t in cuts if g0 < t < g1] + [g1]
            for a, b in zip(pts, pts[1:]):
                if b > a:
                    out.append((self._label(a, b, mods), a, b))
        return out

    def _label(self, g0: int, g1: int, mods) -> str:
        mid = (g0 + g1) // 2
        for s, e, m in mods:
            if s <= mid < e:
                return f"in:{m}"
        around = [sp for sp in self.spans if sp.start <= mid < sp.end]
        if around:
            sp = min(around, key=lambda sp: sp.end - sp.start)
            span, lo, hi = sp.name, sp.start, sp.end
        else:
            (lo, hi), span = self.window, "none"
        before = [m for s, e, m in mods if lo <= e <= mid]
        after = [m for s, e, m in mods if mid < s <= hi]
        return (f"{span}:{before[-1] if before else 'start'}"
                f"->{after[0] if after else 'end'}")

    def idle_by_label(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, int] = defaultdict(int)
        for label, s, e in self.gaps():
            tot[label] += e - s
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / 1e9) for k, v in best]

    def span_calls(self, name: str) -> List[Span]:
        return [sp for sp in self.spans if sp.name == name]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, span_names: Iterable[str],
         window_span: Optional[str] = None) -> Trace:
    """Read ``path`` (a ``.xplane.pb`` or a trace directory).  The window
    is the first host span named ``window_span``, or else the extent of
    every device event."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    wanted = set(span_names) | ({window_span} if window_span else set())
    devices: List[Device] = []
    spans: List[Span] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device()
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev.modules = [(module_name(e.name), int(e.start_ns),
                                    int(e.end_ns)) for e in line.events]
                elif line.name == OP_LINE:
                    dev.ops = [(op_name(e.name), int(e.start_ns),
                                int(e.end_ns)) for e in line.events]
            if dev.ops or dev.modules:
                devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.end_ns)))
    if not devices:
        raise ValueError(f"{path}: no device plane with ops")
    win = [sp for sp in spans if sp.name == window_span]
    if win:
        window = (win[0].start, win[0].end)
        spans = [sp for sp in spans if sp.name != window_span]
    else:
        ev = [t for d in devices for _, s, e in d.ops + d.modules
              for t in (s, e)]
        window = (min(ev), max(ev))
    return Trace(devices=devices, spans=sorted(spans, key=lambda s: s.start),
                 window=window)
