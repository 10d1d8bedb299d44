"""The program's own spans in a profiler trace, beside the device's work.

The program names its host work with ``repro.core.spans``: ``campaign.*``
spans around the campaign loop's stages and ``session.*`` spans inside a
session's build and step. This module reads them from an ``.xplane.pb`` over
the benchmark's window, as ``bench.trace`` reads the window and the device
operations, and gives:

- each span's intervals, merged, and the device busy time inside them;
- the window's idle device time by the innermost program span that covers
  it (``other`` where none does);
- device time by program: the jitted module each operation ran in, from the
  device plane's ``XLA Modules`` line, or where a plane has none from the
  operation's ``hlo_module`` stat.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import (DEVICE_PLANE, HOST_PLANE, IDLE_OTHER, OPS_LINE, WINDOW_SPAN,
                         Interval, clip, gaps, merge, overlap)

PROGRAM_SPAN = re.compile(r"^(campaign|session)\.")
MODULES_LINE = "XLA Modules"
#: ``jit_matmul(11053972262140898904)`` -> ``jit_matmul``
MODULE_ID = re.compile(r"\(\d+\)$")
Named = Tuple[float, float, str]


@dataclass
class ProgramSpans:
    window_s: float
    busy_s: float                                  #: averaged over devices
    n_devices: int
    spans: Dict[str, List[Interval]] = field(default_factory=dict)   #: merged
    busy_in: Dict[str, float] = field(default_factory=dict)          #: name -> s
    idle_by_span: Dict[str, float] = field(default_factory=dict)     #: innermost
    device_s_by_program: Dict[str, float] = field(default_factory=dict)

    def seconds(self, name: str) -> float:
        return sum(b - a for a, b in self.spans.get(name, []))

    def idle_share(self, name: str) -> Optional[float]:
        """1 - device busy time inside the span's intervals over their
        length; None where the span never ran in the window."""
        length = self.seconds(name)
        return None if length <= 0 else 1.0 - self.busy_in.get(name, 0.0) / length

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]]

    def top_programs(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in
                sorted(self.device_s_by_program.items(), key=lambda kv: -kv[1])[:n]]


def innermost(intervals: Sequence[Named], lo: float, hi: float) -> List[Named]:
    """[lo, hi] cut into pieces, each named after the innermost interval
    that covers it (the one opened last), or ``other``. The program's spans
    nest, as ``with`` blocks on one thread do."""
    out: List[Named] = []
    stack: List[Named] = []
    cur = lo

    def upto(t: float) -> None:
        nonlocal cur
        if t > cur:
            out.append((cur, t, stack[-1][2] if stack else IDLE_OTHER))
            cur = t

    for iv in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][1] <= iv[0]:
            upto(stack[-1][1])
            stack.pop()
        upto(iv[0])
        stack.append(iv)
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return out


def _program(ev, starts: List[float], modules: List[Named]) -> str:
    if modules:
        i = bisect.bisect_right(starts, ev.start_ns * 1e-9) - 1
        if i >= 0 and ev.start_ns * 1e-9 < modules[i][1]:
            return modules[i][2]
        return IDLE_OTHER
    return next((str(v) for k, v in ev.stats if k == "hlo_module"), IDLE_OTHER)


def read(path: str) -> ProgramSpans:
    """The program spans of one ``.xplane.pb``; times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: List[Interval] = []
    named: List[Named] = []
    devices: List[List[Tuple[str, float, float]]] = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    if ev.name == WINDOW_SPAN:
                        window.append(iv)
                    elif PROGRAM_SPAN.match(ev.name):
                        named.append((*iv, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                 MODULE_ID.sub("", ev.name))
                for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else []))
            starts = [m[0] for m in modules]
            ops = [(_program(ev, starts, modules), ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                   for ev in (lines[OPS_LINE].events if OPS_LINE in lines else [])]
            devices.append(ops)
    devices = [ops for ops in devices if ops]
    if not devices:
        raise ValueError(f"{path}: no device ran an operation in the trace")
    if window:
        lo, hi = min(a for a, _ in window), max(b for _, b in window)
    else:
        lo = min(s for ops in devices for _, s, _ in ops)
        hi = max(s + d for ops in devices for _, s, d in ops)
    named = [(max(a, lo), min(b, hi), n) for a, b, n in named if b > lo and a < hi]
    out = ProgramSpans(window_s=hi - lo, busy_s=0.0, n_devices=len(devices))
    for name in sorted({n for _, _, n in named}):
        out.spans[name] = merge((a, b) for a, b, n in named if n == name)
    pieces: Dict[str, List[Interval]] = {}
    for a, b, n in innermost(named, lo, hi):
        pieces.setdefault(n, []).append((a, b))
    share = 1.0 / len(devices)
    for ops in devices:
        inside = [(p, s, d) for p, s, d in ops if lo <= s < hi]
        busy = merge(clip([(s, s + d) for _, s, d in inside], lo, hi))
        out.busy_s += sum(b - a for a, b in busy) * share
        for name, intervals in out.spans.items():
            out.busy_in[name] = out.busy_in.get(name, 0.0) + overlap(busy, intervals) * share
        idle = gaps(busy, lo, hi)
        for name, intervals in pieces.items():
            s = overlap(idle, intervals) * share
            if s > 0:
                out.idle_by_span[name] = out.idle_by_span.get(name, 0.0) + s
        for p, _, d in inside:
            out.device_s_by_program[p] = out.device_s_by_program.get(p, 0.0) + d * share
    return out


def of_run(summary) -> Optional[ProgramSpans]:
    """The program spans of the trace that ``bench.trace`` reduced to
    ``summary`` in this run: the newest trace in the run's work directory
    (``bench-*`` in the temporary directory), taken only where its window
    and busy time are the summary's. None without a summary or such a
    trace."""
    if summary is None:
        return None
    found = glob.glob(os.path.join(tempfile.gettempdir(), "bench-*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    spans = read(max(found, key=os.path.getmtime))
    same = (math.isclose(spans.window_s, summary.window_s, rel_tol=1e-9)
            and math.isclose(spans.busy_s, summary.busy_s, rel_tol=1e-9, abs_tol=1e-12))
    return spans if same else None
