"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time and idle share over the benchmark's window, device
time per operation, kernel events by pattern, and idle time by what the host
was doing (the benchmark's own ``bench.*`` spans).

Device operations are the events of the ``XLA Ops`` line of each
``/device:...`` plane. Host spans are ``bench.*`` events on the host plane.
The window is the ``bench.window`` span; without one, the whole trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Pattern, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
#: spans that say what the host was doing, innermost first in a tie
LEAF_SPANS = ("bench.build", "bench.step", "bench.record", "bench.append")
IDLE_OTHER = "other"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                  #: averaged over devices
    n_devices: int
    ops: Dict[str, float] = field(default_factory=dict)        #: name -> s
    op_events: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, pattern: Pattern[str]) -> Tuple[int, float]:
        """(events, device seconds) of the operations ``pattern`` matches,
        summed over devices."""
        hits = [d for name, d in self.op_events if pattern.search(name)]
        return len(hits), sum(hits)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in
                sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no merged busy interval covers."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


HLO_NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)? = ")
HLO_KIND = re.compile(r"kind=(k\w+)|custom_call_target=\"(\w+)\"")


def base_name(op: str) -> str:
    """One row per kind of operation: the HLO instruction's name without
    its number, with its fusion kind or custom-call target
    (``%fusion.12 = f32[..] fusion(..), kind=kOutput`` -> ``fusion:kOutput``)."""
    m = HLO_NAME.match(op)
    if not m:
        return re.sub(r"[.:]\d+$", "", op)
    kind = HLO_KIND.search(op)
    return m.group(1) + (f":{kind.group(1) or kind.group(2)}" if kind else "")


def summarize(path: str) -> TraceSummary:
    """The summary of one ``.xplane.pb``; times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = {}
    device_ops: List[List[Tuple[str, float, float]]] = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
        elif DEVICE_PLANE.match(plane.name):
            ops = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            device_ops.append(ops)
    device_ops = [ops for ops in device_ops if ops]
    if not device_ops:
        raise ValueError(f"{path}: no device ran an operation in the trace")
    if spans.get(WINDOW_SPAN):
        lo, hi = min(a for a, _ in spans[WINDOW_SPAN]), max(b for _, b in spans[WINDOW_SPAN])
    else:
        starts = [s for ops in device_ops for _, s, _ in ops]
        ends = [s + d for ops in device_ops for _, s, d in ops]
        lo, hi = min(starts), max(ends)
    leaves = {name: merge(clip(spans.get(name, []), lo, hi)) for name in LEAF_SPANS}
    summary = TraceSummary(window_s=hi - lo, busy_s=0.0, n_devices=len(device_ops))
    for ops in device_ops:
        inside = [(name, s, d) for name, s, d in ops if lo <= s < hi]
        busy = merge(clip([(s, s + d) for _, s, d in inside], lo, hi))
        summary.busy_s += sum(b - a for a, b in busy) / len(device_ops)
        idle = gaps(busy, lo, hi)
        idle_s = sum(b - a for a, b in idle)
        named = 0.0
        for name, intervals in leaves.items():
            share = overlap(idle, intervals) / len(device_ops)
            if share > 0:
                summary.idle_by_span[name] = summary.idle_by_span.get(name, 0.0) + share
                named += share
        rest = idle_s / len(device_ops) - named
        if rest > 0:
            summary.idle_by_span[IDLE_OTHER] = summary.idle_by_span.get(IDLE_OTHER, 0.0) + rest
        for name, _, d in inside:
            summary.op_events.append((name, d))
            key = base_name(name)
            summary.ops[key] = summary.ops.get(key, 0.0) + d / len(device_ops)
    return summary
