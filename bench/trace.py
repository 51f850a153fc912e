"""Reduce a profiler trace (``.xplane.pb``) to device busy, idle and
per-operation time, on the host's clock of the benchmark's own spans.

Busy time is the union of the intervals in which an operation runs on a
device, clipped to the traced window, and averaged over the devices.  The
window is the benchmark's ``bench.window`` span on the host; on a TPU the
profiler puts host and device events on one clock.  A TPU trace names each
operation by its HLO instruction (``%tpu_custom_call.72 = ...``); the name
is kept up to `` = ``.  Control-flow operations (``while``, ``conditional``)
enclose the operations they run, so each operation's time is its self
time: its duration less that of the operations it encloses.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock

#: where a TPU trace keeps its per-operation events
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of closed intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` leaves uncovered."""
    out, t = [], lo
    for a, b in merged:
        if b <= t:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Trace:
    """What one traced run's reduction keeps."""
    window: Interval                     # the bench.window span
    busy: Dict[str, List[Interval]]      # device plane -> merged op union
    op_s: Dict[str, float]               # op name -> device self seconds
    spans: Dict[str, List[Interval]]     # host span name -> intervals
    host_events: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in the window with an operation on the device, averaged
        over the devices."""
        if not self.busy:
            return 0.0
        lo, hi = self.window
        return sum(covered(m, lo, hi) for m in self.busy.values()) \
            / len(self.busy)

    def device_idle_in(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] in which no device ran an operation
        (averaged over the devices)."""
        if not self.busy:
            return hi - lo
        return sum((hi - lo) - covered(m, lo, hi)
                   for m in self.busy.values()) / len(self.busy)

    def kernel_s(self, names: Sequence[str]) -> float:
        """Device seconds of the operations whose name starts with one of
        ``names``, summed over devices and divided by their number."""
        tot = sum(s for op, s in self.op_s.items()
                  if any(op.startswith(p) for p in names))
        return tot / max(1, len(self.busy))

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest
        idle gaps of the first device, each named by the innermost host
        event that covers most of it."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.window
        dev = sorted(self.busy)[0] if self.busy else None
        idle = gaps(self.busy[dev], lo, hi) if dev else [(lo, hi)]
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        named = []
        for a, b in idle:
            best: Optional[Tuple[float, str]] = None
            for name, s, e in self.host_events:
                if name == WINDOW_SPAN or min(e, b) - max(s, a) < (b - a) / 2:
                    continue
                if best is None or e - s < best[0]:
                    best = (e - s, name)
            named.append([best[1] if best else "no host event", b - a])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Self seconds per name of properly nested ``(name, start, end)``
    events of one line: each event's duration less its children's."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []              # [name, end, child seconds]

    def pop():
        name, start_end, child = stack.pop()
        out[name] += start_end[1] - start_end[0] - child
        if stack:
            stack[-1][2] += start_end[1] - start_end[0]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1][1] <= s:
            pop()
        stack.append([name, (s, e), 0.0])
    while stack:
        pop()
    return dict(out)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def reduce(path: str, *, device_prefix: str = DEVICE_PLANE_PREFIX,
           op_line: str = OP_LINE, host_plane: str = HOST_PLANE) -> Trace:
    """Read one trace file.  ``device_prefix`` / ``op_line`` select the
    planes and the line that hold device operations; the host plane holds
    the benchmark's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    busy: Dict[str, List[Interval]] = {}
    op_s: Dict[str, float] = defaultdict(float)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    host_events: List[Tuple[str, float, float]] = []
    raw_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            evs = []
            for line in plane.lines:
                if line.name.startswith(op_line):
                    evs += [(e.name.split(" = ", 1)[0], e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            raw_ops[plane.name] = evs
        if plane.name == host_plane:
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    t = s + e.duration_ns * 1e-9
                    host_events.append((e.name, s, t))
                    if e.name.startswith("bench."):
                        spans[e.name].append((s, t))
    if not raw_ops:
        raise RuntimeError(f"the trace holds no {device_prefix}* plane")
    if len(spans.get(WINDOW_SPAN, ())) != 1:
        raise RuntimeError(f"the trace holds {len(spans.get(WINDOW_SPAN, ()))}"
                           f" {WINDOW_SPAN} spans, not one")
    lo, hi = spans[WINDOW_SPAN][0]
    for plane, evs in raw_ops.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        for n, sec in self_times(inside).items():
            op_s[n] += sec
        busy[plane] = union([(s, e) for _, s, e in inside])
    return Trace(window=(lo, hi), busy=busy, op_s=dict(op_s),
                 spans=dict(spans), host_events=host_events)
