"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The reduction reads the op line of each TPU plane (``XLA Ops``), and lines
host spans up with it through one anchor annotation: the harness reads
``time.perf_counter()`` and at once opens a ``TraceAnnotation`` named
:data:`ANCHOR`, so the annotation's start in trace time is that
``perf_counter`` reading.  Every number is taken over a window given in
``perf_counter`` seconds.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

ANCHOR = "chipbench.anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SHORT = re.compile(r"^(%\S+ = \S+ [\w-]+)\(")


@dataclasses.dataclass
class DeviceTrace:
    #: per device plane: ``[(op name, start ns, end ns)]`` in trace time
    ops: List[List[Tuple[str, int, int]]]
    #: trace time (ns) minus ``perf_counter`` time (ns)
    offset_ns: float

    def to_ns(self, perf_s: float) -> float:
        return perf_s * 1e9 + self.offset_ns

    def to_perf(self, ns: float) -> float:
        return (ns - self.offset_ns) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(path: str, anchor_perf_s: float) -> DeviceTrace:
    """Read the device op lines of ``path`` and place the anchor."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, anchor_ns = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    )
            ops.append(sorted(evs, key=lambda r: r[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor_ns = float(e.start_ns)
    if anchor_ns is None:
        raise ValueError(f"no {ANCHOR!r} annotation in {path}")
    return DeviceTrace(ops=ops, offset_ns=anchor_ns - anchor_perf_s * 1e9)


def _clip(evs, lo: float, hi: float):
    for name, s, e in evs:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            yield name, s2, e2


def busy_intervals(evs, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the op intervals inside ``[lo, hi)`` (trace ns)."""
    out: List[List[float]] = []
    for _, s, e in sorted(_clip(evs, lo, hi), key=lambda r: r[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: DeviceTrace, t0: float, t1: float) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    lo, hi = trace.to_ns(t0), trace.to_ns(t1)
    per = [sum(e - s for s, e in busy_intervals(evs, lo, hi)) for evs in trace.ops]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_seconds(trace: DeviceTrace, t0: float, t1: float) -> Dict[str, float]:
    """Device seconds of each op inside the window, summed over chips, by
    :func:`short_name`."""
    lo, hi = trace.to_ns(t0), trace.to_ns(t1)
    out: Dict[str, float] = collections.defaultdict(float)
    for evs in trace.ops:
        for name, s, e in _clip(evs, lo, hi):
            out[short_name(name)] += (e - s) / 1e9
    return dict(out)


def seconds_inside(trace: DeviceTrace, intervals, marker: str):
    """Device seconds of the ops whose name holds ``marker`` and whose
    midpoint lies in one of ``intervals`` (trace ns), summed over chips; None
    where there is no such op."""
    intervals = sorted(intervals)
    starts = [a for a, _ in intervals]
    total, found = 0, False
    for evs in trace.ops:
        for name, s, e in evs:
            if marker not in name:
                continue
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid <= intervals[i][1]:
                total += e - s
                found = True
    return total / 1e9 if found else None


def short_name(op: str) -> str:
    """An op's trace name up to its operands: ``%fusion.8 = s32[4101]{...}
    fusion``."""
    m = SHORT.match(op)
    return m.group(1) if m else op[:120]


def idle_gaps(trace: DeviceTrace, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Gaps between busy intervals of the first device, as ``perf_counter``
    seconds ``(start, end)``."""
    lo, hi = trace.to_ns(t0), trace.to_ns(t1)
    busy = busy_intervals(trace.ops[0], lo, hi) if trace.ops else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [
        (trace.to_perf(a), trace.to_perf(b))
        for a, b in zip(edges[::2], edges[1::2]) if b > a
    ]


def label_gaps(gaps: Sequence[Tuple[float, float]], spans) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the deepest
    span that holds the gap's midpoint (``"untraced"`` where none does)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        mid = (a + b) / 2
        best = None
        for sp in spans:
            if sp.t0 <= mid <= sp.t1 and (best is None or sp.depth > best.depth):
                best = sp
        out[best.name if best is not None else "untraced"] += b - a
    return dict(out)
