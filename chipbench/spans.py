"""Self time of the program's tracer spans inside a window."""

from __future__ import annotations

import collections
from typing import Dict


def self_seconds(spans, t0: float, t1: float) -> Dict[str, float]:
    """Seconds each span name spent outside its direct children, over the
    spans that lie inside ``[t0, t1]``.  A direct child is a span of the
    same thread, one level deeper, inside its parent's interval."""
    inside = [s for s in spans if s.t0 >= t0 and s.t1 <= t1]
    out: Dict[str, float] = collections.defaultdict(float)
    for s in inside:
        out[s.name] += s.t1 - s.t0
    by_thread = collections.defaultdict(list)
    for s in inside:
        by_thread[s.tid].append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.t0, s.depth))
        open_stack = []
        for s in group:
            while open_stack and not (s.t0 >= open_stack[-1].t0
                                      and s.t1 <= open_stack[-1].t1
                                      and s.depth > open_stack[-1].depth):
                open_stack.pop()
            if open_stack and open_stack[-1].depth == s.depth - 1:
                out[open_stack[-1].name] -= s.t1 - s.t0
            open_stack.append(s)
    return dict(out)
