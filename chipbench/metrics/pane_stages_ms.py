"""Self time per chunk, in ms, of the fused plane's pane stages: the
``route``, ``expand_panes``, ``dedup_cells`` and ``reduce_by_cell`` spans
of the program's tracer."""

from chipbench.spans import self_seconds

STAGES = ("route", "expand_panes", "dedup_cells", "reduce_by_cell")


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or not any(s in own for s in STAGES):
        return None
    return sum(own.get(s, 0.0) for s in STAGES) / win.chunks * 1e3
