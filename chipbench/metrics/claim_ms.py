"""Self time per chunk, in ms, of the ``claim`` span: the table's
open-addressing claim loop, which places the cells the lookup missed."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "claim" not in own:
        return None
    return own["claim"] / win.chunks * 1e3
