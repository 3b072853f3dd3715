"""Self time per chunk, in ms, of the ``table_update`` span: the lookup
dispatch and its wait, the claim loop and the scatter-add."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "table_update" not in own:
        return None
    return own["table_update"] / win.chunks * 1e3
