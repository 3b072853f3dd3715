"""Self time per chunk, in ms, of the fused plane's ``close`` span: the
watermark advance and the firing of due windows."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "close" not in own:
        return None
    return own["close"] / win.chunks * 1e3
