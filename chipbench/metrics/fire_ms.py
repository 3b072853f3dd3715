"""Self time per chunk, in ms, of the ``fire`` span: the due rows of a
window close turned into Python rows and merged into firing order."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "fire" not in own:
        return None
    return own["fire"] / win.chunks * 1e3
