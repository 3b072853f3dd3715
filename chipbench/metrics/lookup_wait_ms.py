"""Self time per chunk, in ms, of the ``lookup.wait`` span: the host
blocked on the lookup kernel's rows and copying them back."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "lookup.wait" not in own:
        return None
    return own["lookup.wait"] / win.chunks * 1e3
