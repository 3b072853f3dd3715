"""Self time per chunk, in ms, of the ``lookup.dispatch`` span: the eager
pads around the lookup kernel, the ``pallas_call``'s build and launch, and
the slice of its output.  The programs JAX traces, lowers and builds there
are spans of their own (``compile_ms``), so they are not counted here."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "lookup.dispatch" not in own:
        return None
    return own["lookup.dispatch"] / win.chunks * 1e3
