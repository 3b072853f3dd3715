"""Device time per chunk, in ms, of the batched table-lookup kernel, from
the profiler trace."""

from chipbench import roofline


def read(win):
    s = roofline.lookup_seconds(win)
    if s is None or not win.chunks:
        return None
    return s / win.chunks * 1e3
