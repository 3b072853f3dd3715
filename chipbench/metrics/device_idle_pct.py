"""The share of the window, in %, in which no op ran on the device: one
minus the union of the device's op intervals over the window, from the
profiler trace (averaged over the chips)."""

from chipbench import devtrace


def read(win):
    if win.trace is None or not win.trace.ops:
        return None
    return (1.0 - devtrace.busy_s(win.trace, win.t0, win.t1) / win.seconds) * 100.0
