"""The batched table-lookup kernel's share of its roofline, in %: the least
time of the window's lookups (their least bytes over the chip's HBM
bandwidth) over the kernel's device time.  The least bytes do not depend on
how the lookup is built, so the share stays honest whatever implements it."""

from chipbench import roofline


def read(win):
    s = roofline.lookup_seconds(win)
    if s is None or not win.chunks:
        return None
    least = roofline.lookup_least_bytes(win.cells) / win.peaks["hbm_bytes_per_s"]
    return least / s * 100.0
