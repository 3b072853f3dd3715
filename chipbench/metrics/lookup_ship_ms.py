"""Self time per chunk, in ms, of getting the table lookup's planes to the
device: ``lookup.pack`` (the int64 key and start columns split into int32
halves, the owner and occupancy planes cast) and ``lookup.ship`` (the
packed planes handed to the device)."""

from chipbench.spans import self_seconds

SPANS = ("lookup.pack", "lookup.ship")


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or not any(s in own for s in SPANS):
        return None
    return sum(own.get(s, 0.0) for s in SPANS) / win.chunks * 1e3
