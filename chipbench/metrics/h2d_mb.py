"""Bytes handed from the host to the device per chunk, in MB (10^6 B): the
``bytes`` of every ``*.ship`` span in the window (the table lookup's cell
and table planes, and the segment path's ids and values)."""


def read(win):
    ships = [s for s in win.spans
             if s.name.endswith(".ship") and s.t0 >= win.t0 and s.t1 <= win.t1]
    if not win.chunks or not ships:
        return None
    return sum(s.args["bytes"] for s in ships) / win.chunks / 1e6
