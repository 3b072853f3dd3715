"""Kernel cost functions, and where the kernels are in the device trace.

In the device trace an op of a Pallas kernel is a ``custom-call`` to
:data:`PALLAS_TARGET`, named for the jitted program that holds it: the
keyed plane's table lookup is ``%batched_table_lookup.N``.  The match keys
on the call target, not on that name.  On the keyed plane's path the one
Pallas kernel is the batched table lookup, which the table dispatches and
waits for inside its ``table_update`` span; so its ops are the Pallas calls
that run inside a ``table_update`` span.
"""

from chipbench import devtrace

PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
LOOKUP_SPAN = "table_update"


def lookup_seconds(win):
    """Device seconds of the lookup kernel inside the window, or None where
    the trace holds no such op."""
    if win.trace is None:
        return None
    spans = [(win.trace.to_ns(s.t0), win.trace.to_ns(s.t1))
             for s in win.spans if s.name == LOOKUP_SPAN
             and s.t0 >= win.t0 and s.t1 <= win.t1]
    return devtrace.seconds_inside(win.trace, spans, PALLAS_TARGET)


def lookup_least_bytes(cells):
    """Bytes any correct lookup of ``cells`` cells moves at least: 20 B of
    cell planes in (owner, key and start as int32 halves) and 4 B of row out
    per cell, and one table row of 24 B read per cell."""
    return (20 + 4 + 24) * int(sum(cells))
