"""Self time per chunk, in ms, of the ``lookup.dispatch`` span: the launch
of the lookup's jitted program, built once per padded count of cells.  A
program that JAX traces, lowers or builds there, for a padded count it has
not met, is a span of its own (``compile_ms``), so it is not counted here."""

from chipbench.spans import self_seconds


def read(win):
    own = self_seconds(win.spans, win.t0, win.t1)
    if not win.chunks or "lookup.dispatch" not in own:
        return None
    return own["lookup.dispatch"] / win.chunks * 1e3
