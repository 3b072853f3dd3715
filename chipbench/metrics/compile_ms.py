"""Time per chunk, in ms, in which JAX traced, lowered or built a program
(compiled, or loaded from the persistent cache) inside the window: the
union of the program's ``jax.trace``, ``jax.lower`` and ``jax.compile``
spans, so that a trace nested in another counts once."""

NAMES = ("jax.trace", "jax.lower", "jax.compile")


def read(win):
    spans = sorted((s.t0, s.t1) for s in win.spans
                   if s.name in NAMES and s.t0 >= win.t0 and s.t1 <= win.t1)
    if not win.chunks or not spans:
        return None
    total, end = 0.0, float("-inf")
    for t0, t1 in spans:
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total / win.chunks * 1e3
