"""Time per chunk, in ms, in which JAX traced, lowered or built a program
(compiled, or loaded from the persistent cache) inside the window: the
union of the program's ``jax.trace``, ``jax.lower`` and ``jax.compile``
spans, so that a trace nested in another counts once.  A traced window in
which nothing was built reads 0, as a sound run does; a window with no
spans at all (the tracer off) reads nothing."""

NAMES = ("jax.trace", "jax.lower", "jax.compile")


def read(win):
    inside = [s for s in win.spans if s.t0 >= win.t0 and s.t1 <= win.t1]
    if not win.chunks or not inside:
        return None
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted((s.t0, s.t1) for s in inside if s.name in NAMES):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total / win.chunks * 1e3
