"""The plain reference: per-key sum and count over tumbling or sliding
event-time windows, with a watermark at every chunk boundary and late
records on a side channel.

Semantics, for a stream cut into chunks of ``chunk`` items:

* an item with event time ``ts`` belongs to every window ``[s, s + size)``
  with ``s`` a multiple of ``slide`` and ``ts - size < s <= ts`` (tumbling
  windows have ``slide == size``); its assignments are taken newest window
  first;
* after chunk ``k`` the watermark is ``max(ts of chunks 0..k) - lateness``;
  every open window with ``end <= watermark`` fires then, in
  ``(end, start, key)`` order, with the sum and count of its live items;
* an assignment of an item in chunk ``k`` is late when its window's end is
  at or below the watermark of chunk ``k - 1``: it goes to the side channel
  as ``(key, value, ts, start)``, in stream order, and is not counted;
* what has not fired at the end is the open state, in ``(key, start)``
  order.

It imports nothing of the system under test, and is written with whole-array
numpy operations so that it checks a run of millions of items in seconds.
"""

from __future__ import annotations

import numpy as np


def assignments(keys, values, ts, size: int, slide: int):
    """Every (item, window) assignment, item-major and newest window first:
    ``(item index, key, value, ts, start)``."""
    keys = np.asarray(keys, np.int64)
    ts = np.asarray(ts, np.int64)
    panes = -(-size // slide)
    newest = (ts // slide) * slide
    starts = newest[:, None] - slide * np.arange(panes, dtype=np.int64)[None, :]
    valid = starts > (ts - size)[:, None]
    item = np.broadcast_to(np.arange(len(ts))[:, None], starts.shape)[valid]
    return (item, keys[item], np.asarray(values, np.int64)[item], ts[item],
            starts[valid])


def watermarks(ts, chunk: int, lateness: int) -> np.ndarray:
    """The watermark after each chunk."""
    ts = np.asarray(ts, np.int64)
    if len(ts) % chunk:
        raise ValueError("the stream must be a whole number of chunks")
    return np.maximum.accumulate(ts.reshape(-1, chunk).max(axis=1)) - lateness


def _classified(keys, values, ts, *, size, slide, lateness, chunk):
    """Every assignment with its chunk, and whether it is late: ``(chunk,
    key, value, ts, start, late, watermarks)``."""
    wm = watermarks(ts, chunk, lateness)
    item, k, v, t, s = assignments(keys, values, ts, size, slide)
    c = item // chunk
    # the watermark each assignment meets: that of the chunk before its own
    before = np.where(c > 0, wm[np.maximum(c - 1, 0)], np.iinfo(np.int64).min)
    return c, k, v, t, s, s + size <= before, wm


def keyed_windows(keys, values, ts, *, size: int, slide: int, lateness: int,
                  chunk: int):
    """Returns ``(emissions, late, open_state)``.

    ``emissions`` is an ``[n, 5]`` array of ``(key, start, end, sum, count)``
    in firing order, ``late`` an ``[n, 4]`` array of ``(key, value, ts,
    start)`` in stream order, and ``open_state`` an ``[n, 5]`` array like
    ``emissions`` in ``(key, start)`` order."""
    _, k, v, t, s, late_m, wm = _classified(
        keys, values, ts, size=size, slide=slide, lateness=lateness, chunk=chunk)
    late = np.stack([k[late_m], v[late_m], t[late_m], s[late_m]], axis=1)

    k, v, s = k[~late_m], v[~late_m], s[~late_m]
    order = np.lexsort((s, k))
    k, v, s = k[order], v[order], s[order]
    first = np.ones(len(k), bool)
    first[1:] = (k[1:] != k[:-1]) | (s[1:] != s[:-1])
    starts = np.flatnonzero(first)
    totals = np.add.reduceat(v, starts) if len(v) else v
    counts = np.diff(np.append(starts, len(k)))
    cells = np.stack(
        [k[starts], s[starts], s[starts] + size, totals, counts], axis=1
    ).astype(np.int64)
    # a window fires after the first chunk whose watermark reaches its end
    fired_at = np.searchsorted(wm, cells[:, 2], side="left")
    fired = fired_at < len(wm)
    em = cells[fired]
    em = em[np.lexsort((em[:, 0], em[:, 1], em[:, 2], fired_at[fired]))]
    return em, late, cells[~fired]


def chunk_shapes(keys, ts, *, size: int, slide: int, lateness: int, chunk: int):
    """Per chunk, its live (not late) assignments and the distinct ``(key,
    window)`` cells among them: the sizes a chunk sends to the plane's
    reduction and table."""
    c, k, _, _, s, late_m, wm = _classified(
        keys, np.zeros(len(ts), np.int64), ts, size=size, slide=slide,
        lateness=lateness, chunk=chunk)
    c, k, s = c[~late_m], k[~late_m], s[~late_m]
    live = np.bincount(c, minlength=len(wm))
    order = np.lexsort((s, k, c))
    c, k, s = c[order], k[order], s[order]
    new = np.ones(len(c), bool)
    new[1:] = (c[1:] != c[:-1]) | (k[1:] != k[:-1]) | (s[1:] != s[:-1])
    return live, np.bincount(c[new], minlength=len(wm))
