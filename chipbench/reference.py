"""The plain reference: per-key sum and count over tumbling, sliding or
session event-time windows, with a watermark at every chunk boundary and
late records on a side channel.

Semantics of tumbling and sliding windows, for a stream cut into chunks of
``chunk`` items:

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

Session windows (``keyed_sessions``) keep the watermark, the firing order
and the open state's order, and differ in what an item does:

* an item of chunk ``k`` is late when ``ts + gap`` is at or below the
  watermark of chunk ``k - 1``: it goes to the side channel as ``(key,
  value, ts, ts)``;
* a live item is the interval ``[ts, ts + gap)``, and merges with every
  open session of its key that it strictly overlaps, so that an item
  exactly ``gap`` after a session opens a new one; a session that has
  fired is gone, and a live item that would have overlapped it opens a new
  session.

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


def _watermark_met(wm: np.ndarray, c: np.ndarray) -> np.ndarray:
    """The watermark that an item of chunk ``c`` meets: that of the chunk
    before its own (none, the least int64, for the first chunk)."""
    return np.where(c > 0, wm[np.maximum(c - 1, 0)], np.iinfo(np.int64).min)


def _classified(keys, values, ts, *, size, slide, lateness, chunk):
    """Every assignment with its chunk, and whether it is late: ``(chunk,
    key, value, ts, start, late, watermarks)``."""
    wm = watermarks(ts, chunk, lateness)
    item, k, v, t, s = assignments(keys, values, ts, size, slide)
    c = item // chunk
    return c, k, v, t, s, s + size <= _watermark_met(wm, c), wm


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


def _late_sessions(ts, *, gap: int, lateness: int, chunk: int):
    """Each item's chunk, whether it is late for a session, and the
    watermarks: ``(chunk, late, watermarks)``."""
    ts = np.asarray(ts, np.int64)
    wm = watermarks(ts, chunk, lateness)
    c = np.arange(len(ts), dtype=np.int64) // chunk
    return c, ts + gap <= _watermark_met(wm, c), wm


def _merge_overlapping(rows: np.ndarray) -> np.ndarray:
    """Rows ``(key, start, end, sum, count)`` with the intervals of one key
    that strictly overlap merged, also through a third; in ``(key, start)``
    order."""
    rows = rows[np.lexsort((rows[:, 1], rows[:, 0]))]
    k, s, e = rows[:, 0], rows[:, 1], rows[:, 2]
    # each key's intervals on a stretch of the line of its own, so that one
    # running maximum of the ends serves every key
    rank = np.concatenate([[0], np.cumsum(k[1:] != k[:-1])])
    shift = rank * (e.max() - s.min() + 1) - s.min()
    reach = np.maximum.accumulate(e + shift)
    first = np.ones(len(rows), bool)
    first[1:] = s[1:] + shift[1:] >= reach[:-1]
    at = np.flatnonzero(first)
    return np.stack([k[at], s[at], np.maximum.reduceat(e, at),
                     np.add.reduceat(rows[:, 3], at),
                     np.add.reduceat(rows[:, 4], at)], axis=1)


def keyed_sessions(keys, values, ts, *, gap: int, lateness: int, chunk: int):
    """Returns ``(emissions, late, open_state)`` of session windows, in the
    row layouts of :func:`keyed_windows`.

    A session that has fired can be followed by a live item that would have
    overlapped it, so the sessions are not one global sort: each chunk's
    live items merge with the open sessions of their keys, and then the due
    sessions fire.  Merging by overlap does not depend on the items' order
    under one watermark, so this is the item-by-item merge."""
    keys = np.asarray(keys, np.int64)
    values = np.asarray(values, np.int64)
    ts = np.asarray(ts, np.int64)
    _, late_m, wm = _late_sessions(ts, gap=gap, lateness=lateness, chunk=chunk)
    late = np.stack([keys[late_m], values[late_m], ts[late_m], ts[late_m]], axis=1)
    state = np.zeros((0, 5), np.int64)  # the open sessions, in (key, start) order
    emissions = []
    for c in range(len(wm)):
        part = slice(c * chunk, (c + 1) * chunk)
        k, v, t = (a[part][~late_m[part]] for a in (keys, values, ts))
        live = np.stack([k, t, t + gap, v, np.ones_like(t)], axis=1)
        if len(live):
            # the open sessions of the chunk's keys, which lie in runs of
            # one key each in the state
            n = len(state) + 1
            edges = (np.bincount(np.searchsorted(state[:, 0], live[:, 0], "left"),
                                 minlength=n)
                     - np.bincount(np.searchsorted(state[:, 0], live[:, 0], "right"),
                                   minlength=n))
            touched = np.cumsum(edges[:-1]) > 0
            merged = _merge_overlapping(np.concatenate([state[touched], live]))
            rest = state[~touched]
            state = np.insert(rest, np.searchsorted(rest[:, 0], merged[:, 0]),
                              merged, axis=0)
        due = state[:, 2] <= wm[c]
        fired = state[due]
        emissions.append(fired[np.lexsort((fired[:, 0], fired[:, 1], fired[:, 2]))])
        state = state[~due]
    em = np.concatenate(emissions) if emissions else np.zeros((0, 5), np.int64)
    return em, late, state


def session_shapes(keys, ts, *, gap: int, lateness: int, chunk: int):
    """Per chunk, its live (not late) items and the session fragments among
    them: the runs of one key's live items, in ``ts`` order, whose
    neighbours lie less than ``gap`` apart.  These are the sizes a chunk of
    session windows sends to the plane's reduction."""
    keys = np.asarray(keys, np.int64)
    ts = np.asarray(ts, np.int64)
    c, late_m, wm = _late_sessions(ts, gap=gap, lateness=lateness, chunk=chunk)
    c, k, t = c[~late_m], keys[~late_m], ts[~late_m]
    live = np.bincount(c, minlength=len(wm))
    order = np.lexsort((t, k, c))
    c, k, t = c[order], k[order], t[order]
    new = np.ones(len(c), bool)
    new[1:] = (c[1:] != c[:-1]) | (k[1:] != k[:-1]) | (t[1:] - t[:-1] >= gap)
    return live, np.bincount(c[new], minlength=len(wm))
