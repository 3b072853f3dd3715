"""Device-resident keyed window table: dense arrays, open addressing, TTL.

PR 2 realized the fully-partitioned keyed state (§2.4/§4.2, S5 workloads) as
a host dict-of-dicts (:class:`repro.keyed.store.KeyedStore`) — correct, but
the per-chunk merge is a Python loop over cells, which ROADMAP names as the
single-host throughput cap.  This module keeps the key -> window-state table
resident in **dense fixed-capacity arrays** (key slab, window bounds,
accumulators, last-touch timestamps, occupancy bitmap) so the per-chunk
update is whole-chunk vectorized ops — the region-based streaming-state /
transactional-multicore result: the win comes from mutating the table at
stream rate with one fused update instead of per-key interpreter work.

Layout and addressing
    A **row** holds one open cell (a distinct ``(key, window_start)`` pair).
    Rows are addressed by open addressing: a cell's home slot is
    ``cell_hash(key, start) % capacity`` (the same multiplicative-hash family
    as :func:`repro.keyed.store.hash_to_slot`), and an insert probes the
    window ``home .. home + max_probes`` (mod capacity) for a match or an
    empty row.  **Lookup scans the whole probe window** (it does not stop at
    the first empty row), so freeing rows on emission/eviction needs no
    tombstones and a live cell always has exactly one row — the invariant
    that keeps the Pallas full-scan lookup kernel and the numpy probe-window
    realization bit-identical.

Tiering (spill + TTL eviction)
    The host :class:`~repro.keyed.store.KeyedStore` stays on as the
    spill/overflow tier: a cell that cannot be placed within its probe
    window (table full / clustered) is returned to the caller, who merges it
    into the host store; a row idle past ``ttl`` watermark units
    (``last_touch + ttl <= watermark``) is **evicted** to the same tier.
    Tier placement is never semantic — at watermark-close the engine merges
    the due rows of both tiers (sum + count are associative), so emissions
    are bit-exact against :func:`repro.core.semantics.keyed_windows` under
    any capacity, probe budget, or TTL, including pathological ones.

Realizations (the CPU perf-cliff rule of :mod:`repro.keyed.kernels`)
    The numpy probe-window path is the honest CPU realization (XLA's CPU
    sort/scatter lowering loses to numpy's C kernels by an order of
    magnitude here).  When the Pallas kernels are active, lookup dispatches
    to :func:`repro.kernels.ops.table_lookup` — the one-hot full-scan match
    kernel (``kernels/hash_table.py``); claims and the accumulate half stay
    in numpy.  All paths produce bit-identical tables.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.keyed.store import HASH_MULTIPLIER

#: second mix constant (64-bit golden ratio) — decorrelates the window start
#: from the key before the multiplicative hash spreads the cell over rows
_START_MIX = np.uint64(0x9E3779B97F4A7C15)

#: last-touch sentinel for a just-claimed row: far enough below any event
#: time that the first ``max(touch, ts)`` always wins (event times may be
#: negative under disorder), far enough above INT64_MIN that ``touch + ttl``
#: never wraps
_NEVER_TOUCHED = np.int64(-(2 ** 62))


def cell_hash(keys, starts, capacity: int) -> np.ndarray:
    """Home row of each ``(key, window_start)`` cell in ``[0, capacity)``.

    uint64 wraparound arithmetic end to end (negative keys wrap exactly like
    :func:`repro.keyed.store.hash_to_slot`), so scalar and array callers and
    every realization agree bit-for-bit."""
    k = np.asarray(keys, np.int64).astype(np.uint64)
    s = np.asarray(starts, np.int64).astype(np.uint64)
    with np.errstate(over="ignore"):  # uint64 wraparound is the point
        mix = k * np.uint64(HASH_MULTIPLIER) + s * _START_MIX
        return (
            (mix * np.uint64(HASH_MULTIPLIER)) % np.uint64(capacity)
        ).astype(np.int64)


def _claim_rows(
    key, start, end, value, count, touch, occ, cand, ck, cs, ce, stats,
) -> np.ndarray:
    """The open-addressing claim loop shared by the per-shard table and the
    batched all-shard plane (the caller supplies the candidate-row matrix
    ``cand`` — per-shard probe windows or owner-segment-offset global
    windows — and the column arrays, slab or flattened-plane views).

    Deterministic conflict rule: when several cells want the same empty row
    in the same round, the first cell in canonical order wins; losers move
    on to their next in-window empty row in the next round.  Every round
    places at least the first still-active cell, so the loop is bounded by
    the batch size.  ONE implementation serves both paths, so the
    fused==loop placement bit-exactness cannot drift.
    """
    n = len(ck)
    rows = np.full(n, -1, np.int64)
    if not n:
        return rows
    active = np.arange(n)
    while len(active):
        free = ~occ[cand[active]]                        # [a, P]
        has_free = free.any(axis=1)
        spill = active[~has_free]
        if len(spill):
            stats.spilled += len(spill)
        active = active[has_free]
        if not len(active):
            break
        first = np.argmax(free[has_free], axis=1)
        want = cand[active, first]
        # first claimant (canonical cell order) per row wins this round
        _, winner_pos = np.unique(want, return_index=True)
        winners = active[winner_pos]
        w_rows = want[winner_pos]
        rows[winners] = w_rows
        occ[w_rows] = True
        key[w_rows] = ck[winners]
        start[w_rows] = cs[winners]
        end[w_rows] = ce[winners]
        value[w_rows] = 0
        count[w_rows] = 0
        touch[w_rows] = _NEVER_TOUCHED
        stats.inserted += len(winners)
        keep = np.ones(len(active), bool)
        keep[winner_pos] = False
        active = active[keep]
    return rows


@dataclasses.dataclass
class TableStats:
    """Placement accounting (not part of window semantics)."""

    inserted: int = 0   # cells that claimed a fresh row
    hits: int = 0       # cells that accumulated into an existing row
    spilled: int = 0    # cells handed to the host tier (probe window full)
    evicted: int = 0    # rows moved to the host tier by TTL


class DeviceWindowTable:
    """Fixed-capacity open-addressed table of open ``(key, window)`` cells.

    ``capacity`` rows; each row is ``(key, start, end, value, count,
    last_touch)`` plus an occupancy bit.  All mutators take **canonically
    sorted, duplicate-free** cell batches (the engine's ``np.unique`` output)
    — that is what makes claim conflicts deterministic.
    """

    COLUMNS = ("key", "start", "end", "value", "count", "touch")

    def __init__(self, capacity: int, *, max_probes: int = 16):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if max_probes < 1:
            raise ValueError(f"max_probes must be >= 1, got {max_probes}")
        self.capacity = capacity
        self.max_probes = min(max_probes, capacity)
        self.key = np.zeros(capacity, np.int64)
        self.start = np.zeros(capacity, np.int64)
        self.end = np.zeros(capacity, np.int64)
        self.value = np.zeros(capacity, np.int64)
        self.count = np.zeros(capacity, np.int64)
        self.touch = np.zeros(capacity, np.int64)
        self.occ = np.zeros(capacity, bool)
        self.stats = TableStats()

    # -- introspection ---------------------------------------------------------
    @property
    def occupancy(self) -> int:
        return int(self.occ.sum())

    @property
    def load_factor(self) -> float:
        return self.occupancy / self.capacity

    def rows(self) -> np.ndarray:
        """Occupied rows as an ``[n, 6]`` int64 matrix in row-index order
        (columns per :attr:`COLUMNS`) — placement order, NOT canonical."""
        idx = np.flatnonzero(self.occ)
        return np.stack(
            [self.key[idx], self.start[idx], self.end[idx],
             self.value[idx], self.count[idx], self.touch[idx]],
            axis=1,
        )

    def probe_distances(self) -> np.ndarray:
        """Displacement of every occupied row from its cell's home slot
        (``(row - home) % capacity``) — the open-addressing clustering
        signal the health gauges summarize (mean/max probe distance)."""
        idx = np.flatnonzero(self.occ)
        if not len(idx):
            return np.zeros(0, np.int64)
        home = cell_hash(self.key[idx], self.start[idx], self.capacity)
        return (idx - home) % self.capacity

    def health(self) -> dict:
        """Flat health snapshot: occupancy/load plus probe-distance stats
        (zeros on an empty table) — what ``export_health`` turns into
        per-shard gauges."""
        d = self.probe_distances()
        return {
            "capacity": self.capacity,
            "occupancy": self.occupancy,
            "load_factor": self.load_factor,
            "probe_mean": float(d.mean()) if len(d) else 0.0,
            "probe_max": int(d.max()) if len(d) else 0,
        }

    # -- probe-window lookup ---------------------------------------------------
    def _probe_window(self, h: np.ndarray) -> np.ndarray:
        """``[n, P]`` candidate rows for home slots ``h`` (wrapping)."""
        return (h[:, None] + np.arange(self.max_probes, dtype=np.int64)) \
            % self.capacity

    def lookup(self, cell_keys, cell_starts) -> np.ndarray:
        """Row of each cell, or ``-1`` for absent cells.

        Scans the full probe window (no early stop at empties — see module
        docstring), dispatched to the Pallas one-hot match kernel when the
        kernels are active and the numpy gather-and-compare realization
        otherwise; both return the identical (unique) row.
        """
        ck = np.asarray(cell_keys, np.int64)
        cs = np.asarray(cell_starts, np.int64)
        if not len(ck):
            return np.zeros(0, np.int64)
        from repro.kernels import ops  # late import: keyed.store must not pull jax

        if ops.kernels_active():
            rows = np.asarray(
                ops.table_lookup(ck, cs, self.key, self.start, self.occ),
                np.int64,
            )
            return np.where(rows >= self.capacity, np.int64(-1), rows)
        cand = self._probe_window(cell_hash(ck, cs, self.capacity))
        m = (
            self.occ[cand]
            & (self.key[cand] == ck[:, None])
            & (self.start[cand] == cs[:, None])
        )
        first = np.argmax(m, axis=1)
        hit = m.any(axis=1)
        rows = cand[np.arange(len(ck)), first]
        return np.where(hit, rows, np.int64(-1))

    # -- open-addressing claim -------------------------------------------------
    def _claim(self, ck, cs, ce) -> np.ndarray:
        """Claim a row for each (absent) cell; ``-1`` = spill (the shared
        deterministic claim loop — see :func:`_claim_rows`)."""
        return _claim_rows(
            self.key, self.start, self.end, self.value, self.count,
            self.touch, self.occ,
            self._probe_window(cell_hash(ck, cs, self.capacity)),
            ck, cs, ce, self.stats,
        )

    # -- the per-chunk fused update --------------------------------------------
    def update(
        self, cell_keys, cell_starts, cell_ends, value_sums, counts,
        touch_ts: int,
    ) -> Optional[Tuple[np.ndarray, ...]]:
        """Accumulate per-cell partials into the table; returns the spill.

        Cells must be canonically sorted and duplicate-free.  Existing rows
        accumulate (``value += sum``, ``count += n``, ``touch = max(touch,
        touch_ts)``); absent cells claim rows via open addressing; cells that
        cannot be placed are returned as ``(key, start, end, value, count)``
        arrays for the caller's host tier (``None`` when nothing spilled).
        """
        ck = np.asarray(cell_keys, np.int64)
        cs = np.asarray(cell_starts, np.int64)
        ce = np.asarray(cell_ends, np.int64)
        vs = np.asarray(value_sums, np.int64)
        cn = np.asarray(counts, np.int64)
        if not len(ck):
            return None
        rows = self.lookup(ck, cs)
        miss = rows < 0
        self.stats.hits += int((~miss).sum())
        if miss.any():
            rows[miss] = self._claim(ck[miss], cs[miss], ce[miss])
        ok = rows >= 0
        r = rows[ok]
        np.add.at(self.value, r, vs[ok])
        np.add.at(self.count, r, cn[ok])
        np.maximum.at(self.touch, r, np.int64(touch_ts))
        if ok.all():
            return None
        sp = ~ok
        return ck[sp], cs[sp], ce[sp], vs[sp], cn[sp]

    # -- watermark close / TTL eviction ----------------------------------------
    def _extract(self, mask: np.ndarray) -> Tuple[np.ndarray, ...]:
        idx = np.flatnonzero(mask)
        out = (
            self.key[idx].copy(), self.start[idx].copy(),
            self.end[idx].copy(), self.value[idx].copy(),
            self.count[idx].copy(), self.touch[idx].copy(),
        )
        self.occ[idx] = False
        return out

    def take_due(self, watermark: int) -> Tuple[np.ndarray, ...]:
        """Remove and return every row with ``end <= watermark`` (the
        watermark-close set), as ``(key, start, end, value, count, touch)``
        arrays in row-index order — the engine sorts the merged emission."""
        return self._extract(self.occ & (self.end <= watermark))

    def evict_idle(self, watermark: int, ttl: int) -> Tuple[np.ndarray, ...]:
        """Remove and return rows idle past ``ttl`` watermark units
        (``touch + ttl <= watermark``) — the TTL spill to the host tier."""
        out = self._extract(self.occ & (self.touch + ttl <= watermark))
        self.stats.evicted += len(out[0])
        return out

    def clear(self) -> None:
        self.occ[:] = False

    # -- canonical round-trip --------------------------------------------------
    def insert_rows(
        self, keys, starts, ends, values, counts, touches,
    ) -> Optional[Tuple[np.ndarray, ...]]:
        """Bulk-place fully-formed rows (checkpoint restore / rebuild after
        resize).  Rows must be canonically sorted; placement is by the same
        claim rule as live inserts, so a rebuild is deterministic.  Rows
        that do not fit are returned (same layout as :meth:`update` spill,
        plus the touch column) for the host tier."""
        ck = np.asarray(keys, np.int64)
        if not len(ck):
            return None
        cs = np.asarray(starts, np.int64)
        ce = np.asarray(ends, np.int64)
        rows = self._claim(ck, cs, ce)
        ok = rows >= 0
        r = rows[ok]
        self.value[r] = np.asarray(values, np.int64)[ok]
        self.count[r] = np.asarray(counts, np.int64)[ok]
        self.touch[r] = np.asarray(touches, np.int64)[ok]
        if ok.all():
            return None
        sp = ~ok
        return (
            ck[sp],
            cs[sp],
            ce[sp],
            np.asarray(values, np.int64)[sp],
            np.asarray(counts, np.int64)[sp],
            np.asarray(touches, np.int64)[sp],
        )

    # -- §4.2 ownership over rows ----------------------------------------------
    def extract_slot_rows(
        self, slots, num_slots: int
    ) -> Tuple[np.ndarray, ...]:
        """Remove and return every occupied row whose key hashes to a slot in
        ``slots`` (the :meth:`_extract` mask applied to slot ownership) — the
        device tier's half of a row-level slot migration.  Same layout as
        :meth:`take_due`; rows leave in canonical ``(key, start)`` order so
        the recipient's re-insertion is deterministic."""
        from repro.keyed.store import hash_to_slot

        idx = np.flatnonzero(self.occ)
        if not len(idx):
            return self._extract(np.zeros(self.capacity, bool))
        row_slots = hash_to_slot(self.key[idx], num_slots).astype(np.int64)
        mask = np.zeros(self.capacity, bool)
        mask[idx[np.isin(row_slots, np.asarray(slots, np.int64))]] = True
        out = self._extract(mask)
        order = np.lexsort((out[2], out[1], out[0]))
        return tuple(col[order] for col in out)

    def owners(self, slot_table: np.ndarray, num_slots: int) -> np.ndarray:
        """Owner worker of every occupied row (row keys hashed through the
        engine's slot map) — what resize accounting migrates."""
        from repro.keyed.store import hash_to_slot

        idx = np.flatnonzero(self.occ)
        slots = hash_to_slot(self.key[idx], num_slots).astype(np.int64)
        return np.asarray(slot_table, np.int64)[slots]


# ---------------------------------------------------------------------------
# batched all-shard plane
# ---------------------------------------------------------------------------

class BatchedWindowTable:
    """Shard-major stack of ``n_w`` per-shard tables: one ``(n_w, capacity)``
    plane per column, driven by whole-chunk batched mutators.

    Construction **adopts** the shards' slabs: each column is stacked into a
    single ``(n_w, capacity)`` plane and every shard's
    :class:`DeviceWindowTable` is re-pointed at its row of the stack, so the
    per-shard tables become *views* — per-shard mutators (the ``fused=False``
    loop, row-level slot migration) and the batched whole-plane mutators
    below see the same storage, and the barrier snapshot / extract paths
    keep working unchanged.

    Addressing: a cell owned by shard ``w`` lives only in global rows
    ``[w * capacity, (w + 1) * capacity)`` — the shard id is the leading
    component of the cell address, and the probe window wraps *within* the
    shard segment (``w * capacity + (home + p) % capacity``).  Claim
    conflicts are therefore intra-shard only, and because the global
    canonical cell order restricted to one shard equals that shard's own
    canonical order, batched claims place every row exactly where the
    per-shard loop would — the fused and loop paths are bit-identical by
    construction, not by tolerance.

    Placement stats accumulate on shard 0's :class:`TableStats` (the
    stream-global counter home the sharded plane already uses); the barrier
    sums per-shard counters, so fused and loop runs serialize identically.

    Incremental restack (resize without the full-plane memcpy)
        The planes are **over-allocated**: storage holds ``alloc >=
        n_shards`` segments and the public arrays (``key`` / ``occ`` /
        flat views / ``row_owner``) are active-prefix *views* of the first
        ``n_shards``.  Because :meth:`SlotMap.rebalance` keeps survivor
        shard ids stable, a resize never moves a survivor's segment:
        :meth:`restack` re-slices the prefix (shrink), occupancy-clears and
        adopts fresh empty segments in place (grow within ``alloc``), and
        only copies anything when the allocation itself must grow —
        ``copied_bytes`` counts exactly those bytes, so a regression test
        can pin in-place resizes to **zero** slab traffic and the resize
        cost stays proportional to migrated rows.
    """

    _PLANES = ("key", "start", "end", "value", "count", "touch", "occ")

    def __init__(self, tables: List[DeviceWindowTable], *, reserve: int = 0):
        if not tables:
            raise ValueError("need at least one shard table")
        cap = tables[0].capacity
        if any(t.capacity != cap or t.max_probes != tables[0].max_probes
               for t in tables):
            raise ValueError("shard tables must agree on capacity/max_probes")
        self.capacity = cap
        self.max_probes = tables[0].max_probes
        #: bytes memcpy'd by restacks (plane realloc / foreign-slab adopt);
        #: stays 0 across resizes that fit the allocation — the gateable
        #: "no full restack" signal
        self.copied_bytes = 0
        self._alloc = max(len(tables), reserve, 1)
        for name in self._PLANES:
            dt = bool if name == "occ" else np.int64
            setattr(self, f"_a{name}", np.zeros((self._alloc, cap), dt))
        self._arow_owner = np.repeat(
            np.arange(self._alloc, dtype=np.int32), cap
        )
        for w, t in enumerate(tables):
            for name in self._PLANES:
                getattr(self, f"_a{name}")[w] = getattr(t, name)
        self.n_shards = len(tables)
        self._activate()
        self._adopt(tables)

    def _activate(self) -> None:
        """Re-derive the active-prefix views from the backing planes:
        ``(n_shards, capacity)`` per column, their C-contiguous flat
        aliases (global row = ``w*cap + row``), and the row-owner column —
        all views, never copies."""
        n = self.n_shards
        for name in self._PLANES:
            plane = getattr(self, f"_a{name}")[:n]
            setattr(self, name, plane)
            setattr(self, f"_f{name}", plane.reshape(-1))
        #: shard id of every global row — the kernel's 5th match plane
        self.row_owner = self._arow_owner[: n * self.capacity]

    def _adopt(self, tables: List[DeviceWindowTable]) -> None:
        """Re-point every shard table at its segment of the planes (the
        tables become views) and remember the adopted objects so a later
        :meth:`restack` can recognize unmoved segments by identity."""
        for w, t in enumerate(tables):
            t.key, t.start, t.end = self.key[w], self.start[w], self.end[w]
            t.value, t.count = self.value[w], self.count[w]
            t.touch, t.occ = self.touch[w], self.occ[w]
        self._adopted: List[DeviceWindowTable] = list(tables)
        self.stats = tables[0].stats

    def _realloc(self, alloc2: int) -> None:
        """Grow the backing planes; the ONLY place a survivor segment is
        ever copied, and every byte is charged to ``copied_bytes``."""
        n = self.n_shards
        for name in self._PLANES:
            old = getattr(self, f"_a{name}")
            new = np.zeros((alloc2, self.capacity), old.dtype)
            new[:n] = old[:n]
            self.copied_bytes += old[:n].nbytes
            setattr(self, f"_a{name}", new)
        self._arow_owner = np.repeat(
            np.arange(alloc2, dtype=np.int32), self.capacity
        )
        self._alloc = alloc2

    def restack(self, tables: List[DeviceWindowTable]) -> None:
        """Re-form the plane for a resized shard list WITHOUT a full
        restack: survivor tables (recognized by identity — rebalance keeps
        their ids, so shard ``w`` always owns segment ``w``) are untouched;
        a shrink is a prefix re-slice; a grow adopts fresh empty segments
        by clearing occupancy in place.  Slab bytes move only on an
        allocation doubling (``copied_bytes``), so resize cost is strictly
        row-proportional: the migrated rows' ``ingest_rows`` writes land
        directly in the adopted segments."""
        if any(t.capacity != self.capacity or t.max_probes != self.max_probes
               for t in tables):
            raise ValueError("shard tables must agree on capacity/max_probes")
        if len(tables) > self._alloc:
            self._realloc(max(len(tables), 2 * self._alloc))
        prior = self._adopted
        for w, t in enumerate(tables):
            if w < len(prior) and t is prior[w]:
                continue  # survivor: its segment never moves
            if t.occ.any():
                # foreign non-empty table (restore path): copy its slab in
                for name in self._PLANES:
                    getattr(self, f"_a{name}")[w] = getattr(t, name)
                    self.copied_bytes += getattr(t, name).nbytes
            else:
                # fresh shard joining a grow: an empty segment is just a
                # cleared occupancy row — zero column traffic
                self._aocc[w][:] = False
        self.n_shards = len(tables)
        self._activate()
        self._adopt(tables)

    @property
    def total_rows(self) -> int:
        return self.n_shards * self.capacity

    def _probe_window(self, owners: np.ndarray, h: np.ndarray) -> np.ndarray:
        """``[n, P]`` global candidate rows: the per-shard probe window
        offset into each owner's segment (never crosses a shard boundary)."""
        probes = (h[:, None] + np.arange(self.max_probes, dtype=np.int64)) \
            % self.capacity
        return owners[:, None] * self.capacity + probes

    # -- batched lookup --------------------------------------------------------
    def lookup(self, owners, cell_keys, cell_starts) -> np.ndarray:
        """Global row of each ``(owner, key, start)`` cell, ``-1`` = absent.

        One dispatch for ALL shards: the Pallas grid-over-shards full-scan
        match kernel (:func:`repro.kernels.ops.batched_table_lookup`) when
        the kernels are active, the numpy probe-window realization on CPU
        (the XLA-CPU-cliff rule); both return the identical unique row.
        """
        ck = np.asarray(cell_keys, np.int64)
        cs = np.asarray(cell_starts, np.int64)
        ow = np.asarray(owners, np.int64)
        if not len(ck):
            return np.zeros(0, np.int64)
        from repro.kernels import ops  # late import: keyed.store must not pull jax

        if ops.kernels_active():
            rows = np.asarray(
                ops.batched_table_lookup(
                    ow, ck, cs, self.row_owner, self._fkey, self._fstart,
                    self._focc,
                ),
                np.int64,
            )
            return np.where(rows >= self.total_rows, np.int64(-1), rows)
        cand = self._probe_window(ow, cell_hash(ck, cs, self.capacity))
        m = (
            self._focc[cand]
            & (self._fkey[cand] == ck[:, None])
            & (self._fstart[cand] == cs[:, None])
        )
        first = np.argmax(m, axis=1)
        hit = m.any(axis=1)
        rows = cand[np.arange(len(ck)), first]
        return np.where(hit, rows, np.int64(-1))

    # -- batched open-addressing claim -----------------------------------------
    def _claim(self, owners, ck, cs, ce) -> np.ndarray:
        """Claim a global row per (absent) cell; ``-1`` = spill.  THE same
        claim loop as :meth:`DeviceWindowTable._claim` (shared
        :func:`_claim_rows`), fed owner-segment candidate windows: probe
        windows stay inside the owner's segment, so all conflicts are
        intra-shard and resolve in the shard's own canonical cell order."""
        return _claim_rows(
            self._fkey, self._fstart, self._fend, self._fvalue,
            self._fcount, self._ftouch, self._focc,
            self._probe_window(owners, cell_hash(ck, cs, self.capacity)),
            ck, cs, ce, self.stats,
        )

    # -- the whole-plane fused update ------------------------------------------
    def update(
        self, owners, cell_keys, cell_starts, cell_ends, value_sums, counts,
        touch_ts: int,
    ) -> Optional[Tuple[np.ndarray, ...]]:
        """Accumulate ALL shards' per-cell partials in one pass: a single
        lookup dispatch, a single claim loop, a single scatter-add over the
        stacked planes.  Cells must be canonically sorted and duplicate-free
        across the whole batch.  Returns the spill as ``(owner, key, start,
        end, value, count)`` arrays (``None`` when nothing spilled) — the
        caller merges each spilled cell into its owner's host tier."""
        ow = np.asarray(owners, np.int64)
        ck = np.asarray(cell_keys, np.int64)
        cs = np.asarray(cell_starts, np.int64)
        ce = np.asarray(cell_ends, np.int64)
        vs = np.asarray(value_sums, np.int64)
        cn = np.asarray(counts, np.int64)
        if not len(ck):
            return None
        rows = self.lookup(ow, ck, cs)
        miss = rows < 0
        self.stats.hits += int((~miss).sum())
        if miss.any():
            rows[miss] = self._claim(ow[miss], ck[miss], cs[miss], ce[miss])
        ok = rows >= 0
        r = rows[ok]
        np.add.at(self._fvalue, r, vs[ok])
        np.add.at(self._fcount, r, cn[ok])
        np.maximum.at(self._ftouch, r, np.int64(touch_ts))
        if ok.all():
            return None
        sp = ~ok
        return ow[sp], ck[sp], cs[sp], ce[sp], vs[sp], cn[sp]

    # -- batched watermark close / TTL eviction --------------------------------
    def _extract(self, mask: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Remove masked rows; returns ``(owner, key, start, end, value,
        count, touch)`` in global (shard-major) row order — the same order
        the per-shard loop produces shard by shard."""
        idx = np.flatnonzero(mask)
        out = (
            self.row_owner[idx].astype(np.int64),
            self._fkey[idx].copy(), self._fstart[idx].copy(),
            self._fend[idx].copy(), self._fvalue[idx].copy(),
            self._fcount[idx].copy(), self._ftouch[idx].copy(),
        )
        self._focc[idx] = False
        return out

    def take_due(self, watermark: int) -> Tuple[np.ndarray, ...]:
        """Remove and return every due row of EVERY shard (``end <=
        watermark``) in one mask over the stacked planes."""
        return self._extract(self._focc & (self._fend <= watermark))

    def evict_idle(self, watermark: int, ttl: int) -> Tuple[np.ndarray, ...]:
        """One TTL sweep over all shards; the owner column routes each
        evicted row back to its shard's host tier."""
        out = self._extract(
            self._focc & (self._ftouch + ttl <= watermark)
        )
        self.stats.evicted += len(out[0])
        return out

    def open_rows(self) -> Tuple[np.ndarray, ...]:
        """Every occupied row of every shard (global row order), WITHOUT
        removing — the early-firing provisional-pane source."""
        idx = np.flatnonzero(self._focc)
        return (
            self._fkey[idx], self._fstart[idx], self._fend[idx],
            self._fvalue[idx], self._fcount[idx],
        )

    def per_shard_occupancy(self) -> np.ndarray:
        """Occupied-row count per shard — one reduction over the stacked
        occupancy plane."""
        return self.occ.sum(axis=1).astype(np.int64)

    def per_shard_health(self) -> List[dict]:
        """One :meth:`DeviceWindowTable.health`-shaped snapshot per shard,
        computed over the stacked planes (probe distances are intra-segment:
        a row's home is within its shard's own ``capacity`` ring)."""
        out = []
        for w in range(self.n_shards):
            idx = np.flatnonzero(self.occ[w])
            if len(idx):
                home = cell_hash(self.key[w][idx], self.start[w][idx],
                                 self.capacity)
                d = (idx - home) % self.capacity
            else:
                d = np.zeros(0, np.int64)
            out.append({
                "capacity": self.capacity,
                "occupancy": int(len(idx)),
                "load_factor": len(idx) / self.capacity,
                "probe_mean": float(d.mean()) if len(d) else 0.0,
                "probe_max": int(d.max()) if len(d) else 0,
            })
        return out
