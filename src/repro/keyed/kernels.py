"""Per-chunk cell reduction: the keyed engine's hot path and its baseline.

A chunk of keyed window assignments is reduced to one partial aggregate per
**cell** (a distinct ``(key, window)`` pair, numbered ``0..num_cells``).
Two interchangeable implementations:

* ``"segment"`` — the hot path, O(m log m + cells) work: stable
  sort-by-cell followed by a segment reduce.  When the Pallas kernels are
  active (TPU, or forced via ``use_kernels``) this runs on the device: an
  XLA sort feeding the scatter-free prefix-sum reduction
  :func:`repro.kernels.segment_reduce.segment_sum_sorted`.  Otherwise it is
  the same algorithm in numpy's C kernels (radix sort + prefix-sum
  difference), the honest CPU realization.
* ``"masked"`` — the S2 masked full-scan baseline, shaped exactly like
  ``PartitionedState.run``'s per-slot scan: a sequential ``lax.scan`` over
  the chunk in which every cell inspects every item through a mask,
  O(num_cells * m) work.  This is what the keyed subsystem replaces;
  ``benchmarks/keyed_throughput.py`` measures the gap.

Both produce bit-identical int32 partials (sums and counts), so the engine's
exactness contract is implementation-independent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels import segment_reduce as sr
from repro.obs.trace import NULL_TRACER

IMPLS = ("segment", "masked")


def dedup_cells(keys, starts):
    """Canonical duplicate-free cell batch over the ``(key, start)``
    columns.  Returns ``(cells [n, 2] int64, inverse [m])`` with cells in
    lexicographic ``(key, start)`` order — the canonical order every table
    mutator requires.  Because ownership is a function of the key, the
    global canonical order restricted to one shard IS that shard's
    canonical order, which is what lets the fused all-shard plane dedup a
    chunk once instead of once per shard.

    Implemented as a lexsort + boundary flags rather than
    ``np.unique(axis=0)``: the axis-unique path compares rows through a
    void view (a memcmp per comparison), several times slower than two
    keyed integer sorts for the same result — this is the hottest single
    op of the per-chunk ingest.
    """
    k = np.asarray(keys, np.int64)
    s = np.asarray(starts, np.int64)
    if not len(k):
        return np.zeros((0, 2), np.int64), np.zeros(0, np.int64)
    order = np.lexsort((s, k))
    ks, ss = k[order], s[order]
    new = np.ones(len(ks), bool)
    new[1:] = (ks[1:] != ks[:-1]) | (ss[1:] != ss[:-1])
    inv = np.empty(len(ks), np.int64)
    inv[order] = np.cumsum(new) - 1
    return np.stack([ks[new], ss[new]], axis=1), inv


def sort_by_cell(cell_ids, values):
    """Stable sort of (cell_ids, values) by cell id — the 'sort-by-key' half
    of the hot path; stability keeps equal-cell rows in stream order."""
    order = jnp.argsort(cell_ids, stable=True)
    return cell_ids[order], values[order]


@functools.partial(jax.jit, static_argnames=("num_cells",))
def _device_segment_path(cell_ids, values, num_cells: int):
    # device shape of the hot path: XLA sort feeding the prefix-sum reduce;
    # the scope names its ops in a profile whatever this function is called
    with jax.named_scope("reduce_by_cell"):
        ids_sorted, vals_sorted = sort_by_cell(cell_ids, values)
        return sr.segment_sum_sorted(vals_sorted, ids_sorted, num_cells)


def _host_segment_path(cell_ids, values, num_cells: int):
    # CPU shape of the same algorithm: numpy radix sort + prefix-sum
    # difference (XLA's CPU sort/cumsum are comparator/loop lowering — an
    # order of magnitude slower than numpy's C kernels here)
    ids = np.asarray(cell_ids)
    order = np.argsort(ids, kind="stable")
    ids_s = ids[order]
    vals_s = np.asarray(values, np.int64)[order]
    prefix = np.concatenate(
        [np.zeros((1, vals_s.shape[1]), np.int64),
         np.cumsum(vals_s, axis=0)],
    )
    ends = np.searchsorted(ids_s, np.arange(num_cells), side="right")
    totals = prefix[ends]
    out = np.diff(
        np.concatenate([np.zeros((1, vals_s.shape[1]), np.int64), totals]),
        axis=0,
    )
    return out.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("num_cells",))
def _masked_path(cell_ids, values, num_cells: int):
    cells = jnp.arange(num_cells, dtype=jnp.int32)[:, None]

    def step(acc, row):
        cid, val = row
        return acc + jnp.where(cells == cid, val[None, :], 0), None

    acc0 = jnp.zeros((num_cells, values.shape[1]), jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, (cell_ids, values.astype(jnp.int32)))
    return acc


def reduce_by_cell(cell_ids, values, num_cells: int, *, impl: str = "segment",
                   tracer=NULL_TRACER):
    """Per-cell sums of ``values [m, d]`` grouped by ``cell_ids [m]``.

    Returns an int32 ``[num_cells, d]`` table.  ``impl`` selects the sorted
    segment-reduce hot path or the masked full-scan baseline (see module
    docstring); both are exact for int32-range data.  On the device path
    ``tracer`` times the shipping of ids and values and the wait for the
    sums (``segment.ship`` / ``segment.wait``).
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if num_cells == 0 or cell_ids.shape[0] == 0:
        return jnp.zeros((num_cells, values.shape[1]), jnp.int32)
    if impl == "segment":
        if ops.kernels_active():
            ids, vals = ops.ship(
                tracer, "segment", np.asarray(cell_ids, np.int32),
                np.asarray(values, np.int32),
            )
            return ops.wait(
                tracer, "segment", _device_segment_path(ids, vals, num_cells)
            )
        return _host_segment_path(cell_ids, values, num_cells)
    return _masked_path(
        jnp.asarray(cell_ids, jnp.int32), jnp.asarray(values, jnp.int32),
        num_cells,
    )
