"""Hash-table row lookup — the Pallas kernel for device window tables.

The device-resident keyed window table (:mod:`repro.keyed.table`) maps a
cell (a ``(key, window_start)`` pair) to its row in a dense fixed-capacity
slab.  The table invariant (lookups scan the whole probe window, so a live
cell has exactly one row) lets the device realization skip pointer chasing
entirely: matching is a **full-scan one-hot compare** — every cell block is
compared against every table block with broadcast equality, and the row
index is recovered as a min-reduction over match candidates.  No gathers,
no scatters: broadcast compares and min-reductions are exactly what the VPU
wants, the same design point as the one-hot MXU contraction in
``segment_reduce.py``.

The sequential TPU grid runs table blocks innermost; the per-cell-block
output is initialized to the miss sentinel (``capacity``) on the first
table step and min-accumulated across steps.  Because a cell has at most
one live row, min-index equals the unique match.

int64 keys/starts are compared as **lo/hi int32 halves** (four equality
planes ANDed) — TPU vector units have no i64 lanes, and under default
JAX x64-off config ``jnp`` would silently narrow anyway; the dispatch layer
(:func:`repro.kernels.ops.table_lookup`) does the split host-side with
uint64 wraparound so negative keys round-trip exactly.

The accumulate half of the table update runs on the host
(:meth:`repro.keyed.table.BatchedWindowTable.update`); this module only
holds the match/lookup kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _match_candidates(
    cell_lo_hi, table_lo_hi, occ, base: int, capacity: int,
):
    """``[bn, bc]`` candidate row indices: the row index where all four
    int32 planes match an occupied row, else ``capacity`` (the miss/identity
    of the min-accumulation)."""
    (cklo, ckhi, cslo, cshi) = cell_lo_hi
    (tklo, tkhi, tslo, tshi) = table_lo_hi
    m = (
        (tklo[None, :] == cklo[:, None])
        & (tkhi[None, :] == ckhi[:, None])
        & (tslo[None, :] == cslo[:, None])
        & (tshi[None, :] == cshi[:, None])
        & (occ[None, :] != 0)
    )
    idx = base + jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    return jnp.where(m, idx, jnp.int32(capacity))


def _table_lookup_kernel(
    cklo_ref, ckhi_ref, cslo_ref, cshi_ref,
    tklo_ref, tkhi_ref, tslo_ref, tshi_ref, occ_ref,
    out_ref, *, capacity: int, block_table: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, capacity)

    cand = _match_candidates(
        (cklo_ref[0], ckhi_ref[0], cslo_ref[0], cshi_ref[0]),
        (tklo_ref[0], tkhi_ref[0], tslo_ref[0], tshi_ref[0]),
        occ_ref[0],
        base=j * block_table,
        capacity=capacity,
    )
    out_ref[0, :] = jnp.minimum(out_ref[0, :], jnp.min(cand, axis=1))


def table_lookup(
    cell_lo_hi, table_lo_hi, occ, *, block_cells: int = 128,
    block_table: int = 512, interpret: bool = False,
):
    """Row index of each cell in the table, ``capacity`` = miss.

    ``cell_lo_hi``: four int32 ``[n]`` arrays (key lo/hi, start lo/hi);
    ``table_lo_hi``: the same four planes at ``[C]``; ``occ``: int32 ``[C]``
    occupancy.  Returns int32 ``[n]``.  Padding convention: cell padding may
    hold any value (padded outputs are sliced off by the caller); table
    padding must be unoccupied.
    """
    n = cell_lo_hi[0].shape[0]
    capacity = occ.shape[0]
    bn = min(block_cells, n)
    bc = min(block_table, capacity)

    def pad_to(a, mult):
        short = (-a.shape[0]) % mult
        if short:
            a = jnp.concatenate([a, jnp.zeros((short,), a.dtype)])
        return a

    cells = [pad_to(jnp.asarray(a, jnp.int32), bn)[None, :]
             for a in cell_lo_hi]
    table = [pad_to(jnp.asarray(a, jnp.int32), bc)[None, :]
             for a in (*table_lo_hi, occ)]
    n_pad = cells[0].shape[1]
    c_pad = table[0].shape[1]
    kernel = functools.partial(
        _table_lookup_kernel, capacity=capacity, block_table=bc
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn, c_pad // bc),
        in_specs=[pl.BlockSpec((1, bn), lambda i, j: (0, i))] * 4
        + [pl.BlockSpec((1, bc), lambda i, j: (0, j))] * 5,
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(*cells, *table)
    return out[0, :n]


# ---------------------------------------------------------------------------
# batched all-shard lookup (grid over shards)
# ---------------------------------------------------------------------------

def _batched_match_candidates(
    cell_planes, table_planes, occ, base: int, total: int,
):
    """``[bn, bc]`` candidates for the batched table: the four int32 key /
    start planes of :func:`_match_candidates` plus a fifth **owner plane**
    (the cell's shard id vs the row's shard id), so a cell can only match a
    row inside its own shard segment of the stacked plane."""
    (cown, cklo, ckhi, cslo, cshi) = cell_planes
    (town, tklo, tkhi, tslo, tshi) = table_planes
    m = (
        (town[None, :] == cown[:, None])
        & (tklo[None, :] == cklo[:, None])
        & (tkhi[None, :] == ckhi[:, None])
        & (tslo[None, :] == cslo[:, None])
        & (tshi[None, :] == cshi[:, None])
        & (occ[None, :] != 0)
    )
    idx = base + jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    return jnp.where(m, idx, jnp.int32(total))


def _batched_table_lookup_kernel(
    cown_ref, cklo_ref, ckhi_ref, cslo_ref, cshi_ref,
    town_ref, tklo_ref, tkhi_ref, tslo_ref, tshi_ref, occ_ref,
    out_ref, *, total: int, block_table: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, total)

    cand = _batched_match_candidates(
        (cown_ref[0], cklo_ref[0], ckhi_ref[0], cslo_ref[0], cshi_ref[0]),
        (town_ref[0], tklo_ref[0], tkhi_ref[0], tslo_ref[0], tshi_ref[0]),
        occ_ref[0],
        base=j * block_table,
        total=total,
    )
    out_ref[0, :] = jnp.minimum(out_ref[0, :], jnp.min(cand, axis=1))


def batched_table_lookup(
    cell_planes, table_planes, occ, *, block_cells: int = 128,
    block_table: int = 512, interpret: bool = False,
):
    """Global row of each cell in an ``n_w``-shard batched table (stacked
    shard-major to ``[n_w * capacity]`` planes); ``n_w * capacity`` = miss.

    ``cell_planes``: five int32 ``[n]`` arrays (owner, key lo/hi, start
    lo/hi); ``table_planes``: the same five at ``[n_w * capacity]`` (the
    row-owner plane is ``row // capacity``); ``occ``: int32 occupancy.
    The sequential grid walks table blocks innermost — when ``block_table``
    divides ``capacity`` each step visits exactly one shard's rows, i.e.
    the grid IS the loop over shards, executed as ONE kernel dispatch for
    the whole plane; in the general case the owner plane alone keeps
    matches inside the owning segment.  Padding convention matches
    :func:`table_lookup`: cell padding arbitrary, table padding unoccupied.
    """
    n = cell_planes[0].shape[0]
    total = occ.shape[0]
    bn = min(block_cells, n)
    bc = min(block_table, total)

    def pad_to(a, mult):
        short = (-a.shape[0]) % mult
        if short:
            a = jnp.concatenate([a, jnp.zeros((short,), a.dtype)])
        return a

    cells = [pad_to(jnp.asarray(a, jnp.int32), bn)[None, :]
             for a in cell_planes]
    table = [pad_to(jnp.asarray(a, jnp.int32), bc)[None, :]
             for a in (*table_planes, occ)]
    n_pad = cells[0].shape[1]
    c_pad = table[0].shape[1]
    kernel = functools.partial(
        _batched_table_lookup_kernel, total=total, block_table=bc
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn, c_pad // bc),
        in_specs=[pl.BlockSpec((1, bn), lambda i, j: (0, i))] * 5
        + [pl.BlockSpec((1, bc), lambda i, j: (0, j))] * 6,
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(*cells, *table)
    return out[0, :n]
