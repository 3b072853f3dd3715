"""Segment-reduce / scatter-accumulate — Pallas TPU kernel pair.

The hot path of keyed per-slot state updates (``repro.keyed``): instead of
every owner scanning the whole chunk masked (the S2 masked full-scan
baseline, O(num_cells * m) work), the chunk is sorted by cell id and reduced
segment-at-a-time.  The keyed engine's device path drives the pure-XLA
:func:`segment_sum_sorted` (prefix sum + gather over the sorted chunk).
The Pallas ``segment_sum`` serves :func:`repro.kernels.ops.segment_sum`;
``scatter_add`` folds partials into a device-resident state table.  Neither
kernel is on the engine's hot path.

Both kernels share one TPU-friendly trick: a row block of ``br`` items is
reduced against all ``S`` segments with a single one-hot matmul
``partial[S, d] = onehot[S, br] @ values[br, d]`` — an MXU contraction
instead of a per-row scatter — and the sequential TPU grid accumulates
partials into the (block-constant) output, initialized on the first step.
The contraction is order-blind, so sorting is not required for correctness.

The one-hot holds every segment, so a block costs ``O(br * S)`` and the
output block is ``[S, d]`` in VMEM: these kernels suit a few thousand
segments.  The keyed engine's per-chunk cell reduction runs the scatter-free
:func:`segment_sum_sorted` instead, which has no such bound.

The MXU has no int32 contraction.  Integer inputs are split into four bytes,
each contracted in bfloat16 with a float32 accumulator (a block of at most
256 rows of bytes sums below 2**24, so every partial is exact), and the
bytes are recombined with int32 wraparound — so the keyed engine's
bit-exactness contract holds through the kernel.  Row counts are padded to
the block size with an out-of-range cell id, which the one-hot encoding maps
to zero contribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _acc_dtype(dtype):
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _onehot_partial(ids_block, values_block, num_segments, acc_dtype):
    """``[S, d]`` partial: one-hot of ids (rows beyond ``num_segments`` drop
    out) contracted against the value rows on the MXU.  Integer values go
    through the exact byte split described in the module docstring."""
    br = values_block.shape[0]
    seg = jax.lax.broadcasted_iota(jnp.int32, (num_segments, br), 0)
    hit = ids_block[None, :] == seg
    if acc_dtype == jnp.float32:
        return jnp.dot(
            hit.astype(jnp.float32), values_block.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    if br > 256:
        raise ValueError(f"integer blocks hold at most 256 rows, got {br}")
    onehot = hit.astype(jnp.bfloat16)
    values = values_block.astype(jnp.int32)
    out = jnp.zeros((num_segments, values.shape[1]), jnp.int32)
    for k in range(4):
        byte = ((values >> (8 * k)) & 0xFF).astype(jnp.bfloat16)
        part = jnp.dot(onehot, byte, preferred_element_type=jnp.float32)
        out = out + (part.astype(jnp.int32) << (8 * k))
    return out


def segment_sum_sorted(values, seg_ids, num_segments: int):
    """Pure-XLA segment sum for **sorted** ids: prefix-sum + gather.

    This is what sorting buys off-TPU: no scatter at all.  ``P[k]`` is the
    running prefix total; each segment is a difference of two gathered
    prefix rows (``searchsorted`` finds the segment ends).  Integer
    wraparound makes the differences exact even when the prefix sums
    overflow, as long as the true segment sums fit the accumulator.
    Ids ``>= num_segments`` (padding) sort to the tail and drop out.
    """
    acc = _acc_dtype(values.dtype)
    d = values.shape[1]
    prefix = jnp.concatenate(
        [jnp.zeros((1, d), acc), jnp.cumsum(values.astype(acc), axis=0)],
        axis=0,
    )
    ends = jnp.searchsorted(
        seg_ids, jnp.arange(num_segments, dtype=seg_ids.dtype), side="right"
    )
    totals = prefix[ends]  # sum of all rows with id <= segment
    return totals - jnp.concatenate(
        [jnp.zeros((1, d), acc), totals[:-1]], axis=0
    )


def _segment_sum_kernel(ids_ref, vals_ref, out_ref, *, num_segments: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _onehot_partial(
        ids_ref[0], vals_ref[...], num_segments, out_ref.dtype
    )


def segment_sum(
    values, seg_ids, num_segments: int, *, block_rows: int = 128,
    interpret: bool = False,
):
    """``out[s, :] = sum over rows r with seg_ids[r] == s of values[r, :]``.

    values ``[R, d]`` (int or float), seg_ids ``[R]`` int32 in ``[0, S]``
    (ids ``>= S`` contribute nothing — the caller's padding convention).
    Returns ``[S, d]`` in the i32/f32 accumulator dtype.
    """
    R, d = values.shape
    acc = _acc_dtype(values.dtype)
    if R == 0:
        return jnp.zeros((num_segments, d), acc)
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad, d), values.dtype)], axis=0
        )
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), num_segments, jnp.int32)]
        )
    kernel = functools.partial(_segment_sum_kernel, num_segments=num_segments)
    return pl.pallas_call(
        kernel,
        grid=((R + pad) // br,),
        in_specs=[
            pl.BlockSpec((1, br), lambda i: (0, i)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), acc),
        interpret=interpret,
    )(seg_ids.astype(jnp.int32)[None, :], values)


def _scatter_add_kernel(ids_ref, table_ref, rows_ref, out_ref, *,
                        num_cells: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = table_ref[...]

    out_ref[...] += _onehot_partial(
        ids_ref[0], rows_ref[...], num_cells, out_ref.dtype
    )


def scatter_add(
    table, ids, rows, *, block_rows: int = 128, interpret: bool = False,
):
    """``out = table; out[ids[r], :] += rows[r, :]`` with repeats allowed.

    table ``[C, d]``, ids ``[R]`` int32 in ``[0, C]`` (``>= C`` drops the
    row), rows ``[R, d]``.  Returns the updated ``[C, d]`` table (same
    dtype family as the i32/f32 accumulator).
    """
    C, d = table.shape
    acc = _acc_dtype(table.dtype)
    table = table.astype(acc)
    R = rows.shape[0]
    if R == 0:
        return table
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), rows.dtype)], axis=0)
        ids = jnp.concatenate([ids, jnp.full((pad,), C, jnp.int32)])
    kernel = functools.partial(_scatter_add_kernel, num_cells=C)
    return pl.pallas_call(
        kernel,
        grid=((R + pad) // br,),
        in_specs=[
            pl.BlockSpec((1, br), lambda i: (0, i)),
            pl.BlockSpec((C, d), lambda i: (0, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((C, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, d), acc),
        interpret=interpret,
    )(ids.astype(jnp.int32)[None, :], table, rows)
