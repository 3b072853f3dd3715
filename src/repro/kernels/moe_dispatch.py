"""MoE token dispatch (gather) — Pallas TPU kernel with scalar prefetch.

The paper's fully-partitioned (S2) routing on-chip: the emitter's hash table
(`row_token`, built by the sort-based capacity packer in
`repro.models.moe.dispatch_indices`) is SCALAR-PREFETCHED so the input
`index_map` can route each buffer row to its source token — TPU's answer to
the CUDA gather/scatter dispatch (DESIGN §8).  Rows mapped to the dummy
token (== T) read a zero row instead.

The combine (weighted scatter-add) stays an XLA scatter: revisiting output
blocks in arbitrary order is not a TPU-grid-friendly pattern, and the
scatter is bandwidth-bound either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_token_ref, x_ref, o_ref, *, rows_per_block: int, num_tokens: int):
    r0 = pl.program_id(0) * rows_per_block
    # x_ref block = [rows_per_block, d] rows gathered by the index map is not
    # possible for multiple rows per block, so rows_per_block == 1 here: the
    # index map has already routed x_ref to the right token row.
    tok = row_token_ref[r0]
    valid = tok < num_tokens
    row = x_ref[0].astype(o_ref.dtype)
    o_ref[0] = jnp.where(valid, row, jnp.zeros_like(row))


def moe_gather(x, row_token, *, interpret: bool = False):
    """x [T, d]; row_token [R] int32 in [0, T] (T = dummy).  Returns [R, d].

    Equivalent to `ref.moe_gather_ref` (x padded with a zero row)."""
    T, d = x.shape
    R = row_token.shape[0]

    kernel = functools.partial(_kernel, rows_per_block=1, num_tokens=T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[
            pl.BlockSpec(
                (1, d), lambda r, row_token: (jnp.minimum(row_token[r], T - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, d), lambda r, row_token: (r, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        interpret=interpret,
    )(row_token, x)
