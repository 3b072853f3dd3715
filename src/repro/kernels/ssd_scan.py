"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the GPU SSD kernel (DESIGN §8): no warp-level parallel
scan; instead the chunked formulation turns intra-chunk work into dense
(chunk x chunk) and (chunk x N) MXU matmuls, and the only sequential piece —
the inter-chunk state carry h [N, P] — lives in VMEM scratch across the
innermost (chunk) grid axis.  Grid = (B, H, S/chunk).

Inputs are per-head expanded: x [B,H,S,P], dt [B,H,S] (already softplus'd,
fp32), A [H] (negative), Bm/Cm [B,H,S,N].  Outputs y [B,H,S,P] and the final
state h [B,H,N,P].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,
    y_ref, hout_ref,
    h_scr,
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)       # [c, P]
    dt = dt_ref[0, 0].astype(jnp.float32)     # [1, c] (lane-major block)
    a = a_ref[0]                              # scalar A for this head
    bmat = b_ref[0, 0].astype(jnp.float32)    # [c, N]
    cmat = c_ref[0, 0].astype(jnp.float32)    # [c, N]

    dA = dt[0] * a                            # [c] (negative)
    cum = jnp.cumsum(dA)                      # [c]
    total = cum[-1]
    x_dt = x * dt[0][:, None]                 # [c, P]

    # intra-chunk: y_diag = (C B^T * L) x_dt, L[i,j] = exp(cum_i - cum_j), i>=j
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [c, c]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.clip(cum[:, None] - cum[None, :], -60.0, 0.0))
    L = jnp.where(ii >= jj, decay, 0.0)
    y = jax.lax.dot_general(
        scores * L, x_dt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [c, P]

    # carry-in contribution: y += C exp(cum) h_prev
    out_decay = jnp.exp(jnp.clip(cum, -60.0, 0.0))[:, None]      # [c, 1]
    y = y + jax.lax.dot_general(
        cmat * out_decay, h_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: h = h * exp(total) + sum_j exp(total - cum_j) B_j x_j
    in_decay = jnp.exp(jnp.clip(total - cum, -60.0, 0.0))[:, None]  # [c, 1]
    h_new = h_scr[...] * jnp.exp(jnp.clip(total, -60.0, 0.0)) + jax.lax.dot_general(
        bmat * in_decay, x_dt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [N, P]
    h_scr[...] = h_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(
    x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False,
):
    """x [B,H,S,P]; dt [B,H,S] fp32; A [H] fp32 (negative); Bm/Cm [B,H,S,N].

    Returns (y [B,H,S,P], h_final [B,H,N,P] fp32)."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    dt3 = dt[:, :, None, :]  # [B,H,1,S] so the block is [1, chunk] lane-major
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1,), lambda b, h, ic: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt3, A, Bm, Cm)
    return y, h
