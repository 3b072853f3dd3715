"""Blocked causal/sliding flash attention — Pallas TPU kernel.

TPU-native design (not a CUDA port): the (block_q x block_k) score tile and
the (block_q x head_dim) accumulator live in VMEM scratch; the kv axis is the
innermost grid dimension, so TPU's sequential minor-to-major grid walk plays
the role of the CUDA softmax loop.  Block shapes are multiples of 128 to keep
the MXU fed.  Online-softmax state (m, l) is carried in VMEM scratch across
kv steps; fully-masked kv blocks are skipped with `pl.when` (matching the
block ranges the pure-JAX `attend_chunked` visits — same FLOPs).

Layouts: q [B, Hq, Sq, hd]; k/v [B, Hkv, Skv, hd]; out like q.  GQA is
handled by the kv index_map (kv head = q head // group) — no materialized
head broadcast.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * block_q
    k_lo = ik * block_k
    # static-shape positions; block-level skip decided with pl.when
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    needed = True
    if causal:
        needed = k_lo <= q_lo + block_q - 1  # block intersects the triangle
    if window:
        needed = jnp.logical_and(needed, k_lo + block_k - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]          # [bq, 128] broadcast lanes
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)              # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 128]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True
        ) * jnp.ones_like(l_scr)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """q [B, Hq, Sq, hd]; k, v [B, Hkv, Skv, hd] -> [B, Hq, Sq, hd]."""
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    g = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (lane-broadcast)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q, k, v)
