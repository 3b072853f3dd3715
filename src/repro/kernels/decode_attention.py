"""Single-token KV-cache attention (flash-decode) — Pallas TPU kernel.

Decode is memory-bound: the kernel's job is to stream the KV cache through
VMEM exactly once at full HBM bandwidth.  Grid = (B, Hq, kv_blocks) with the
kv axis innermost (sequential), online-softmax state in VMEM scratch; the
validity mask comes from a precomputed [Skv] bias vector (0 / -inf), so no
scalar plumbing is needed.  The query row is tiny ([1, hd]) and stays
resident; `q` is blocked per (batch, head).
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
    q_ref, k_ref, v_ref, bias_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    softcap: float,
    num_kv_blocks: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # [1, hd]
    k = k_ref[0, 0].astype(jnp.float32)                # [bk, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [1, bk]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias_ref[...].astype(jnp.float32)          # [1, bk] validity bias

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True) * jnp.ones_like(
        l_scr
    )
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-37)).astype(
            o_ref.dtype
        )


def decode_attention(
    q, cache_k, cache_v, valid_len,
    *,
    softcap: float = 0.0,
    window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
):
    """q [B, Hq, hd]; cache_k/v [B, Hkv, S, hd]; valid_len scalar int32.

    Returns [B, Hq, hd]."""
    B, Hq, hd = q.shape
    Hkv, S = cache_k.shape[1], cache_k.shape[2]
    g = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    scale = 1.0 / math.sqrt(hd)

    pos = jnp.arange(S)
    valid = pos < valid_len
    if window:
        valid &= pos > valid_len - window
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[None, :]  # [1, S]

    kernel = functools.partial(
        _kernel, scale=scale, softcap=softcap, num_kv_blocks=nk
    )
    q4 = q[:, :, None, :]  # [B, Hq, 1, hd]
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, block_k), lambda b, h, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q4, cache_k, cache_v, bias)
    return out[:, :, 0, :]
