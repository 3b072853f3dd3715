"""Jit'd dispatch layer: Pallas kernel on TPU, pure-jnp reference elsewhere.

`use_kernels("auto" | "kernel" | "ref" | "interpret")` flips the
implementation globally; "auto" selects the compiled kernels when the default
backend is TPU and the reference otherwise, and "interpret" runs the kernels
in Pallas interpret mode (CPU tests).  The model code calls these wrappers,
so swapping implementations never touches model definitions.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import decode_attention as _dk
from repro.kernels import flash_attention as _fk
from repro.kernels import hash_table as _ht
from repro.kernels import moe_dispatch as _mk
from repro.kernels import ref as _ref
from repro.kernels import segment_reduce as _sr
from repro.kernels import ssd_scan as _sk

_MODE = "auto"  # "auto" | "kernel" | "ref" | "interpret"


def use_kernels(mode: str) -> None:
    global _MODE
    assert mode in ("auto", "kernel", "ref", "interpret")
    _MODE = mode


def _kernel_enabled() -> Optional[bool]:
    """True => compiled kernel; False => jnp ref; None->interpret kernel."""
    if _MODE == "kernel":
        return True
    if _MODE == "ref":
        return False
    if _MODE == "interpret":
        return None
    return True if jax.default_backend() == "tpu" else False


def compiled_kernels() -> bool:
    """True when the Pallas kernels run compiled: neither the jnp reference
    nor interpret mode — what a run on the chip must see."""
    return _kernel_enabled() is True


def kernels_active() -> bool:
    """True when the Pallas kernels (compiled or interpret) are selected —
    callers with a host-side fallback (e.g. the keyed cell reduction) use
    this to pick their realization per backend."""
    return _kernel_enabled() is not False


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0):
    mode = _kernel_enabled()
    if mode is False:
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap
        )
    return _fk.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=mode is None,
    )


@functools.partial(jax.jit, static_argnames=("softcap", "window"))
def decode_attention(q, cache_k, cache_v, valid_len, *, softcap=0.0, window=0):
    mode = _kernel_enabled()
    if mode is False:
        return _ref.decode_attention_ref(
            q, cache_k, cache_v, valid_len, softcap=softcap, window=window
        )
    return _dk.decode_attention(
        q, cache_k, cache_v, valid_len, softcap=softcap, window=window,
        interpret=mode is None,
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128):
    mode = _kernel_enabled()
    if mode is False:
        return _ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    return _sk.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=mode is None)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def segment_sum(values, seg_ids, num_segments: int):
    """Per-segment sums, order-blind in every mode (like the other ops
    wrappers: identical semantics whichever implementation dispatches)."""
    mode = _kernel_enabled()
    if mode is False:
        return _ref.segment_sum_ref(values, seg_ids, num_segments)
    return _sr.segment_sum(
        values, seg_ids, num_segments, interpret=mode is None
    )


def _split_i64(a) -> tuple:
    """int64 host array -> (lo, hi) int32 bit halves via uint64 wraparound
    (negative values split/compare exactly; jnp under x64-off would narrow)."""
    u = np.asarray(a, np.int64).astype(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    hi = (u >> np.uint64(32)).astype(np.uint32).view(np.int32)
    return lo, hi


def table_lookup(cell_keys, cell_starts, table_keys, table_starts, table_occ):
    """Row index of each ``(key, start)`` cell in a device window table
    (``capacity`` = miss) — the match half of the table's insert/accumulate.
    Keys and starts are int64 on the host; the kernel and its reference
    compare int32 lo/hi halves."""
    cells = _split_i64(cell_keys) + _split_i64(cell_starts)
    table = _split_i64(table_keys) + _split_i64(table_starts)
    occ = np.asarray(table_occ, np.int32)
    mode = _kernel_enabled()
    if mode is False:
        return _ref.table_lookup_ref(cells, table, occ)
    return _ht.table_lookup(
        tuple(jnp.asarray(c) for c in cells),
        tuple(jnp.asarray(t) for t in table),
        jnp.asarray(occ),
        interpret=mode is None,
    )


def batched_table_lookup(
    cell_owners, cell_keys, cell_starts,
    row_owners, table_keys, table_starts, table_occ,
):
    """Global row of each ``(owner, key, start)`` cell in an all-shard
    batched window table (shard-major stacked planes; ``n_w * capacity`` =
    miss) — ONE dispatch for every shard's cells, the fused plane's
    replacement for ``n_w`` per-shard :func:`table_lookup` calls.  Owner ids
    are small ints and ship as a single int32 plane; keys/starts split into
    lo/hi int32 halves exactly like :func:`table_lookup`."""
    cells = (np.asarray(cell_owners, np.int32),) \
        + _split_i64(cell_keys) + _split_i64(cell_starts)
    table = (np.asarray(row_owners, np.int32),) \
        + _split_i64(table_keys) + _split_i64(table_starts)
    occ = np.asarray(table_occ, np.int32)
    mode = _kernel_enabled()
    if mode is False:
        return _ref.batched_table_lookup_ref(cells, table, occ)
    return _ht.batched_table_lookup(
        tuple(jnp.asarray(c) for c in cells),
        tuple(jnp.asarray(t) for t in table),
        jnp.asarray(occ),
        interpret=mode is None,
    )


@jax.jit
def moe_gather(x, row_token):
    mode = _kernel_enabled()
    if mode is False:
        return _ref.moe_gather_ref(x, row_token)
    return _mk.moe_gather(x, row_token, interpret=mode is None)


def moe_combine(expert_out, row_token, row_weight, num_tokens: int):
    return _ref.moe_combine_ref(expert_out, row_token, row_weight, num_tokens)
