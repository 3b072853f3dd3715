"""The table lookup's dispatch: the host pads the cell planes to the
kernel's cell block, and one jitted program serves every count of cells that
pads to the same shape."""

import jax
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.obs import Tracer

SHARDS, CAPACITY = 3, 512


@pytest.fixture
def interpret():
    ops.use_kernels("interpret")
    try:
        yield
    finally:
        ops.use_kernels("auto")


def _table(seed):
    """Stacked shard planes with distinct keys, about half the rows
    occupied, and row 0 (owner 0) holding key 0 and start 0: the cell the
    zero padding spells."""
    rng = np.random.default_rng(seed)
    total = SHARDS * CAPACITY
    owner = np.arange(total) // CAPACITY
    key = rng.permutation(np.arange(1, total + 1)) * rng.choice([-1, 1], total) * 2**33
    start = rng.integers(-4, 4, total) * 10
    occ = rng.random(total) < 0.5
    key[0], start[0], occ[0] = 0, 0, True
    return owner, key, start, occ


def _cells(table, n, seed):
    """``n`` cells: an absent one first, then the all-zero cell, then
    occupied rows' cells and absent cells in turn."""
    owner, key, start, occ = table
    rng = np.random.default_rng(seed)
    rows = rng.choice(np.flatnonzero(occ), n)
    rows[1:2] = 0
    own, k, s = owner[rows], key[rows], start[rows]
    absent = np.arange(n) % 2 == 0
    absent[1:2] = False
    k = np.where(absent, rng.integers(-2**40, 2**40, n) | 1, k)
    return own, k, s


def test_one_program_per_padded_cell_count(interpret):
    jax.clear_caches()
    table = _table(0)
    tr = Tracer(recorder=None)
    counts = (100, 1, 127, 129, 256, 200)
    for i, n in enumerate(counts):
        with tr.span("call"):
            ops.batched_table_lookup(*_cells(table, n, i), *table, tracer=tr)
    calls = [s for s in tr.spans if s.name == "call"]
    dispatches = [s for s in tr.spans if s.name == "lookup.dispatch"]
    builds = [s for s in tr.spans
              if (s.name, s.args.get("fun") if s.args else None) in (
                  ("jax.trace", "batched_table_lookup"),
                  ("jax.lower", "jit(batched_table_lookup)"))]

    def inside(s, outer):
        return outer.t0 <= s.t0 and s.t1 <= outer.t1 and s.depth > outer.depth

    assert all(any(inside(b, d) for d in dispatches) for b in builds)
    per_call = [sorted(b.name for b in builds if inside(b, c)) for c in calls]
    # 128 cells, then 256: the first count of each padded shape builds
    new = ["jax.lower", "jax.trace"]
    assert per_call == [new, [], [], new, [], []]


@pytest.mark.parametrize("n", [1, 127, 128, 129, 1187])
def test_padded_cells_never_leak_into_rows(interpret, n):
    table = _table(1)
    cells = _cells(table, n, n)
    got = ops.batched_table_lookup(*cells, *table)
    want = np.asarray(kref.batched_table_lookup_ref(
        ops._planes(*cells), ops._planes(*table[:3]), table[3].astype(np.int32)))
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, want)
    # even places hold absent cells, odd places occupied rows' cells, the
    # first of them row 0's all-zero cell
    total = SHARDS * CAPACITY
    assert (got[::2] == total).all() and (got[1::2] < total).all()
    assert n == 1 or got[1] == 0
