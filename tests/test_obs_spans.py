"""The spans below the fused plane's stages: the table lookup's pack, ship,
dispatch and wait, the claim loop, the close's ``take_due`` and ``fire``,
the segment path's transfers, and the ``jax.*`` and ``gc`` spans an enabled
wall-clock tracer records for the programs JAX builds and the collector's
passes."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.keyed import KeyedWindowAdapter, WindowSpec, synthetic_keyed_items
from repro.keyed import kernels as kk
from repro.keyed.table import BatchedWindowTable, DeviceWindowTable
from repro.kernels import ops
from repro.kernels.hash_table import BLOCK_CELLS
from repro.obs import LogicalClock, Tracer, WallClock
from repro.runtime import StreamExecutor

#: each new span and the stage span it nests in
PARENT = {
    "lookup": "table_update", "claim": "table_update",
    "lookup.pack": "lookup", "lookup.ship": "lookup",
    "lookup.dispatch": "lookup", "lookup.wait": "lookup",
    "segment.ship": "reduce_by_cell", "segment.wait": "reduce_by_cell",
    "take_due": "close", "fire": "close",
}
JAX_SPANS = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture
def interpret():
    ops.use_kernels("interpret")
    try:
        yield
    finally:
        ops.use_kernels("auto")


def _run(tracer, n_chunks=5, chunk=128):
    spec = WindowSpec(kind="tumbling", size=8, lateness=2)
    ad = KeyedWindowAdapter(spec, num_slots=64, backend="device_table",
                            capacity=64, ttl=16)
    ex = StreamExecutor(ad, degree=4, chunk_size=chunk, tracer=tracer)
    items = synthetic_keyed_items(chunk * n_chunks, num_keys=256, seed=7)
    return ex.run([items[i * chunk:(i + 1) * chunk] for i in range(n_chunks)])


def _parent(s, spans):
    """The span one level up on the same thread that holds ``s``."""
    for p in spans:
        if (p.tid == s.tid and p.depth == s.depth - 1
                and p.t0 <= s.t0 and s.t1 <= p.t1):
            return p
    return None


@pytest.fixture(scope="module")
def traced_run():
    # the lookup's program is built once per padded shape in a process: with
    # the caches cleared the first chunk builds it inside ``lookup.dispatch``
    jax.clear_caches()
    ops.use_kernels("interpret")
    try:
        tr = Tracer(recorder=None)
        outs = _run(tr)
    finally:
        ops.use_kernels("auto")
    return tr.spans, outs


def test_every_new_span_nests_under_its_stage(traced_run):
    spans, _ = traced_run
    for name, parent in PARENT.items():
        found = [s for s in spans if s.name == name]
        assert found, f"no {name} span"
        for s in found:
            p = _parent(s, spans)
            assert p is not None and p.name == parent, (name, p)
    for s in spans:
        if s.name in JAX_SPANS or s.name == "gc":
            assert _parent(s, spans) is not None, s


def test_span_args_count_cells_and_rows(traced_run):
    spans, outs = traced_run
    for s in spans:
        if s.name == "lookup":
            assert s.args["cells"] > 0
        elif s.name == "claim":
            p = _parent(s, spans)
            lookup = [c for c in spans if c.name == "lookup" and _parent(c, spans) is p]
            assert 0 < s.args["cells"] <= lookup[0].args["cells"]
        elif s.name == "fire":
            due = [c for c in spans if c.name == "take_due"
                   and _parent(c, spans) is _parent(s, spans)]
            assert s.args["rows"] >= due[0].args["rows"]
    fired = sum(s.args["rows"] for s in spans if s.name == "fire")
    emitted = sum(len(o["emissions"]["key"]) for o in outs)
    assert 0 < emitted <= fired


def test_new_programs_are_built_inside_dispatch_or_reduce(traced_run):
    spans, _ = traced_run
    built = [s for s in spans if s.name == "jax.compile"]
    homes = [s for s in spans if s.name in ("lookup.dispatch", "reduce_by_cell")]

    def home(s):
        return next((h.name for h in homes if h.tid == s.tid and h.depth < s.depth
                     and h.t0 <= s.t0 and s.t1 <= h.t1), None)

    assert built and all(home(s) is not None for s in built)
    assert any(home(s) == "lookup.dispatch" for s in built)
    assert all(isinstance(s.args["cached"], bool) and s.args["fun"] for s in built)


def test_ship_and_wait_bytes_are_the_arrays_moved(interpret):
    tables = [DeviceWindowTable(32, max_probes=4) for _ in range(3)]
    bt = BatchedWindowTable(tables)
    n = 7
    tr = Tracer(recorder=None)
    rng = np.random.default_rng(0)
    keys = rng.integers(-2**40, 2**40, n)
    with tr.span("table_update"):
        bt.update(rng.integers(0, 3, n), keys, keys * 0, keys * 0 + 4,
                  np.ones(n, np.int64), np.ones(n, np.int64), touch_ts=1, tracer=tr)
    by = {s.name: s for s in tr.spans}
    # five int32 cell planes (owner, key lo/hi, start lo/hi), padded to the
    # kernel's cell block; five int32 table planes and the occupancy plane,
    # one int32 row back per padded cell
    pad = BLOCK_CELLS
    assert by["lookup.ship"].args["bytes"] == 20 * pad + 24 * bt.total_rows
    assert by["lookup.wait"].args["bytes"] == 4 * pad
    ids = np.array([0, 2, 1, 2, 0], np.int32)
    vals = np.ones((5, 2), np.int64)
    with tr.span("reduce_by_cell"):
        out = kk.reduce_by_cell(ids, vals, 3, tracer=tr)
    np.testing.assert_array_equal(out, [[2, 2], [1, 1], [2, 2]])
    by = {s.name: s for s in tr.spans}
    assert by["segment.ship"].args["bytes"] == ids.nbytes + 5 * 2 * 4
    assert by["segment.wait"].args["bytes"] == 3 * 2 * 4


@pytest.mark.parametrize("clock", [WallClock, LogicalClock])
def test_only_wall_clock_tracers_record_builds_and_collections(clock):
    tr = Tracer(clock=clock(), recorder=None)
    with tr.span("outer"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5))  # a new function: a new program
        gc.collect()
    names = [s.name for s in tr.spans]
    if clock is LogicalClock:
        assert names == ["outer"]
        return
    outer = next(s for s in tr.spans if s.name == "outer")
    assert set(JAX_SPANS) | {"gc"} <= set(names)
    for s in tr.spans:
        if s is not outer:
            assert s.depth >= 1 and outer.t0 <= s.t0 <= s.t1 <= outer.t1
    assert any(s.args == {"generation": 2} for s in tr.spans if s.name == "gc")


def test_nothing_is_recorded_without_an_open_span():
    tr = Tracer(recorder=None)
    jax.jit(lambda x: x - 7)(jnp.arange(3))
    gc.collect()
    with tr.span("after"):
        pass
    assert [s.name for s in tr.spans] == ["after"]


def test_a_trace_nested_in_a_trace_is_one_level_down():
    tr = Tracer(recorder=None)
    inner = jax.jit(lambda x: x + 2)

    @jax.jit
    def outer(x):
        return inner(x) * 5

    with tr.span("outer"):
        outer(jnp.arange(4))
    traces = {s.args["fun"]: s for s in tr.spans if s.name == "jax.trace"}
    a, b = traces["outer"], traces["<lambda>"]
    assert (a.depth, b.depth) == (1, 2)
    assert a.t0 <= b.t0 and b.t1 <= a.t1


def test_tracers_are_held_weakly():
    tr = Tracer(recorder=None)
    with tr.span("x"):
        pass
    ref = weakref.ref(tr)
    del tr
    gc.collect()
    assert ref() is None


def test_traced_run_with_kernels_is_bit_identical(interpret, traced_run):
    _, traced = traced_run
    plain = _run(None)
    for a, b in zip(traced, plain):
        for ch in ("emissions", "late", "early"):
            for k in a[ch]:
                np.testing.assert_array_equal(a[ch][k], b[ch][k])
