"""The readers of the spans below the fused stages, on made-up spans; and
the program's spans on the profiler's clock: each span enters a
``TraceAnnotation``, which lands on the profile's host plane where the
anchor puts the span itself."""

import shutil
import time

import jax
import numpy as np
import pytest

from chipbench import devtrace, harness
from repro.keyed import KeyedWindowAdapter, WindowSpec, synthetic_keyed_items
from repro.kernels import ops
from repro.obs import Tracer
from repro.obs.trace import SpanRecord
from repro.runtime import StreamExecutor

READERS = ("lookup_ship_ms.sat", "lookup_dispatch_ms.sat", "lookup_wait_ms.sat",
           "claim_ms.sat", "fire_ms.sat", "compile_ms.sat", "h2d_mb.sat")

#: one chunk's spans as (name, t0, t1, depth, args), in seconds from its start
CHUNK = [
    ("chunk", 0.0, 10.0, 0, None),
    ("reduce_by_cell", 0.0, 1.0, 1, None),
    ("segment.ship", 0.1, 0.2, 2, {"bytes": 200_000}),
    ("jax.trace", 0.3, 0.5, 2, {"fun": "_device_segment_path"}),
    ("segment.wait", 0.6, 0.9, 2, {"bytes": 8_000}),
    ("table_update", 1.0, 8.0, 1, None),
    ("lookup", 1.0, 6.0, 2, {"cells": 1000}),
    ("lookup.pack", 1.0, 1.5, 3, None),
    ("lookup.ship", 1.5, 2.0, 3, {"bytes": 6_300_000}),
    ("lookup.dispatch", 2.0, 5.0, 3, None),
    ("jax.trace", 2.1, 2.6, 4, {"fun": "wrapped"}),
    ("jax.trace", 2.2, 2.4, 5, {"fun": "inner"}),
    ("jax.lower", 2.6, 3.0, 4, {"fun": "jit(wrapped)"}),
    ("jax.compile", 3.0, 3.5, 4, {"fun": "jit(wrapped)", "cached": True}),
    ("lookup.wait", 5.0, 5.75, 3, {"bytes": 4000}),
    ("claim", 6.0, 7.25, 2, {"cells": 30}),
    ("close", 8.0, 10.0, 1, None),
    ("take_due", 8.0, 8.5, 2, {"rows": 0}),
    ("fire", 8.5, 9.0, 2, {"rows": 0}),
]
#: what each reader reads per chunk, from the spans above
WANT = {
    "lookup_ship_ms.sat": 1000.0,      # pack 0.5 s + ship 0.5 s
    "lookup_dispatch_ms.sat": 1600.0,  # 3 s less trace 0.5, lower 0.4, compile 0.5
    "lookup_wait_ms.sat": 750.0,
    "claim_ms.sat": 1250.0,
    "fire_ms.sat": 500.0,
    "compile_ms.sat": 1600.0,          # 0.2 + 1.4 s, the nested trace once
    "h2d_mb.sat": 6.5,
}


def _window(spans, t0=0.0, t1=20.0, chunks=2):
    return harness.Window(t0=t0, t1=t1, chunks=chunks, cells=np.array([1000] * chunks),
                          compiles=0, spans=spans, trace=None, peaks={})


def _chunks(n):
    return [SpanRecord(name, 10.0 * k + a, 10.0 * k + b, 0, depth, args)
            for k in range(n) for name, a, b, depth, args in CHUNK]


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_its_spans_per_chunk(name):
    assert harness.load_metric(name)(_window(_chunks(2))) == pytest.approx(WANT[name])


#: what a reader reads on a traced window without its spans, where that is
#: not nothing: no program built reads 0
WITHOUT = {"compile_ms.sat": 0.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_spans(name):
    assert harness.load_metric(name)(_window([])) is None
    stages_only = [s for s in _chunks(2) if s.depth <= 1]
    assert harness.load_metric(name)(_window(stages_only)) == WITHOUT.get(name)


def test_compile_ms_reads_zero_where_nothing_is_built_in_the_window():
    """The jax.* spans of a chunk before the window, and none inside it, read
    0; with no chunk in the window they read nothing."""
    built_before = [s for s in _chunks(3) if s.name.startswith("jax.") == (s.t0 < 10.0)]
    assert harness.load_metric("compile_ms.sat")(
        _window(built_before, t0=10.0, t1=30.0)) == 0.0
    assert harness.load_metric("compile_ms.sat")(
        _window(built_before, t0=10.0, t1=30.0, chunks=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_leaves_out_spans_outside_the_window(name):
    # the first of three chunks falls before the window
    assert harness.load_metric(name)(_window(_chunks(3), t0=10.0, t1=30.0)) \
        == pytest.approx(WANT[name])


def test_program_spans_sit_on_the_profiler_clock(tmp_path):
    """Each ``table_update`` span starts within 50 us of its annotation on
    the host plane, once the anchor has placed the profile's clock."""
    from jax.profiler import ProfileData

    spec = WindowSpec(kind="tumbling", size=8, lateness=2)
    ad = KeyedWindowAdapter(spec, num_slots=64, backend="device_table", capacity=64)
    tr = Tracer(recorder=None)
    ex = StreamExecutor(ad, degree=2, chunk_size=128, tracer=tr)
    items = synthetic_keyed_items(128 * 4, num_keys=128, seed=5)
    log_dir = str(tmp_path / "trace")
    ops.use_kernels("interpret")
    try:
        ex.process(items[:128])  # attaches the plane before the profile
        tr.reset()
        jax.profiler.start_trace(log_dir)
        try:
            anchor = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
                pass
            for i in range(1, 4):
                ex.process(items[i * 128:(i + 1) * 128])
        finally:
            jax.profiler.stop_trace()
    finally:
        ops.use_kernels("auto")
    path = devtrace.find_xplane(log_dir)
    trace = devtrace.load(path, anchor)
    starts = sorted(
        e.start_ns for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for e in line.events if e.name == "table_update"
    )
    spans = sorted(s.t0 for s in tr.spans if s.name == "table_update")
    shutil.rmtree(log_dir)
    assert len(spans) == 3 and len(starts) == len(spans)
    for t0, ns in zip(spans, starts):
        assert abs(trace.to_perf(ns) - t0) < 50e-6
