"""The trace reduction, on a small trace recorded on a TPU v5 lite chip."""

import json
import os

import numpy as np
import pytest

from chipbench import devtrace, harness, roofline
from repro.obs.trace import SpanRecord

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture(scope="module")
def win():
    with open(os.path.join(FIX, "tiny_q12.json")) as f:
        d = json.load(f)
    trace = devtrace.load(os.path.join(FIX, "tiny_q12.xplane.pb"), d["anchor"])
    spans = [SpanRecord(n, t0, t1, tid, depth) for n, t0, t1, tid, depth in d["spans"]]
    return harness.Window(t0=d["t0"], t1=d["t1"], chunks=d["chunks"],
                          cells=np.array(d["cells"]), compiles=0, spans=spans,
                          trace=trace, peaks=harness.load_peaks("TPU v5 lite"))


def test_busy_and_idle_cover_the_window(win):
    busy = devtrace.busy_s(win.trace, win.t0, win.t1)
    gaps = devtrace.idle_gaps(win.trace, win.t0, win.t1)
    assert 0 < busy < win.seconds
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(win.seconds, rel=1e-6)
    labels = devtrace.label_gaps(gaps, win.spans)
    assert sum(labels.values()) == pytest.approx(win.seconds - busy, rel=1e-6)
    assert "table_update" in labels


def test_ops_are_named_and_summed(win):
    ops = devtrace.op_seconds(win.trace, win.t0, win.t1)
    assert sum(ops.values()) >= devtrace.busy_s(win.trace, win.t0, win.t1) * 0.999
    assert any(name.endswith("custom-call") for name in ops)
    assert all(len(name) <= 200 for name in ops)


def test_one_lookup_kernel_per_chunk(win):
    spans = [(win.trace.to_ns(s.t0), win.trace.to_ns(s.t1)) for s in win.spans
             if s.name == "table_update" and win.t0 <= s.t0 and s.t1 <= win.t1]
    assert len(spans) == win.chunks
    calls = [
        e for evs in win.trace.ops for e in evs
        if roofline.PALLAS_TARGET in e[0]
        and any(a <= (e[1] + e[2]) / 2 <= b for a, b in spans)
    ]
    assert len(calls) == win.chunks
    s = roofline.lookup_seconds(win)
    assert s == pytest.approx(sum(e[2] - e[1] for e in calls) / 1e9)


@pytest.mark.parametrize("name", [m["name"] for m in harness.load_benchmark()["per_layer"]])
def test_every_reader_reads_the_recorded_trace(win, name):
    v = harness.load_metric(name)(win)
    assert v is not None and v >= 0
    if name.startswith(("lookup_roofline", "device_idle_pct")):
        assert 0 < v < 100
