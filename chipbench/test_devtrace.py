"""The trace reduction, on the trace of ``q12_tumble.saturate`` recorded on
a TPU v5 lite chip; and every per-layer metric read on the recorded trace
of each cell that reports it."""

import pytest

from chipbench import devtrace, harness, recording, roofline

BENCH = harness.load_benchmark()


@pytest.fixture(scope="module")
def recorded():
    """The recorded window of a cell, loaded once; a cell with none fails
    with the command line that records it."""
    windows = {}

    def get(cell):
        if cell not in windows:
            windows[cell] = recording.load(cell)
        if windows[cell] is None:
            pytest.fail(f"no recorded trace of {cell}: record one on a TPU with "
                        f"{recording.command(cell)}")
        return windows[cell]
    return get


@pytest.fixture(scope="module")
def win(recorded):
    return recorded("q12_tumble.saturate")


def test_busy_and_idle_cover_the_window(win):
    busy = devtrace.busy_s(win.trace, win.t0, win.t1)
    gaps = devtrace.idle_gaps(win.trace, win.t0, win.t1)
    assert 0 < busy < win.seconds
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(win.seconds, rel=1e-6)
    labels = devtrace.label_gaps(gaps, win.spans)
    assert sum(labels.values()) == pytest.approx(win.seconds - busy, rel=1e-6)
    # the deepest span holding a gap names it: since the table update has
    # spans below it, its idle time falls under them
    table = {"table_update", "lookup", "lookup.pack", "lookup.ship", "lookup.dispatch",
             "lookup.wait", "claim"}
    assert table & set(labels)


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


def _metric_cells():
    """Each (per-layer metric, cell that reports it) pair."""
    return [(m["name"], w["name"]) for w in BENCH["workloads"]
            for m in harness.cell_metrics(BENCH, w["name"], "per_layer")]


@pytest.mark.parametrize("name,cell", _metric_cells())
def test_every_reader_reads_the_recorded_trace(recorded, name, cell):
    v = harness.load_metric(name)(recorded(cell))
    assert v is not None and v >= 0
    if name.startswith(("lookup_roofline", "device_idle_pct")):
        assert 0 < v < 100
