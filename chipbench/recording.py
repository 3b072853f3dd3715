"""A cell's recorded trace: the tiny size a cell is run at for its tests
and its recording, and the fixture pair that a recording writes.

A recording of cell ``<cell>`` is two files under ``chipbench/fixtures/``:
``<cell>.xplane.pb``, the profiler's trace of a traced window, and
``<cell>.json``, the window as the per-layer readers see it (the anchor,
the window in ``perf_counter`` seconds, its chunks and their live cells,
the programs built inside it, and the program's tracer spans that overlap
it, with their args) and, under ``about``, how it was recorded.
``chipbench/record.py`` makes one on a TPU; the tests read every per-layer
metric on the recording of each cell that reports it.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Optional

import numpy as np

from chipbench import devtrace, harness
from repro.obs.trace import SpanRecord

FIXTURES_DIR = os.path.join(harness.BENCH_DIR, "fixtures")

#: 2 shards of 256 rows, chunks of 256 bids over 200 ms of event time,
#: windows of 2 s (sliding by 400 ms), so that windows fire, bids come late
#: and rows spill to the host tier within a dozen chunks
TINY = {"plane": {"shards": 2, "num_slots": 8, "chunk": 256}, "capacity": 256,
        "tps": 1391, "window": {"size_ms": 2000, "slide_ms": 400}}
#: the tiny cell with Q5's shape: hopping windows, keyed by auction
SLIDING = {"key": "auction", "window": {**TINY["window"], "kind": "sliding"}}
#: the tiny cell with Q11's shape: session windows keyed by bidder, with a
#: gap short enough against the jitter that some bids come late
SESSION = {"window": {"kind": "session", "gap_ms": 40}}


def tiny(kind: str) -> dict:
    """The overrides that cut a configuration with windows of ``kind`` to
    the tiny size; its window kind, key and stream stay its own."""
    return {**TINY, **SESSION} if kind == "session" else TINY


def paths(cell: str, fixtures_dir: str = FIXTURES_DIR):
    """The ``(json, xplane)`` paths of the recording of ``cell``."""
    stem = os.path.join(fixtures_dir, cell)
    return stem + ".json", stem + ".xplane.pb"


def command(cell: str) -> str:
    """The command line that records ``cell`` on a TPU."""
    return f"python3 chipbench/record.py --workload {cell} --seed <n>"


def save(cell: str, win: harness.Window, anchor: float, xplane: str, about: dict,
         fixtures_dir: str = FIXTURES_DIR) -> None:
    """Write the recording of ``cell``: the window ``win``, whose profile
    is the file ``xplane`` and was placed by the anchor read at ``anchor``."""
    json_path, xplane_path = paths(cell, fixtures_dir)
    spans = [[s.name, s.t0, s.t1, s.tid, s.depth, s.args] for s in win.spans
             if s.t1 >= win.t0 and s.t0 <= win.t1]
    data = {"about": about, "anchor": anchor, "t0": win.t0, "t1": win.t1,
            "chunks": win.chunks, "cells": np.asarray(win.cells).tolist(),
            "compiles": win.compiles, "spans": spans}
    with open(json_path, "w") as f:
        json.dump(data, f)
    shutil.copyfile(xplane, xplane_path)


def load(cell: str, fixtures_dir: str = FIXTURES_DIR) -> Optional[harness.Window]:
    """The window of the recording of ``cell``, with its device trace and
    the peaks of the device it was recorded on; None where there is none."""
    json_path, xplane_path = paths(cell, fixtures_dir)
    if not (os.path.exists(json_path) and os.path.exists(xplane_path)):
        return None
    with open(json_path) as f:
        d = json.load(f)
    return harness.Window(
        t0=d["t0"], t1=d["t1"], chunks=d["chunks"], cells=np.array(d["cells"]),
        compiles=d["compiles"], spans=[SpanRecord(*s) for s in d["spans"]],
        trace=devtrace.load(xplane_path, d["anchor"]),
        peaks=harness.load_peaks(d["about"]["device"]),
    )
