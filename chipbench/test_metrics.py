"""Per-layer metrics are files found by name; the peaks table refuses an
unknown device; the lookup's least bytes; and BENCHMARK.json keeps to the
shape the harness reads."""

import json
import os
import re

import numpy as np
import pytest

from chipbench import harness, roofline

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(harness.load_metric(name))


def test_a_new_metric_is_a_new_file_found_by_name(tmp_path):
    (tmp_path / "chunks_seen.py").write_text(
        "def read(win):\n    return float(win.chunks)\n")
    (tmp_path / "only_sat.sat.py").write_text("def read(win):\n    return 2.0\n")
    win = harness.Window(t0=0.0, t1=1.0, chunks=3, cells=np.array([1, 2, 3]),
                         compiles=0, spans=[], trace=None, peaks={})
    assert harness.load_metric("chunks_seen", str(tmp_path))(win) == 3.0
    assert harness.load_metric("chunks_seen.rate", str(tmp_path))(win) == 3.0
    assert harness.load_metric("only_sat.sat", str(tmp_path))(win) == 2.0
    with pytest.raises(FileNotFoundError):
        harness.load_metric("missing", str(tmp_path))


def test_trace_metrics_read_nothing_without_a_trace():
    win = harness.Window(t0=0.0, t1=1.0, chunks=3, cells=np.array([1, 2, 3]),
                         compiles=0, spans=[], trace=None, peaks={})
    for name in ("lookup_kernel_ms.sat", "lookup_roofline.sat", "device_idle_pct.sat",
                 "pane_stages_ms.sat", "close_ms.sat", "table_update_ms.sat"):
        assert harness.load_metric(name)(win) is None
    assert harness.load_metric("compiles_in_window.sat")(win) == 0.0


def test_lookup_least_bytes_per_cell():
    # 20 B of cell planes in, 4 B of row out, one 24 B table row read
    assert roofline.lookup_least_bytes([1]) == 48
    assert roofline.lookup_least_bytes(np.array([41007, 40887])) == 48 * 81894


def test_peaks_are_keyed_by_device_kind():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


def test_cells_report_their_metrics():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(BENCH, w["name"], "end_to_end")}
        layer = harness.cell_metrics(BENCH, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    root = harness.ROOT
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(root, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert all(k in data for k in c["reduced"])
        names.add(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
    layers = {}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        if "layer" in m:
            layers.setdefault(m["layer"], set()).add(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
