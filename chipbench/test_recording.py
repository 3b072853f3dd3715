"""The recorder's fixture pair: a traced run of a tiny cell, with the Pallas
kernels in interpret mode, written and read back through the loader the
reader tests use; the tiny size chosen by window kind; and the recorder's
refusal of a machine without a TPU."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, recording
from repro.kernels import ops


def test_a_recorded_window_reads_back_as_it_was(tmp_path, monkeypatch):
    peaks = harness.load_peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "load_peaks", lambda kind: peaks)
    kept = []

    def keep(win, anchor, xplane):
        recording.save("tiny.cell", win, anchor, xplane, {"device": "cpu"}, str(tmp_path))
        kept.append((win, anchor))

    ops.use_kernels("interpret")
    try:
        harness.run_cell("q12_tumble.saturate", 2**31 + 23, 0.2, True,
                         t_start=time.perf_counter(), log=lambda m: None,
                         overrides=recording.tiny("tumbling"), on_trace=keep)
    finally:
        ops.use_kernels("auto")
    (win, anchor), = kept
    got = recording.load("tiny.cell", str(tmp_path))
    assert (got.t0, got.t1, got.chunks, got.compiles) == (win.t0, win.t1, win.chunks,
                                                           win.compiles)
    assert got.chunks > 0 and np.array_equal(got.cells, win.cells)
    assert got.trace.to_ns(anchor) == pytest.approx(win.trace.to_ns(anchor))

    def rows(spans):
        return [(s.name, s.t0, s.t1, s.tid, s.depth, s.args) for s in spans]
    inside = [s for s in win.spans if s.t1 >= win.t0 and s.t0 <= win.t1]
    assert rows(got.spans) == rows(inside)
    assert any(s.name == "lookup.ship" and s.args["bytes"] > 0 for s in got.spans)
    assert len(got.spans) < len(win.spans)  # set-up's spans stay out
    for name in ("lookup_ship_ms.sat", "h2d_mb.sat", "fire_ms.sat", "compile_ms.sat"):
        assert harness.load_metric(name)(got) == harness.load_metric(name)(win)


def test_no_recording_loads_as_nothing(tmp_path):
    assert recording.load("q12_tumble.saturate", str(tmp_path)) is None
    assert recording.command("x.y") == "python3 chipbench/record.py --workload x.y --seed <n>"


@pytest.mark.parametrize("name", ["nexmark_q12_tumble", "nexmark_q5_hop",
                                  "nexmark_q11_session"])
def test_the_tiny_size_follows_the_window_kind(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        config = json.load(f)
    kind, key = config["window"]["kind"], config["key"]
    harness.apply_overrides(config, {}, recording.tiny(kind))
    assert (config["window"]["kind"], config["key"]) == (kind, key)
    assert config["capacity"] == 256 and config["plane"]["chunk"] == 256
    spec = harness.window_spec(config["window"], late_policy="side")
    want = {"tumbling": 2000, "sliding": 2000, "session": 40}[kind]
    assert harness.horizon_ms(config["window"]) == want
    assert spec.kind == kind


def test_the_recorder_refuses_a_machine_without_a_tpu():
    root = os.path.dirname(harness.BENCH_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/record.py", "--workload", "q12_tumble.saturate",
         "--seed", "1", "--commit", "none"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr
