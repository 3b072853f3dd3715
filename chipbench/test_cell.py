"""One cell driven end to end at a tiny size, with the Pallas kernels in
interpret mode: bit-exact against the reference, a well-formed result line,
and ``correct`` false under the control and under each fault the cell can
have.  (A cell on one chip has no exchange between chips to leave out.)"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import bidstream, harness
from chipbench.recording import SESSION, SLIDING, TINY
from repro.keyed import kernels as kk
from repro.keyed.runtime import KeyedWindowAdapter
from repro.keyed.table import BatchedWindowTable
from repro.kernels import ops


@pytest.fixture
def interpret():
    ops.use_kernels("interpret")
    try:
        yield
    finally:
        ops.use_kernels("auto")


def _run(cell="q12_tumble.saturate", more=None, **kw):
    return harness.run_cell(cell, 2**31 + 17, 0.2, False, t_start=time.perf_counter(),
                            overrides={**TINY, **(more or {})}, log=lambda m: None, **kw)


@pytest.mark.parametrize("more", [None, SLIDING], ids=["tumbling", "sliding"])
def test_cell_end_to_end_is_exact_with_a_well_formed_line(interpret, more):
    cell = "q12_tumble.saturate"
    r = _run(cell, more)
    line = json.loads(json.dumps(r))
    assert list(line)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 and c["limit"] == 0 for c in line["check"].values())
    want = {m["name"] for m in harness.cell_metrics(harness.load_benchmark(), cell,
                                                    "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])


def test_control_dropping_late_records_is_not_correct(interpret):
    r = _run(late_policy="drop")
    assert r["correct"] is False and r["check"]["late_rows_off"]["value"] > 0


def _unchanged(self, *args, **kwargs):
    return None


def _half_batch(orig):
    def prepare(self, chunk):
        return orig(self, chunk[: len(chunk) // 2])
    return prepare


def _altered(orig):
    def reduce_by_cell(*args, **kwargs):
        out = np.array(orig(*args, **kwargs))
        out[0, 0] += 1
        return out
    return reduce_by_cell


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_faults_make_the_check_fail(interpret, monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(BatchedWindowTable, "update", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(KeyedWindowAdapter, "prepare_chunk",
                            _half_batch(KeyedWindowAdapter.prepare_chunk))
    else:
        monkeypatch.setattr(kk, "reduce_by_cell", _altered(kk.reduce_by_cell))
    r = _run()
    assert r["correct"] is False
    assert r["check"]["emission_rows_off"]["value"] + r["check"]["open_rows_off"]["value"] > 0


def test_the_command_refuses_a_machine_without_a_tpu():
    root = os.path.dirname(harness.BENCH_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "q12_tumble.saturate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr
    assert "TPU" in harness.chip_problem(1)


def test_session_plane_checks_exact_and_a_dropped_row_shows(interpret):
    """A session plane driven directly for a dozen chunks checks exact, late
    bids and open sessions included, and one emission, late row or open
    session left out is counted."""
    _, _, config, traffic = harness.load_cell("q12_tumble.saturate")
    harness.apply_overrides(config, traffic, {**TINY, **SESSION})
    stream = bidstream.BidStream.for_config(config, 2**31 + 17)
    ex = harness.build_plane(config, late_policy="side")
    chunks = [stream.chunk(k) for k in range(12)]
    outs = [ex.process(c) for c in chunks]
    items, snapshot = np.concatenate(chunks), ex.snapshot_barrier()
    checks = harness.check(outs, snapshot, items, config)
    assert all(c["value"] == 0 and c["limit"] == 0 for c in checks.values())
    assert sum(len(o["late"]["key"]) for o in outs) > 0
    assert len(snapshot["w_key"]) > 0
    for channel, off in (("emissions", "emission_rows_off"), ("late", "late_rows_off")):
        k = next(i for i, o in enumerate(outs) if len(o[channel]["key"]))
        cut = list(outs)
        cut[k] = {**outs[k], channel: {c: v[1:] for c, v in outs[k][channel].items()}}
        assert harness.check(cut, snapshot, items, config)[off]["value"] > 0
    short = {k: v[1:] if k.startswith("w_") else v for k, v in snapshot.items()}
    assert harness.check(outs, short, items, config)["open_rows_off"]["value"] > 0


def test_a_plane_with_no_state_on_the_device_stops_after_its_first_chunk(interpret,
                                                                         monkeypatch):
    """Whatever the window kind and wherever the program keeps its state, a
    plane that reports open rows and none on the device tier stops the run
    after chunk 0, naming the configuration, the kind and both counts."""
    sent = []
    process = harness._Source.process
    monkeypatch.setattr(harness._Source, "process",
                        lambda self, k: (sent.append(k), process(self, k)))
    monkeypatch.setattr(harness, "_rows", lambda ex: {"resident": 0, "spill": 335})
    with pytest.raises(RuntimeError, match=r"configuration nexmark_q12_tumble: .* none "
                                           r"of its open rows of session windows on the "
                                           r"device tier \(0 resident rows, 335 in the "
                                           r"host store\)"):
        _run(more=SESSION)
    assert sent == [0]
