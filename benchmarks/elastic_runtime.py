"""Elastic-runtime benchmark: throughput tracking across resize events.

Two planes, cross-validated:

* **Simulated data plane** (deterministic): each chunk's service time comes
  from the calibrated discrete-event farm (:mod:`repro.core.simulator`) at
  the current degree, while the REAL control plane (metrics bus, autoscaler,
  §4.x resize accounting) runs on a logical clock.  Per-phase measured
  throughput is checked against the analytic envelope from
  :mod:`repro.core.analytics` (``m / accumulator_completion``): the
  acceptance gate is every post-resize phase within ``ENVELOPE_TOL``.
* **Real SPMD plane** (subprocess, 8 host devices): a `StreamExecutor` over
  the S2 partitioned pattern executes a grow/shrink schedule for real,
  reporting per-phase wall throughput, resize cost, and the compile-cache
  hit when a degree is revisited.

Emits ``results/elastic_runtime.json`` plus the aggregator's CSV rows, and
— because the simulated plane runs under a logical clock — a
byte-deterministic Perfetto-loadable trace
(``results/elastic_runtime_trace.json``: chunk spans, resize instants, a
degree counter track) with its flat metrics snapshot
(``results/elastic_runtime_metrics.json``).

Run:  PYTHONPATH=src python -m benchmarks.elastic_runtime
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import Row, derived

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# workload calibration (time units, paper-style synthetic costs)
T_F = 1.0          # per-item task time
T_ACC = 0.05       # collector fold time
FLUSH_EVERY = 16
CHUNK = 512
NUM_CHUNKS = 16
SCHEDULE = {4: 4, 8: 8, 12: 2}   # chunk index -> new degree (grow, grow, shrink)
ENVELOPE_TOL = 0.10              # post-resize throughput within 10% of model


def _simulated_phases():
    """Drive the runtime control plane over the discrete-event data plane.

    The control plane runs under a shared :class:`LogicalClock`, so the
    exported Chrome trace (chunk spans, resize instants, degree counter
    track) is deterministic byte-for-byte — the trace artifact is itself a
    regression surface, not just a debugging aid.
    """
    from repro.core import analytics, simulator
    from repro.obs import MetricsRegistry, Tracer
    from repro.runtime.metrics import ChunkRecord, LogicalClock, MetricsBus, ResizeRecord
    from repro.core.patterns import PartitionedState

    clock = LogicalClock()
    bus = MetricsBus(clock=clock)
    tracer = Tracer(clock=clock)   # one clock: spans line up with the bus
    registry = MetricsRegistry()
    service_hist = registry.histogram("elastic.chunk_service_s")
    degree = 2
    phases = []          # one entry per constant-degree phase
    current = {"degree": degree, "items": 0, "t0": 0.0, "chunks": 0}

    def close_phase():
        span = clock.now() - current["t0"]
        if current["chunks"] == 0 or span <= 0:
            return
        measured = current["items"] / span
        modeled = current["items"] / (
            current["chunks"]
            * analytics.accumulator_completion(
                CHUNK, T_F, T_ACC, current["degree"], FLUSH_EVERY
            )
        )
        phases.append(
            {
                "degree": current["degree"],
                "chunks": current["chunks"],
                "throughput_measured": measured,
                "throughput_model": modeled,
                "rel_err": abs(measured - modeled) / modeled,
                "within_envelope": abs(measured - modeled) / modeled
                <= ENVELOPE_TOL,
            }
        )

    for i in range(NUM_CHUNKS):
        if i in SCHEDULE:
            close_phase()
            n_new = SCHEDULE[i]
            handoff = PartitionedState.handoff_volume(64, degree, n_new)
            bus.record_resize(
                ResizeRecord(
                    t=clock.now(),
                    n_old=degree,
                    n_new=n_new,
                    protocol="S2-block-handoff",
                    handoff_items=handoff,
                    reason=f"schedule@chunk{i}",
                )
            )
            tracer.instant("resize", n_old=degree, n_new=n_new,
                           protocol="S2-block-handoff",
                           handoff_items=handoff)
            degree = n_new
            current = {"degree": degree, "items": 0, "t0": clock.now(),
                       "chunks": 0}
        res = simulator.simulate_accumulator(
            CHUNK, degree, T_F, T_ACC, flush_every=FLUSH_EVERY
        )
        t0 = clock.now()
        tracer.counter("degree", n_w=degree)
        with tracer.span("chunk", m=CHUNK, degree=degree):
            clock.advance(res.completion_time)
        service_hist.record(res.completion_time)
        bus.record_chunk(
            ChunkRecord(
                t_start=t0,
                t_end=clock.now(),
                m=CHUNK,
                n_workers=degree,
                queue_depth=0,
                collector_updates=res.state_updates_sent,
            )
        )
        current["items"] += CHUNK
        current["chunks"] += 1
    close_phase()
    for k, p in enumerate(phases):
        registry.gauge(f"elastic.phase{k}.throughput").set(
            p["throughput_measured"]
        )
        registry.gauge(f"elastic.phase{k}.n_w").set(p["degree"])
    registry.counter("elastic.chunks").inc(NUM_CHUNKS)
    registry.counter("elastic.resizes").inc(len(bus.resizes))
    return phases, bus, tracer, registry


def _real_spmd_rows():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "benchmarks", "_elastic_runtime_child.py"),
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"elastic_runtime child exited {proc.returncode}: "
            f"{proc.stderr.strip()[-2000:]}"
        )
    rows, records = [], []
    for line in proc.stdout.strip().splitlines():
        if line.startswith("{"):
            records.append(json.loads(line))
            continue
        parts = line.split(",", 2)
        if len(parts) == 3:
            rows.append(Row(parts[0], float(parts[1]), parts[2]))
    return rows, records


def run() -> list[Row]:
    from repro.obs import write_metrics, write_trace

    phases, bus, tracer, registry = _simulated_phases()
    rows = []
    for k, p in enumerate(phases):
        rows.append(
            Row(
                f"elastic_runtime/sim/phase{k}_n{p['degree']}",
                1e6 / p["throughput_measured"],  # us per item (simulated)
                derived(
                    n_w=p["degree"],
                    thpt=p["throughput_measured"],
                    model=p["throughput_model"],
                    rel_err=p["rel_err"],
                    ok=int(p["within_envelope"]),
                ),
            )
        )
    spmd_rows, spmd_records = _real_spmd_rows()
    rows.extend(spmd_rows)

    report = {
        "workload": {
            "t_f": T_F, "t_acc": T_ACC, "flush_every": FLUSH_EVERY,
            "chunk": CHUNK, "num_chunks": NUM_CHUNKS,
            "schedule": {str(k): v for k, v in SCHEDULE.items()},
            "envelope_tol": ENVELOPE_TOL,
        },
        "simulated_phases": phases,
        "resizes": [
            {
                "t": r.t, "n_old": r.n_old, "n_new": r.n_new,
                "protocol": r.protocol, "handoff_items": r.handoff_items,
            }
            for r in bus.resizes
        ],
        "all_within_envelope": all(p["within_envelope"] for p in phases),
        "real_spmd": spmd_records,
        "trace_path": "results/elastic_runtime_trace.json",
        "metrics_path": "results/elastic_runtime_metrics.json",
    }
    os.makedirs(os.path.join(_REPO, "results"), exist_ok=True)
    # logical clock -> the trace artifact is byte-deterministic
    write_trace(
        os.path.join(_REPO, "results", "elastic_runtime_trace.json"),
        tracer, registry=registry, process_name="elastic_runtime",
    )
    write_metrics(
        os.path.join(_REPO, "results", "elastic_runtime_metrics.json"),
        registry,
    )
    out = os.path.join(_REPO, "results", "elastic_runtime.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(
        Row(
            "elastic_runtime/report",
            0.0,
            derived(
                phases=len(phases),
                all_within_envelope=int(report["all_within_envelope"]),
                path="results/elastic_runtime.json",
            ),
        )
    )
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(run())
