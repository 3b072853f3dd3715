"""Child process for benchmarks/elastic_runtime.py: REAL SPMD elastic run.

8 placeholder host devices; a StreamExecutor drives the S2 partitioned
pattern through a grow/grow/shrink schedule.  Prints aggregator CSV rows
plus one JSON line per phase/resize (consumed by the parent's report).

On a 1-core container wall-clock scaling is not meaningful; what this
establishes is (a) resizes preserve outputs while the farm keeps serving,
(b) the §4.2 handoff accounting, and (c) the compiled-step cache: revisiting
a degree costs ~0 compile (the cache-hit row).
"""

import json
import os
import time

# host devices only: this process must never take the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import patterns  # noqa: E402
from repro.runtime import PartitionedAdapter, StreamExecutor  # noqa: E402

CHUNK = 64
NUM_CHUNKS = 12
NUM_SLOTS = 32
SCHEDULE = {3: 4, 6: 8, 9: 4}  # grow, grow, shrink (4 revisited -> cache hit)


def main() -> None:
    pat = patterns.PartitionedState(
        f=lambda x, s: x * 2 + s,
        ns=lambda x, s: s + x,
        h=lambda x: (x.astype(jnp.int32) * 7) % NUM_SLOTS,
        num_slots=NUM_SLOTS,
    )
    xs = np.arange(CHUNK * NUM_CHUNKS, dtype=np.int32)
    v0 = jnp.zeros((NUM_SLOTS,), dtype=jnp.int32)
    ex = StreamExecutor(PartitionedAdapter(pat, v0), degree=2, chunk_size=CHUNK)

    resize_cost = {}
    phase = {"degree": 2, "items": 0, "t0": time.perf_counter()}
    phases = []

    def close_phase():
        span = time.perf_counter() - phase["t0"]
        if phase["items"] and span > 0:
            phases.append(
                {
                    "degree": phase["degree"],
                    "items": phase["items"],
                    "throughput_items_per_s": phase["items"] / span,
                }
            )

    for i in range(NUM_CHUNKS):
        if i in SCHEDULE:
            close_phase()
            t0 = time.perf_counter()
            rec = ex.set_degree(SCHEDULE[i], reason=f"schedule@chunk{i}")
            resize_cost[f"{rec.n_old}->{rec.n_new}"] = time.perf_counter() - t0
            phase = {"degree": SCHEDULE[i], "items": 0,
                     "t0": time.perf_counter()}
        ex.process(jnp.asarray(xs[i * CHUNK : (i + 1) * CHUNK]))
        phase["items"] += CHUNK
    close_phase()

    # correctness gate: the elastic run must equal the serial oracle
    _, v_ref = pat.reference(jnp.asarray(xs), v0)
    assert (np.asarray(ex.state) == np.asarray(v_ref)).all(), "resize broke state"

    # compile-cache: revisiting degree 4 must not add a new compiled step
    assert ex.compiled_degrees == [2, 4, 8], ex.compiled_degrees

    for k, p in enumerate(phases):
        print(
            f"elastic_runtime/spmd/phase{k}_n{p['degree']},"
            f"{1e6 / p['throughput_items_per_s']:.3f},"
            f"n_w={p['degree']};thpt={p['throughput_items_per_s']:.4g}"
        )
    for edge, cost in resize_cost.items():
        print(f"elastic_runtime/spmd/resize_{edge},{cost * 1e6:.3f},"
              f"protocol=S2-block-handoff")
    for p in phases:
        print(json.dumps({"kind": "phase", **p}))
    for r in ex.metrics.resizes:
        print(json.dumps({
            "kind": "resize", "n_old": r.n_old, "n_new": r.n_new,
            "protocol": r.protocol, "handoff_items": r.handoff_items,
            "cost_s": resize_cost.get(f"{r.n_old}->{r.n_new}"),
        }))


if __name__ == "__main__":
    main()
