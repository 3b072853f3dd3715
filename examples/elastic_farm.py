"""Elastic scaling demo — the paper's §4.x adaptivity protocols:

* the `repro.runtime` elastic streaming runtime: an autoscaled farm over a
  live bursty stream, resizing online through the §4.x protocols;
* S2 partitioned: grow the farm 4 -> 8 workers; state handoff volume per the
  block protocol; results unchanged.
* S3 accumulator: shrink 8 -> 4 by merging workers (s_i (+) s_j).
* S4 successive approximation: new workers join with the current global best.
* checkpoint-mediated mesh resize for a training state.

Run:  PYTHONPATH=src python examples/elastic_farm.py
(8 placeholder host devices on the CPU backend are set before jax import —
demo only; it never takes an accelerator.)
"""

import os

# host devices only: this demo must never take the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import shutil  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint import checkpoint as ckpt  # noqa: E402
from repro.core import AccumulatorState, PartitionedState  # noqa: E402


def mesh(n):
    return jax.make_mesh((n,), ("workers",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def runtime_demo() -> None:
    """The tentpole path: a live stream, a backpressure queue, and an
    autoscaler resizing the S2 farm online — outputs equal to the oracle."""
    import numpy as np

    from repro.core import PartitionedState
    from repro.runtime import (
        Autoscaler, BackpressureQueue, BoundedSource, BurstyRate, Chunker,
        PartitionedAdapter, QueueDepthPolicy, StreamExecutor, pump,
    )

    num_slots = 16
    pat = PartitionedState(
        f=lambda x, s: x * 2 + s, ns=lambda x, s: s + x,
        h=lambda x: (x.astype(jnp.int32) * 7) % num_slots, num_slots=num_slots,
    )
    data = np.arange(256, dtype=np.int32)
    ex = StreamExecutor(
        PartitionedAdapter(pat, jnp.zeros(num_slots, jnp.int32)),
        degree=2, chunk_size=16,
    )
    scaler = Autoscaler(QueueDepthPolicy(), candidates=[2, 4, 8],
                        cooldown_chunks=1)
    src = BoundedSource(data)
    q = BackpressureQueue(96, high_watermark=48, low_watermark=8)
    chunker = Chunker(16)
    outs, pend, t = [], None, 0
    while not (src.exhausted and q.depth == 0):
        pend = pump(src, BurstyRate(base=8, burst=64, period=4, duty=2), q, t,
                    pending=pend)
        q.observe()
        while chunker.ready(q):
            scaler.maybe_scale(ex, queue=q)
            outs.append(ex.process(chunker.next_chunk(q), queue_depth=q.depth))
        t += 1
    ys_ref, v_ref = pat.reference(jnp.asarray(data), jnp.zeros(num_slots, jnp.int32))
    assert (np.concatenate([np.asarray(o) for o in outs]) == np.asarray(ys_ref)).all()
    assert (np.asarray(ex.state) == np.asarray(v_ref)).all()
    edges = [(r.n_old, r.n_new, r.protocol) for r in ex.metrics.resizes]
    print(f"runtime: {len(outs)} chunks, resizes {edges}, "
          f"final degree {ex.degree} — outputs == serial oracle")


def main() -> None:
    runtime_demo()
    xs = jnp.arange(64, dtype=jnp.int32)
    pat = PartitionedState(
        f=lambda x, s: s, ns=lambda x, s: s + x, h=lambda x: x % 16,
        num_slots=16,
    )
    v0 = jnp.zeros(16, jnp.int32)

    # run on 4 workers, grow to 8 (paper §4.2 adaptivity)
    ys, v4 = pat.run(mesh(4), "workers", xs[:32], v0)
    moved = PartitionedState.handoff_volume(16, 4, 8)
    print(f"S2 grow 4->8: {moved}/16 slots change owner (block protocol)")
    v_res = PartitionedState.reshard(v4, 4, 8)  # value is placement-invariant
    from jax.sharding import NamedSharding, PartitionSpec as P

    m8 = mesh(8)
    v_res = jax.device_put(v_res, NamedSharding(m8, P("workers")))  # the handoff
    ys2, v8 = pat.run(m8, "workers", xs[32:], v_res)
    # oracle: one serial pass over the whole stream
    _, v_ref = pat.reference(xs, v0)
    assert (v8 == v_ref).all(), (v8, v_ref)
    print(f"   state after resize matches serial oracle: {v8.tolist()}")

    # S3: merge two workers' accumulators when shrinking
    acc = AccumulatorState(
        f=lambda x, s: s, g=lambda x: x, combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    merged = acc.merge_workers(jnp.int32(100), jnp.int32(23))
    print(f"S3 shrink: merged accumulator {int(merged)} (= s_i + s_j)")

    # checkpoint-mediated resize of a sharded training-ish state
    tmp = "/tmp/repro_elastic_ckpt"
    shutil.rmtree(tmp, ignore_errors=True)
    state = {"w": jnp.arange(32.0).reshape(8, 4)}
    ckpt.save(tmp, 1, state, metadata={"note": "resize demo"})
    from jax.sharding import NamedSharding, PartitionSpec as P

    new_mesh = mesh(8)
    shardings = {"w": NamedSharding(new_mesh, P("workers", None))}
    restored, _ = ckpt.restore(tmp, 1, state, sharding_tree=shardings)
    print(f"ckpt resize: restored onto 8-way mesh, sharding "
          f"{restored['w'].sharding.spec}, value ok="
          f"{bool((restored['w'] == state['w']).all())}")


if __name__ == "__main__":
    main()
