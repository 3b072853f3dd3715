"""SPMD resize-correctness checks for the elastic runtime (§4.x adaptivity).

Executed as a SUBPROCESS by tests/test_runtime.py with 8 placeholder host
devices (same pattern as spmd_checks.py).  Proves the acceptance criterion:
for S2, S3, and S4, a stream processed with mid-stream parallelism-degree
changes (grow AND shrink) produces outputs and final state identical to the
fixed-degree ``reference()`` oracle — bit-exact, since all test functions
are integer or exact-min arithmetic.  Also drills the supervisor's
failure->shrink / recovery->grow path and the compiled-step cache.
"""

import os
import shutil

# host devices only: this process must never take the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import patterns  # noqa: E402
from repro.runtime import (  # noqa: E402
    AccumulatorAdapter,
    Autoscaler,
    FailurePlan,
    PartitionedAdapter,
    QueueDepthPolicy,
    SeparateAdapter,
    StreamExecutor,
    SuccessiveAdapter,
    Supervisor,
)

CHUNK = 16
NUM_CHUNKS = 8


def chunks_of(xs):
    return [xs[i : i + CHUNK] for i in range(0, len(xs), CHUNK)]


# grow 2->4->8 then shrink back to 2 mid-stream
SCHEDULE = {2: 4, 4: 8, 6: 2}


def check_s2_partitioned_resize():
    num_slots = 16
    pat = patterns.PartitionedState(
        f=lambda x, s: x * 2 + s,
        ns=lambda x, s: s + x,
        h=lambda x: (x.astype(jnp.int32) * 7) % num_slots,
        num_slots=num_slots,
    )
    xs = jnp.arange(CHUNK * NUM_CHUNKS, dtype=jnp.int32)
    v0 = jnp.zeros((num_slots,), dtype=jnp.int32)

    ex = StreamExecutor(PartitionedAdapter(pat, v0), degree=2, chunk_size=CHUNK)
    outs = ex.run(chunks_of(xs), schedule=SCHEDULE)

    ys_ref, v_ref = pat.reference(xs, v0)
    got = np.concatenate([np.asarray(o) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    np.testing.assert_array_equal(np.asarray(ex.state), np.asarray(v_ref))
    # resize accounting: three §4.2 block-handoff events with exact volumes
    assert [r.protocol for r in ex.metrics.resizes] == ["S2-block-handoff"] * 3
    assert [r.handoff_items for r in ex.metrics.resizes] == [
        patterns.PartitionedState.handoff_volume(num_slots, a, b)
        for a, b in ((2, 4), (4, 8), (8, 2))
    ]
    print("S2 resize ok")


def check_s2_slotmap_resize():
    """Slot-map ownership: degrees that do NOT divide num_slots (4, 5 over
    18) run and resize bit-exactly, with the slot-map handoff accounting."""
    num_slots = 18
    pat = patterns.PartitionedState(
        f=lambda x, s: x * 3 + s,
        ns=lambda x, s: s + 2 * x,
        h=lambda x: (x.astype(jnp.int32) * 11) % num_slots,
        num_slots=num_slots,
        ownership="slotmap",
    )
    chunk = 20  # divisible by 2, 4, 5 — none of which divide 18 except 2
    xs = jnp.arange(chunk * NUM_CHUNKS, dtype=jnp.int32)
    v0 = jnp.zeros((num_slots,), dtype=jnp.int32)

    ex = StreamExecutor(PartitionedAdapter(pat, v0), degree=2, chunk_size=chunk)
    outs = ex.run(
        [xs[i : i + chunk] for i in range(0, len(xs), chunk)],
        schedule={2: 4, 4: 5, 6: 2},
    )
    ys_ref, v_ref = pat.reference(xs, v0)
    got = np.concatenate([np.asarray(o) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    np.testing.assert_array_equal(np.asarray(ex.state), np.asarray(v_ref))
    assert [r.protocol for r in ex.metrics.resizes] == \
        ["S2-slotmap-handoff"] * 3
    assert [r.handoff_items for r in ex.metrics.resizes] == [
        pat.transition_volume(a, b) for a, b in ((2, 4), (4, 5), (5, 2))
    ]
    print("S2 slotmap resize ok (non-divisor degrees)")


def check_s3_accumulator_resize():
    # f reads only the item (view-independent) so per-item outputs are
    # degree-invariant; the final state is exact by assoc+comm regardless.
    pat = patterns.AccumulatorState(
        f=lambda x, view: x * 3 - 1,
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    xs = jnp.arange(1, CHUNK * NUM_CHUNKS + 1, dtype=jnp.int32)

    ex = StreamExecutor(
        AccumulatorAdapter(pat, flush_every=2), degree=2, chunk_size=CHUNK
    )
    outs = ex.run(chunks_of(xs), schedule=SCHEDULE)

    ys_ref, s_ref = pat.reference(xs)
    got = np.concatenate([np.asarray(o) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    assert int(ex.state) == int(s_ref), (int(ex.state), int(s_ref))
    protos = [r.protocol for r in ex.metrics.resizes]
    assert protos == ["S3-identity-init", "S3-identity-init", "S3-merge"], protos
    print("S3 resize ok")


def check_s3_state_threading():
    """s0 threading: chunk N+1's views include chunk N's commits (run a
    view-reading f at fixed degree and compare to one whole-stream run)."""
    pat = patterns.AccumulatorState(
        f=lambda x, view: view,
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    xs = jnp.arange(1, 33, dtype=jnp.int32)
    ex = StreamExecutor(AccumulatorAdapter(pat, flush_every=4), degree=2,
                        chunk_size=16)
    chunked = ex.run([xs[i : i + 16] for i in range(0, 32, 16)])
    whole_ys, whole_s = pat.run(
        jax.make_mesh((2,), ("workers",),
                      axis_types=(jax.sharding.AxisType.Auto,)),
        "workers", xs, flush_every=4,
    )
    # NOTE: chunked views flush MORE often at chunk boundaries than one whole
    # run with the same flush period would between chunks — the final states
    # must agree exactly, the (stale) views need not.
    assert int(ex.state) == int(whole_s) == int(jnp.sum(xs))
    print("S3 state threading ok")


def check_s4_successive_resize():
    pat = patterns.SuccessiveApproximationState(
        c=lambda x, s: x < s,
        s_prime=lambda x, s: jnp.minimum(x, s),
        direction="min",
    )
    rng = np.random.default_rng(7)
    data = rng.integers(0, 1_000_000, size=CHUNK * NUM_CHUNKS)
    xs = jnp.asarray(data, dtype=jnp.int32)

    ex = StreamExecutor(
        SuccessiveAdapter(pat, jnp.int32(2_000_000), sync_every=2),
        degree=2,
        chunk_size=CHUNK,
    )
    outs = ex.run(chunks_of(xs), schedule=SCHEDULE)

    # oracle: serial fold; committed value after chunk k is the running min
    # over everything seen so far — degree-invariant because min is exact.
    running = 2_000_000
    for k, out in enumerate(outs):
        running = min(running, int(data[: (k + 1) * CHUNK].min()))
        assert int(out["committed"]) == running, (k, int(out["committed"]), running)
    _, s_ref = pat.reference(xs, jnp.int32(2_000_000))
    assert int(ex.state) == int(s_ref) == int(data.min())
    assert all(r.protocol == "S4-global-join" for r in ex.metrics.resizes)
    print("S4 resize ok")


def check_s5_separate_resize():
    pat = patterns.SeparateTaskState(
        f=lambda x: x * x,
        s=lambda y, s: s * 31 + y,  # non-commutative: order must be canonical
    )
    xs = jnp.arange(CHUNK * NUM_CHUNKS, dtype=jnp.int32)
    ex = StreamExecutor(SeparateAdapter(pat, jnp.int32(1)), degree=2,
                        chunk_size=CHUNK)
    outs = ex.run(chunks_of(xs), schedule=SCHEDULE)
    ys_ref, trace_ref, s_ref = pat.reference(xs, jnp.int32(1))
    got = np.concatenate([np.asarray(o["ys"]) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    assert int(ex.state) == int(s_ref)
    assert all(r.protocol == "S5-noop" for r in ex.metrics.resizes)
    print("S5 resize ok")


def check_compiled_step_cache():
    """Resizing back to an old degree must reuse the cached compiled step."""
    pat = patterns.SeparateTaskState(f=lambda x: x + 1, s=lambda y, s: s + y)
    ex = StreamExecutor(SeparateAdapter(pat, jnp.int32(0)), degree=2,
                        chunk_size=CHUNK)
    xs = jnp.arange(CHUNK, dtype=jnp.int32)
    ex.process(xs)
    step2 = ex._steps[2]
    ex.set_degree(4, reason="test")
    ex.process(xs)
    ex.set_degree(2, reason="test")
    assert ex._steps[2] is step2  # same jitted callable: no re-trace
    ex.process(xs)
    assert ex.compiled_degrees == [2, 4]
    print("compiled-step cache ok")


def check_autoscaler_online():
    """Queue-depth policy grows under backlog and shrinks when drained, and
    the resized run still matches the oracle bit-exactly."""
    from repro.runtime import BackpressureQueue, BoundedSource, Chunker, ConstantRate, pump

    num_slots = 16
    pat = patterns.PartitionedState(
        f=lambda x, s: x + 3 * s,
        ns=lambda x, s: s + 2 * x,
        h=lambda x: (x.astype(jnp.int32) * 13) % num_slots,
        num_slots=num_slots,
    )
    data = np.arange(CHUNK * 12, dtype=np.int32)
    v0 = jnp.zeros((num_slots,), dtype=jnp.int32)
    ex = StreamExecutor(PartitionedAdapter(pat, v0), degree=2, chunk_size=CHUNK)
    scaler = Autoscaler(
        QueueDepthPolicy(), candidates=[2, 4, 8], cooldown_chunks=1
    )
    src = BoundedSource(data)
    q = BackpressureQueue(capacity=6 * CHUNK, high_watermark=3 * CHUNK,
                          low_watermark=CHUNK // 2)
    chunker = Chunker(CHUNK)
    outs, pend, t = [], None, 0
    while not (src.exhausted and q.depth == 0):
        # heavy arrivals early (backlog builds), then the source drains
        pend = pump(src, ConstantRate(3 * CHUNK), q, t, pending=pend)
        q.observe()
        while chunker.ready(q):
            scaler.maybe_scale(ex, queue=q)  # decide on pre-take depth
            c = chunker.next_chunk(q)
            outs.append(ex.process(c, queue_depth=q.depth))
        t += 1
    ys_ref, v_ref = pat.reference(jnp.asarray(data), v0)
    got = np.concatenate([np.asarray(o) for o in outs])
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    np.testing.assert_array_equal(np.asarray(ex.state), np.asarray(v_ref))
    assert len(ex.metrics.resizes) >= 1, "backlog never triggered a resize"
    assert any(r.n_new > r.n_old for r in ex.metrics.resizes), "no grow event"
    print(f"autoscaler online ok ({len(ex.metrics.resizes)} resizes, "
          f"final degree {ex.degree})")


def check_supervisor_failure_recovery():
    pat = patterns.AccumulatorState(
        f=lambda x, view: x,
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    data = np.arange(1, CHUNK * 6 + 1, dtype=np.int32)

    def chunk_fn(i):
        return jnp.asarray(data[i * CHUNK : (i + 1) * CHUNK])

    ckpt_dir = "/tmp/repro_runtime_supervisor_test"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    ex = StreamExecutor(AccumulatorAdapter(pat, flush_every=4), degree=4,
                        chunk_size=CHUNK)
    sup = Supervisor(
        ex, chunk_fn, num_chunks=6, ckpt_dir=ckpt_dir, ckpt_every=2,
        failure_plan=FailurePlan(fail_at=3, recover_after=2),
    )
    outs = sup.run()
    assert sorted(outs) == list(range(6))
    got = np.concatenate([np.asarray(outs[i]) for i in range(6)])
    ys_ref, s_ref = pat.reference(jnp.asarray(data))
    np.testing.assert_array_equal(got, np.asarray(ys_ref))
    assert int(ex.state) == int(s_ref)
    kinds = [e.kind for e in sup.events]
    assert "failure" in kinds and "shrink" in kinds and "grow" in kinds, kinds
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("supervisor failure/recovery ok")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.devices()
    check_s2_partitioned_resize()
    check_s2_slotmap_resize()
    check_s3_accumulator_resize()
    check_s3_state_threading()
    check_s4_successive_resize()
    check_s5_separate_resize()
    check_compiled_step_cache()
    check_autoscaler_online()
    check_supervisor_failure_recovery()
    print("ALL RUNTIME CHECKS PASSED")
