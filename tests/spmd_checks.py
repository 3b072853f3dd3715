"""SPMD equivalence checks for the state access patterns.

Executed as a SUBPROCESS by tests/test_spmd.py so the 8 placeholder host
devices never leak into the main pytest process (smoke tests and benches must
see 1 device).  Exits non-zero on the first failure.
"""

import os

# host devices only: this process must never take the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import patterns, semantics  # noqa: E402


def make_mesh(n):
    return jax.make_mesh(
        (n,), ("workers",), axis_types=(jax.sharding.AxisType.Auto,)
    )


def check_partitioned():
    num_slots = 16
    for n_w in (2, 4, 8):
        mesh = make_mesh(n_w)
        pat = patterns.PartitionedState(
            f=lambda x, s: x * 2 + s,
            ns=lambda x, s: s + x,
            h=lambda x: (x.astype(jnp.int32) * 7) % num_slots,
            num_slots=num_slots,
        )
        xs = jnp.arange(64, dtype=jnp.int32)
        v0 = jnp.zeros((num_slots,), dtype=jnp.int32)
        ys_ref, v_ref = pat.reference(xs, v0)
        ys, v = pat.run(mesh, "workers", xs, v0)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(ys_ref))
    print("partitioned ok")


def check_partitioned_adaptivity():
    # state value invariant under reshard; handoff volume matches block math
    assert patterns.PartitionedState.handoff_volume(16, 4, 4) == 0
    v_up = patterns.PartitionedState.handoff_volume(16, 4, 8)
    v_down = patterns.PartitionedState.handoff_volume(16, 8, 4)
    assert v_up == v_down == 14  # slots 0-1 keep owner 0; the rest move
    assert 0 < patterns.PartitionedState.handoff_volume(64, 8, 16) < 64
    print("partitioned adaptivity ok")


def check_accumulator():
    pat = patterns.AccumulatorState(
        f=lambda x, view: x + view,       # reads the (possibly stale) view
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    xs = jnp.arange(1, 65, dtype=jnp.int32)
    ys_ref, s_ref = pat.reference(xs)
    for n_w in (2, 4, 8):
        mesh = make_mesh(n_w)
        for flush_every in (1, 2, 4, 8):
            ys, s = pat.run(mesh, "workers", xs, flush_every=flush_every)
            # final state exact regardless of schedule ((+) assoc+comm)
            assert int(s) == int(s_ref), (n_w, flush_every, int(s), int(s_ref))
    # merge rule (adaptivity): s_i (+) s_j
    assert int(pat.merge_workers(jnp.int32(3), jnp.int32(4))) == 7
    assert int(pat.new_worker_state()) == 0
    print("accumulator ok")


def check_accumulator_flush1_views():
    # with flush_every=1 and n_w=1 the parallel run IS the serial fold
    pat = patterns.AccumulatorState(
        f=lambda x, view: view,
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.int32(0),
    )
    xs = jnp.arange(1, 17, dtype=jnp.int32)
    ys_ref, s_ref = pat.reference(xs)
    mesh = make_mesh(1)
    ys, s = pat.run(mesh, "workers", xs, flush_every=1)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ys_ref))
    assert int(s) == int(s_ref)
    print("accumulator flush1 ok")


def check_successive():
    pat = patterns.SuccessiveApproximationState(
        c=lambda x, s: x < s,
        s_prime=lambda x, s: jnp.minimum(x, s),
        direction="min",
    )
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.random(64), dtype=jnp.float32)
    trace_ref, s_ref = pat.reference(xs, jnp.float32(np.inf))
    for n_w in (2, 4, 8):
        mesh = make_mesh(n_w)
        for sync_every in (1, 2, 8):
            trace, s = pat.run(
                mesh, "workers", xs, jnp.float32(np.inf), sync_every=sync_every
            )
            # min is assoc+comm: final global state exact
            assert float(s) == float(s_ref)
            # local traces are monotone non-increasing per worker
            tr = np.asarray(trace).reshape(n_w, -1)
            assert (np.diff(tr, axis=1) <= 1e-9).all()
    print("successive ok")


def check_separate():
    pat = patterns.SeparateTaskState(
        f=lambda x: x * x,
        s=lambda y, s: s * 31 + y,  # NON-commutative fold: order must be canonical
    )
    xs = jnp.arange(32, dtype=jnp.int32)
    ys_ref, trace_ref, s_ref = pat.reference(xs, jnp.int32(1))
    for n_w in (2, 4, 8):
        mesh = make_mesh(n_w)
        ys, trace, s = pat.run(mesh, "workers", xs, jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(ys_ref))
        np.testing.assert_array_equal(np.asarray(trace), np.asarray(trace_ref))
        assert int(s) == int(s_ref)
    assert pat.speedup_bound(100.0, 1.0) == 101.0
    print("separate ok")


def check_farm_map():
    from repro.core.farm import TaskFarm
    from jax import lax

    mesh = make_mesh(8)
    farm = TaskFarm(mesh, "workers")
    xs = jnp.arange(64, dtype=jnp.float32)
    ys = farm.map(lambda x: x * 3.0, xs)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(xs) * 3.0)
    tot = farm.map(
        lambda x: x,
        xs,
        collector=lambda y, ax: lax.psum(jnp.sum(y), ax),
    )
    assert float(tot) == float(xs.sum())
    assert farm.n_workers == 8
    print("farm ok")


def check_moe_a2a():
    """Expert-parallel all_to_all MoE == dense oracle (no drops)."""
    from repro.launch.sharding import ShardingRules, use_rules
    from repro.models import moe as moe_lib
    from repro.models.config import MoEConfig

    mesh = jax.make_mesh(
        (4, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    cfg = MoEConfig(num_experts=8, top_k=2, num_shared=1, d_ff_expert=32,
                    capacity_factor=8.0)  # big cf: no drops
    d = 16
    params = moe_lib.init_moe(jax.random.PRNGKey(0), d, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, d), jnp.float32)
    rules = ShardingRules(
        mesh=mesh, dp_axes=("data",), fsdp_axis=None, moe_a2a=True
    )
    out = jax.jit(
        lambda x: moe_lib.moe_ffn_a2a(x, params, cfg, activation="silu",
                                      rules=rules)
    )(x)[0]
    ref_out = jax.jit(
        lambda x: moe_lib.moe_ffn_dense_oracle(x, params, cfg)
    )(x)[0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5
    )
    # gradients flow through the a2a dispatch
    g = jax.grad(
        lambda p: jnp.sum(
            moe_lib.moe_ffn_a2a(x, p, cfg, activation="silu", rules=rules)[0] ** 2
        )
    )(params)
    gnorm = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
    print("moe a2a ok")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.devices()
    check_moe_a2a()
    check_partitioned()
    check_partitioned_adaptivity()
    check_accumulator()
    check_accumulator_flush1_views()
    check_successive()
    check_separate()
    check_farm_map()
    print("ALL SPMD CHECKS PASSED")
