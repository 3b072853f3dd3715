"""Child process for benchmarks.shardmap_farm — real shard_map farm on 16
placeholder host devices.  Prints CSV rows on stdout."""

import os

# host devices only: this process must never take the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=16 "
    + os.environ.get("XLA_FLAGS", "")
)

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import patterns  # noqa: E402

M = 4096
D = 64  # per-task dummy work: D x D matvec chain


def main() -> None:
    mesh = jax.make_mesh(
        (16,), ("workers",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    w = jnp.eye(D, dtype=jnp.float32) * 0.999

    def f(x, view):  # t_f: dummy compute reading the (stale) state view
        vec = jnp.full((D,), x, dtype=jnp.float32)
        for _ in range(4):
            vec = jnp.tanh(w @ vec)
        return jnp.sum(vec) + view

    pat = patterns.AccumulatorState(
        f=f,
        g=lambda x: x,
        combine=lambda a, b: a + b,
        zero=lambda: jnp.float32(0.0),
    )
    xs = jnp.linspace(0.0, 1.0, M, dtype=jnp.float32)

    for flush_every in (1, 4, 16, 64, 256):
        run = jax.jit(
            lambda xs: pat.run(mesh, "workers", xs, flush_every=flush_every)
        )
        lowered = run.lower(xs)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        static_ars = hlo.count("all-reduce(")
        dyn_flushes = (M // 16) // flush_every
        ys, s = run(xs)
        jax.block_until_ready((ys, s))
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            ys, s = run(xs)
        jax.block_until_ready((ys, s))
        us = (time.perf_counter() - t0) / iters * 1e6
        print(
            f"shardmap_farm/accumulator/flush={flush_every},{us:.3f},"
            f"final_state={float(s):.4g};allreduce_sites={static_ars};"
            f"flushes_per_step={dyn_flushes};devices={jax.device_count()}"
        )


if __name__ == "__main__":
    main()
