"""Task-farm and pipeline runners over a device mesh (paper §2).

The farm maps the paper's emitter/workers/collector onto SPMD: a stream chunk
arrives sharded over the worker axis (emitter = the sharding), each shard
applies the worker function, and (optionally) a collector collective merges
results.  A gpipe-style pipeline runner is included for completeness (the
paper's other canonical stream pattern) and exercised at smoke scale.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map


@dataclasses.dataclass(frozen=True)
class TaskFarm:
    """Stateless farm: ``ys = map(f, xs)`` with xs sharded over ``axis``.

    ``ordered=False`` reflects the paper's collector-less variant (no global
    reordering); per-shard order is preserved.
    """

    mesh: Mesh
    axis: str

    @property
    def n_workers(self) -> int:
        return self.mesh.shape[self.axis]

    def map(self, f: Callable, xs, *, collector: Optional[Callable] = None):
        def worker(xs_local):
            ys_local = jax.vmap(f)(xs_local)
            if collector is not None:
                ys_local = collector(ys_local, self.axis)
            return ys_local

        out_spec = P() if collector is not None else P(self.axis)
        return shard_map(
            worker, mesh=self.mesh, in_specs=(P(self.axis),), out_specs=out_spec
        )(xs)

    def run_stream(self, step: Callable, stream: Sequence, state, *run_args):
        """Drive a stateful pattern over successive stream chunks.

        ``step(state, chunk) -> (state, out)`` where ``step`` is typically a
        closed-over ``pattern.run(mesh, axis, ...)``.

        Subsumed by :class:`repro.runtime.executor.StreamExecutor`, which
        adds online resizing, metrics, and a compiled-step cache; this
        wrapper delegates to the executor module's chunked fold and is kept
        for fixed-degree callers.
        """
        from repro.runtime import executor as _executor  # local: no cycle

        return _executor.run_stream(step, stream, state, *run_args)


def pipeline_stages(
    stage_fns: Sequence[Callable],
    xs,
    *,
    num_microbatches: int,
):
    """Reference gpipe-style pipeline over stages (paper's pipeline pattern).

    Single-program form: microbatches flow through `stage_fns` with a rolled
    schedule; stage ``i`` processes microbatch ``t - i`` at tick ``t``.  Used
    at smoke scale to validate the schedule math (the production mesh uses the
    pod axis for data parallelism instead — see DESIGN §7).
    """
    n_stages = len(stage_fns)
    mb = jax.tree.map(
        lambda leaf: leaf.reshape((num_microbatches, -1) + leaf.shape[1:]), xs
    )
    # simple sequential-fill schedule: correctness reference, not a perf model
    outs = []
    for i in range(num_microbatches):
        x = jax.tree.map(lambda leaf: leaf[i], mb)
        for fn in stage_fns:
            x = fn(x)
        outs.append(x)
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *outs)
