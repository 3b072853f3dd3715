"""Parallel implementations of the paper's state access patterns (paper §4).

Each pattern is a small, composable object with three faces:

* ``run(mesh, axis, ...)`` — an SPMD execution of a stream chunk over the
  worker axis of a device mesh (`jax.shard_map`).  The farm's *emitter* is the
  input sharding, the *workers* are the shards along ``axis``, and the
  *collector* (the paper's mutually-exclusive global-state commit) is a
  collective (`psum`/`pmin`/`all_gather`).
* ``reference(...)`` — the serial oracle (delegates to
  :mod:`repro.core.semantics`).
* adaptivity helpers — the paper's §4.x "Adaptivity" protocols: repartition /
  merge / re-init state when the parallelism degree changes.

The upper layers of the framework consume these: gradient accumulation and
metrics use :class:`AccumulatorState`, the serving KV-session store and MoE
dispatch use :class:`PartitionedState`, best-checkpoint tracking uses
:class:`SuccessiveApproximationState`, the (ZeRO-sharded) optimizer step uses
:class:`SeparateTaskState`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import semantics

shard_map = jax.shard_map


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def _pvary(x, axis: str):
    """Mark a replicated value as device-varying over ``axis`` (VMA typing)
    so it can seed a scan carry that becomes varying."""
    return jax.tree.map(lambda leaf: lax.pcast(leaf, (axis,), to="varying"), x)


def _unvary(x, axis: str):
    """Re-type a value known to be identical on every shard of ``axis`` as
    axis-invariant (so it can leave shard_map with out_spec P()).  `pmax` of
    identical numeric values is exact."""
    return jax.tree.map(lambda leaf: lax.pmax(leaf, axis), x)


# ---------------------------------------------------------------------------
# §4.1 Serial
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SerialState:
    """The degenerate pattern: state serializes the whole computation.

    Kept as (a) the semantic oracle and (b) an honest implementation — the
    paper's point is that this class admits *no* parallelism, so ``run``
    is simply the sequential fold executed identically on every shard.
    """

    f: Callable
    ns: Callable

    def reference(self, xs, s0):
        return semantics.serial(self.f, self.ns, xs, s0)

    def run(self, mesh: Mesh, axis: str, xs, s0):
        # State dependence chains every task: no decomposition is sound.
        return semantics.serial(self.f, self.ns, xs, s0)


# ---------------------------------------------------------------------------
# §4.2 Fully partitioned
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartitionedState:
    """State is a vector ``v[0..N)``; ``h`` maps tasks to slots; every slot
    has exactly one owning worker (paper §4.2).

    Two ownership modes:

    * ``ownership="block"`` (the paper's distribution): slot ``p`` is owned
      by ``p // (N // n_w)``; only divisors of ``num_slots`` are feasible
      degrees, and the state vector is sharded over the worker axis.
    * ``ownership="slotmap"`` (generalized, `repro.keyed`-style): ownership
      is an explicit balanced slot -> owner table
      (``owner(p) = (p * n_w) // N``), so **any** degree in
      ``[1, num_slots]`` is feasible; the state vector is replicated and
      each worker commits only its owned slots (reassembled by `psum`).

    ``run`` routes every task to its owner: each worker scans the *whole*
    stream chunk in order, masking in the tasks it owns.  Per-slot update
    order equals stream order (the paper's guarantee), outputs are exchanged
    with a `psum` (each task is computed by exactly one worker).  This is the
    semantically-exact farm; the high-throughput realizations (MoE
    ``all_to_all`` dispatch, KV-session routing, the `repro.keyed`
    sort+segment-reduce engine) live in the upper layers and are tested
    against this.
    """

    f: Callable
    ns: Callable
    h: Callable
    num_slots: int
    ownership: str = "block"   # "block" | "slotmap"

    def __post_init__(self):
        if self.ownership not in ("block", "slotmap"):
            raise ValueError(f"unknown ownership mode {self.ownership!r}")

    def reference(self, xs, v0):
        return semantics.partitioned(self.f, self.ns, self.h, xs, v0)

    # -- ownership -----------------------------------------------------------
    def slots_per_worker(self, n_w: int) -> int:
        if n_w < 1:
            raise ValueError(f"worker count must be >= 1, got {n_w}")
        if self.num_slots % n_w:
            raise ValueError(
                f"block ownership needs num_slots % n_w == 0: "
                f"num_slots={self.num_slots} does not divide over {n_w} workers "
                f"(remainder {self.num_slots % n_w}); choose a worker count "
                f"from the divisors of {self.num_slots}"
            )
        return self.num_slots // n_w

    def owner_table(self, n_w: int) -> np.ndarray:
        """slot -> owner, length ``num_slots``.  Balanced-contiguous in
        slotmap mode (reduces to the block rule when ``n_w`` divides);
        the block rule (validated) otherwise."""
        if self.ownership == "slotmap":
            if not 1 <= n_w <= self.num_slots:
                raise ValueError(
                    f"worker count must be in [1, {self.num_slots}], got {n_w}"
                )
            return ((np.arange(self.num_slots, dtype=np.int64) * n_w)
                    // self.num_slots).astype(np.int32)
        return (np.arange(self.num_slots) // self.slots_per_worker(n_w)
                ).astype(np.int32)

    def owner(self, slot, n_w: int):
        if self.ownership == "slotmap":
            return (slot * n_w) // self.num_slots
        return slot // self.slots_per_worker(n_w)

    def validate_degree(self, n_w: int) -> None:
        self.owner_table(n_w)  # raises on an infeasible degree

    def feasible_degrees(self, max_degree: int) -> list:
        """Degrees this ownership mode admits — the autoscaler's clamp.
        Derived from :meth:`validate_degree` so the feasibility rule has a
        single source of truth."""
        out = []
        for n in range(1, min(max_degree, self.num_slots) + 1):
            try:
                self.validate_degree(n)
            except ValueError:
                continue
            out.append(n)
        return out

    # -- SPMD execution -------------------------------------------------------
    def run(self, mesh: Mesh, axis: str, xs, v0):
        """xs sharded over ``axis`` (emitter); v0 sharded over ``axis`` in
        block mode, replicated in slotmap mode.

        Returns ``(ys, v_final)`` with matching shardings.
        """
        if self.ownership == "slotmap":
            return self._run_slotmap(mesh, axis, xs, v0)
        n_w = _axis_size(mesh, axis)
        spw = self.slots_per_worker(n_w)
        f, ns, h = self.f, self.ns, self.h

        def worker(v_local, xs_local):
            w = lax.axis_index(axis)
            xs_all = jax.tree.map(
                lambda leaf: lax.all_gather(leaf, axis, tiled=True), xs_local
            )

            def step(v, x):
                slot = h(x)
                mine = (slot // spw) == w
                local_slot = jnp.where(mine, slot - w * spw, 0)
                sp = jax.tree.map(lambda leaf: leaf[local_slot], v)
                y = f(x, sp)
                new_sp = ns(x, sp)
                v = jax.tree.map(
                    lambda leaf, nl: leaf.at[local_slot].set(
                        jnp.where(mine, nl, leaf[local_slot])
                    ),
                    v,
                    new_sp,
                )
                y = jax.tree.map(lambda leaf: jnp.where(mine, leaf, 0), y)
                return v, y

            v_final, ys_all = lax.scan(step, v_local, xs_all)
            # each y computed by exactly one worker -> psum reassembles stream
            ys_all = jax.tree.map(lambda leaf: lax.psum(leaf, axis), ys_all)
            # hand back this worker's emitter slice
            chunk = jax.tree.map(lambda leaf: leaf.shape[0] // n_w, ys_all)
            ys_local = jax.tree.map(
                lambda leaf, c: lax.dynamic_slice_in_dim(leaf, w * c, c, axis=0),
                ys_all,
                chunk,
            )
            return ys_local, v_final

        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )(v0, xs)

    def _run_slotmap(self, mesh: Mesh, axis: str, xs, v0):
        """Slot-map ownership run: the state vector is replicated, each
        worker scans the chunk committing only its owned slots, and the
        final vector is reassembled slot-by-slot from the owners (exactly
        one worker contributes each slot, so `psum` of the masked vectors
        is exact)."""
        n_w = _axis_size(mesh, axis)
        table = jnp.asarray(self.owner_table(n_w), jnp.int32)
        f, ns, h = self.f, self.ns, self.h

        def worker(v_rep, xs_local):
            w = lax.axis_index(axis)
            xs_all = jax.tree.map(
                lambda leaf: lax.all_gather(leaf, axis, tiled=True), xs_local
            )

            def step(v, x):
                slot = h(x)
                mine = table[slot] == w
                sp = jax.tree.map(lambda leaf: leaf[slot], v)
                y = f(x, sp)
                new_sp = ns(x, sp)
                v = jax.tree.map(
                    lambda leaf, nl: leaf.at[slot].set(
                        jnp.where(mine, nl, leaf[slot])
                    ),
                    v,
                    new_sp,
                )
                y = jax.tree.map(lambda leaf: jnp.where(mine, leaf, 0), y)
                return v, y

            v_scanned, ys_all = lax.scan(step, _pvary(v_rep, axis), xs_all)
            ys_all = jax.tree.map(lambda leaf: lax.psum(leaf, axis), ys_all)
            chunk = jax.tree.map(lambda leaf: leaf.shape[0] // n_w, ys_all)
            ys_local = jax.tree.map(
                lambda leaf, c: lax.dynamic_slice_in_dim(leaf, w * c, c, axis=0),
                ys_all,
                chunk,
            )
            own = table == w
            v_final = jax.tree.map(
                lambda leaf: lax.psum(
                    jnp.where(
                        own.reshape(own.shape + (1,) * (leaf.ndim - 1)),
                        leaf,
                        0,
                    ),
                    axis,
                ),
                v_scanned,
            )
            return ys_local, v_final

        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(P(), P(axis)),
            out_specs=(P(axis), P()),
        )(v0, xs)

    # -- adaptivity (paper §4.2): repartition slots over a new worker count ---
    @staticmethod
    def reshard(v: Any, n_old: int, n_new: int) -> Any:
        """Block repartitioning of the state vector onto ``n_new`` workers.

        With block ownership the repartition is a pure re-slicing: worker ``i``
        of the new farm owns slots ``[i*N/n_new, (i+1)*N/n_new)``; the handoff
        volume matches the paper's neighbour-transfer accounting.  Returns the
        (logically identical) state vector — callers re-place it with the new
        sharding; `repro.checkpoint.reshard` does the device placement.
        """
        del n_old, n_new  # block layout: value is placement-invariant
        return v

    @staticmethod
    def handoff_volume(num_slots: int, n_old: int, n_new: int) -> int:
        """Number of slots that change owner when n_old -> n_new (paper's
        adaptivity cost).

        Both degrees must divide ``num_slots`` — with a ragged block size the
        floor-division owner map silently mis-assigns the tail slots, so the
        count would be wrong rather than approximate.
        """
        for name, n in (("n_old", n_old), ("n_new", n_new)):
            if n < 1:
                raise ValueError(f"{name} must be >= 1, got {n}")
            if num_slots % n:
                raise ValueError(
                    f"handoff accounting needs num_slots % {name} == 0: "
                    f"num_slots={num_slots}, {name}={n} "
                    f"(remainder {num_slots % n})"
                )
        old_owner = np.arange(num_slots) // (num_slots // n_old)
        new_owner = np.arange(num_slots) // (num_slots // n_new)
        return int(np.sum(old_owner != new_owner))

    def transition_volume(self, n_old: int, n_new: int) -> int:
        """Slots changing owner for *this* pattern's ownership mode.

        Block mode delegates to :meth:`handoff_volume` (divisor degrees
        only); slotmap mode diffs the canonical balanced tables — the
        compiled step bakes the canonical table per degree, so a transition
        moves exactly the slots on which the two tables disagree.  (The
        keyed store's :class:`repro.keyed.store.SlotMap` instead migrates a
        *minimal* set, which host-driven steps can afford because ownership
        is read from state rather than baked into compiled code.)
        """
        if self.ownership == "slotmap":
            return int(
                np.sum(self.owner_table(n_old) != self.owner_table(n_new))
            )
        return self.handoff_volume(self.num_slots, n_old, n_new)


# ---------------------------------------------------------------------------
# §4.3 Accumulator
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AccumulatorState:
    """``s = g(x) (+) s`` with associative+commutative ``(+)``.

    Workers keep local accumulators initialized to the identity and flush to
    the collector every ``flush_every`` tasks; on TPU the collector *is* a
    `psum` over the worker axis (the reduction tree plays the role of the
    collector thread, and the reduced value arriving at every shard is the
    paper's collector->emitter->workers feedback broadcast).

    ``flush_every`` trades collector pressure against staleness of the view
    read by ``f`` — the paper's Fig. 4 knob, and exactly the gradient
    accumulation period in the training substrate.
    """

    f: Callable           # f : alpha x gamma -> beta, reads the *view*
    g: Callable           # g : alpha -> gamma
    combine: Callable     # (+)
    zero: Callable        # () -> gamma identity

    def reference(self, xs):
        return semantics.accumulator(self.f, self.g, self.combine, xs, self.zero())

    def run(self, mesh: Mesh, axis: str, xs, flush_every: int, s0=None):
        """xs sharded over ``axis``; returns (ys sharded, s_global replicated).

        The returned global state is exact (associativity/commutativity);
        per-item ys read the latest flushed global view plus the local
        accumulator — matching the paper's first implementation variant.

        ``s0`` (replicated) seeds the global view — the long-running runtime
        threads the committed state across successive stream chunks with it,
        so chunk N+1's views include chunk N's flushes.  Defaults to the
        identity (a single-chunk run).
        """
        f, g, combine, zero = self.f, self.g, self.combine, self.zero

        def worker(xs_local, s_init):
            m_local = jax.tree.leaves(xs_local)[0].shape[0]
            if m_local % flush_every:
                raise ValueError("flush_every must divide the local chunk size")
            blocks = m_local // flush_every
            xs_blocks = jax.tree.map(
                lambda leaf: leaf.reshape((blocks, flush_every) + leaf.shape[1:]),
                xs_local,
            )

            def flush_block(carry, x_block):
                s_global_view = carry  # last flushed global value

                def one(acc, x):
                    view = combine(acc, s_global_view)
                    y = f(x, view)
                    return combine(g(x), acc), y

                acc, ys = lax.scan(one, _pvary(zero(), axis), x_block)
                # collector commit: exact because (+) is assoc+comm
                s_new = combine(lax.psum(acc, axis), s_global_view)
                return s_new, ys

            s_final, ys = lax.scan(flush_block, s_init, xs_blocks)
            ys = jax.tree.map(
                lambda leaf: leaf.reshape((m_local,) + leaf.shape[2:]), ys
            )
            return ys, s_final

        s_init = zero() if s0 is None else s0
        return shard_map(
            worker, mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(axis), P()),
        )(xs, s_init)

    # -- adaptivity (paper §4.3) ----------------------------------------------
    def merge_workers(self, s_i, s_j):
        """Merged worker's accumulator = ``s_i (+) s_j`` (paper's merge rule)."""
        return self.combine(s_i, s_j)

    def new_worker_state(self):
        """New workers start from the identity."""
        return self.zero()


# ---------------------------------------------------------------------------
# §4.4 Successive approximation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SuccessiveApproximationState:
    """Monotone best-so-far state with stale local copies.

    Workers evaluate the condition ``c`` against a *local* copy; proposals are
    committed by a monotone collective (`pmin`/`pmax`) every ``sync_every``
    tasks — non-improving proposals are discarded by the reduction itself,
    which is the collector's monotonic filter.  Stale local copies only cause
    *extra* proposals (paper's third overhead), never wrong final state.
    """

    c: Callable        # c : alpha x gamma -> bool
    s_prime: Callable  # s' : alpha x gamma -> gamma, monotone w.r.t. `better`
    direction: str = "min"  # "min": s' <= s ; "max": s' >= s

    def _commit(self, s, axis):
        return jax.tree.map(
            lambda leaf: (lax.pmin if self.direction == "min" else lax.pmax)(
                leaf, axis
            ),
            s,
        )

    def _merge(self, a, b):
        op = jnp.minimum if self.direction == "min" else jnp.maximum
        return jax.tree.map(op, a, b)

    def reference(self, xs, s_init):
        return semantics.successive_approximation(self.c, self.s_prime, xs, s_init)

    def run(self, mesh: Mesh, axis: str, xs, s_init, sync_every: int):
        """xs sharded over ``axis``; returns (local trace sharded, s_global)."""
        c, s_prime = self.c, self.s_prime

        def worker(xs_local):
            m_local = jax.tree.leaves(xs_local)[0].shape[0]
            if m_local % sync_every:
                raise ValueError("sync_every must divide the local chunk size")
            blocks = m_local // sync_every
            xs_blocks = jax.tree.map(
                lambda leaf: leaf.reshape((blocks, sync_every) + leaf.shape[1:]),
                xs_local,
            )

            def sync_block(ls, x_block):
                def one(s, x):
                    s_new = lax.cond(c(x, s), lambda: s_prime(x, s), lambda: s)
                    return s_new, s_new

                ls, trace = lax.scan(one, _pvary(ls, axis), x_block)
                # collector: monotone commit + feedback broadcast in one collective
                ls = self._commit(ls, axis)
                return ls, trace

            s_final, trace = lax.scan(sync_block, s_init, xs_blocks)
            trace = jax.tree.map(
                lambda leaf: leaf.reshape((m_local,) + leaf.shape[2:]), trace
            )
            return trace, s_final

        return shard_map(
            worker, mesh=mesh, in_specs=(P(axis),), out_specs=(P(axis), P()),
        )(xs)

    # -- adaptivity (paper §4.4) ----------------------------------------------
    def new_worker_state(self, s_global):
        """New workers join with the current global value (or a safe s_init —
        paper notes both; we hand them the global value to avoid the
        convergence slowdown)."""
        return s_global


# ---------------------------------------------------------------------------
# §4.5 Separate task/state function
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SeparateTaskState:
    """``y = f(x)`` embarrassingly parallel; ``s = s(y, s)`` serialized.

    On TPU the "mutex section" becomes a collective fold: worker-local ys are
    all-gathered and every shard replays the commit fold identically (cheap by
    the pattern's own premise ``t_s << t_f``), yielding a replicated state —
    bit-identical on every shard, commit order = canonical stream order.

    The speedup bound eq.(1) ``t_f/t_s + 1`` governs this pattern; the
    optimizer substrate shrinks ``t_s`` by sharding the fold (ZeRO) instead of
    replaying it, which is the beyond-paper optimization studied in §Perf.
    """

    f: Callable  # f : alpha -> beta
    s: Callable  # s : beta x gamma -> gamma

    def reference(self, xs, s0):
        return semantics.separate_task_state(self.f, self.s, xs, s0)

    def run(self, mesh: Mesh, axis: str, xs, s0):
        n_w = _axis_size(mesh, axis)
        f, s = self.f, self.s

        def worker(xs_local):
            ys_local = jax.vmap(f)(xs_local)  # parallel part, no state access
            ys_all = jax.tree.map(
                lambda leaf: lax.all_gather(leaf, axis, tiled=True), ys_local
            )

            def commit(st, y):
                st_new = s(y, st)
                return st_new, st_new

            # every shard replays the identical canonical-order fold; the
            # result is re-typed as axis-invariant (it is bit-identical).
            s_final, trace = lax.scan(commit, _pvary(s0, axis), ys_all)
            s_final = _unvary(s_final, axis)
            w = lax.axis_index(axis)
            chunk = jax.tree.leaves(ys_local)[0].shape[0]
            trace_local = jax.tree.map(
                lambda leaf: lax.dynamic_slice_in_dim(leaf, w * chunk, chunk, 0),
                trace,
            )
            return ys_local, trace_local, s_final

        return shard_map(
            worker, mesh=mesh, in_specs=(P(axis),), out_specs=(P(axis), P(axis), P()),
        )(xs)

    @staticmethod
    def speedup_bound(t_f: float, t_s: float) -> float:
        """Paper eq. (1)."""
        return t_f / t_s + 1.0
