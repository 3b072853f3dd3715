"""Tests for the keyed windowed-state subsystem (`repro.keyed`).

The acceptance contract: keyed windowed outputs, late records, and the final
store state are **bit-exact** against the serial oracle
(:func:`repro.core.semantics.keyed_windows`) across mid-stream grow and
shrink for all three window kinds, at worker counts that do NOT divide
``num_slots``, on both the sort+segment-reduce hot path and the masked-scan
baseline.  Plus: slot-map invariants, Pallas kernel vs reference, the
autoscaler's feasibility clamp, and the supervisor's checkpoint-replay over
the keyed store.
"""

import shutil

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import semantics
from repro.keyed import (
    KeyedStore,
    KeyedWindowAdapter,
    KeyedWindowEngine,
    SlotMap,
    WindowSpec,
    hash_to_slot,
    plan_relocation,
    reduce_by_cell,
    synthetic_keyed_items,
)
from repro.runtime import (
    Autoscaler,
    BackpressureQueue,
    BoundedSource,
    Chunker,
    ConstantRate,
    FailurePlan,
    QueueDepthPolicy,
    StreamExecutor,
    Supervisor,
    pump,
)

NUM_SLOTS = 20  # degrees 3, 6, 7 do not divide this


def _triples(items):
    return [(int(r["key"]), int(r["value"]), int(r["ts"])) for r in items]


def _emissions(outs):
    return [
        tuple(int(x) for x in row)
        for o in outs
        for row in zip(
            *(o["emissions"][k] for k in ("key", "start", "end", "value",
                                          "count"))
        )
    ]


def _state_rows(state):
    return [
        tuple(int(x) for x in r)
        for r in zip(
            *(np.asarray(state[k]).tolist()
              for k in ("w_key", "w_start", "w_end", "w_value", "w_count"))
        )
    ]


def _spec_for(kind):
    if kind == "tumbling":
        return WindowSpec("tumbling", size=7, lateness=3, late_policy="side")
    if kind == "sliding":
        return WindowSpec("sliding", size=9, slide=4, lateness=3,
                          late_policy="side")
    return WindowSpec("session", gap=5, lateness=3, late_policy="side")


# ---------------------------------------------------------------------------
# slot map
# ---------------------------------------------------------------------------

class TestSlotMap:
    def test_default_table_reduces_to_block_on_divisors(self):
        sm = SlotMap(16, 4)
        np.testing.assert_array_equal(sm.table, np.arange(16) // 4)
        assert sm.counts().tolist() == [4, 4, 4, 4]

    def test_any_worker_count_is_valid_and_balanced(self):
        for n in range(1, NUM_SLOTS + 1):
            c = SlotMap(NUM_SLOTS, n).counts()
            assert c.sum() == NUM_SLOTS
            assert c.max() - c.min() <= 1

    def test_rebalance_is_minimal_and_balanced(self):
        sm = SlotMap(NUM_SLOTS, 6)
        sm2, moved = sm.rebalance(7)
        c = sm2.counts()
        assert c.max() - c.min() <= 1 and c.sum() == NUM_SLOTS
        np.testing.assert_array_equal(
            moved, np.flatnonzero(sm.table != sm2.table)
        )
        # keeping every surviving worker at/below quota means the moved set
        # cannot be smaller: only over-quota/departed slots moved
        again, moved_again = sm2.rebalance(7)
        assert len(moved_again) == 0

    @settings(max_examples=30)
    @given(st.integers(1, NUM_SLOTS), st.integers(1, NUM_SLOTS))
    def test_rebalance_chain_invariants(self, n_a, n_b):
        sm = SlotMap(NUM_SLOTS, n_a)
        sm2, moved = sm.rebalance(n_b)
        assert sm2.n_workers == n_b
        c = sm2.counts()
        assert c.max() - c.min() <= 1
        assert len(moved) == int(np.sum(sm.table != sm2.table))
        # a worker surviving the resize never receives its own slot back
        for s in moved:
            assert sm.table[s] != sm2.table[s]

    def test_handoff_volume_matches_rebalance(self):
        sm = SlotMap(NUM_SLOTS, 4)
        assert sm.handoff_volume(5) == len(sm.rebalance(5)[1])

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            SlotMap(8, 9)
        with pytest.raises(ValueError):
            SlotMap(8, 0)
        with pytest.raises(ValueError):
            SlotMap(8, 2).rebalance(9)


class TestStoreAndRelocation:
    def test_store_pytree_roundtrip_canonical(self):
        store = KeyedStore(NUM_SLOTS, 3)
        from repro.keyed import WindowState

        store.windows_of(5).append(WindowState(0, 7, 10, 2))
        store.windows_of(45).append(WindowState(7, 14, 3, 1))
        t = store.to_pytree()
        store2 = KeyedStore.from_pytree(t)
        t2 = store2.to_pytree()
        for k in t:
            np.testing.assert_array_equal(t[k], t2[k])
        assert store2.n_workers == 3

    def test_from_pytree_is_order_canonical(self):
        """Regression: serialization used to trust the array order, so a
        permuted (but logically identical) pytree rebuilt a store whose
        per-slot dict insertion order and per-key window-list order differed
        from a natively-built one.  from_pytree must canonicalize: any row
        permutation rebuilds the identical in-memory store."""
        from repro.keyed import WindowState

        store = KeyedStore(NUM_SLOTS, 3)
        # adversarial insertion: keys and window starts in decreasing order
        for key in (45, 5, 25, -7):
            for start in (21, 7, 0):
                store.windows_of(key).append(
                    WindowState(start, start + 7, key + start, 1)
                )
        t = store.to_pytree()
        rng = np.random.default_rng(0)
        perm = rng.permutation(len(t["w_key"]))
        shuffled = dict(
            t, **{k: t[k][perm]
                  for k in ("w_key", "w_start", "w_end", "w_value", "w_count")}
        )
        store2 = KeyedStore.from_pytree(shuffled)
        t2 = store2.to_pytree()
        for k in t:
            np.testing.assert_array_equal(t[k], t2[k], err_msg=k)
        # in-memory canonical form, not just canonical serialization:
        for slot_dict in store2.slots:
            assert list(slot_dict) == sorted(slot_dict)
            for wins in slot_dict.values():
                starts = [w.start for w in wins]
                assert starts == sorted(starts)

    def test_negative_keys_hash_consistently(self):
        """Scalar and array hashing must agree on negative keys (int64 keys
        are signed; a bare uint64 cast crashes on scalars but wraps on
        arrays) — and the engine must route them end to end."""
        for key in (-5, -1, 0, 7, -(2 ** 40)):
            scalar = int(hash_to_slot(key, NUM_SLOTS))
            arr = int(hash_to_slot(np.array([key], np.int64), NUM_SLOTS)[0])
            assert scalar == arr and 0 <= scalar < NUM_SLOTS
        from repro.keyed import keyed_stream

        items = keyed_stream(
            np.array([-3, 5, -3, -3, 5, -7], np.int64),
            np.arange(6, dtype=np.int64),
            np.arange(6, dtype=np.int64),
        )
        spec = WindowSpec("tumbling", size=4)
        eng = KeyedWindowEngine(spec, num_slots=NUM_SLOTS)
        out = eng.process_chunk(items)
        o_em, o_open, _ = semantics.keyed_windows(
            "tumbling", [(int(r["key"]), int(r["value"]), int(r["ts"]))
                         for r in items],
            size=4, watermark_every=6,
        )
        got = [tuple(int(x) for x in row)
               for row in zip(*(out["emissions"][k]
                                for k in ("key", "start", "end", "value",
                                          "count")))]
        assert got == o_em
        assert _state_rows(eng.snapshot()) == [tuple(t) for t in o_open]

    def test_plan_relocation_hash_collision_requeues(self):
        sessions = {0: 10, 1: 11, 2: 12}
        placements, requeued = plan_relocation(sessions, 2, policy="hash")
        assert len(placements) + len(requeued) == 3
        # every placement goes to the re-hashed slot
        for old, new in placements.items():
            assert new == int(hash_to_slot(sessions[old], 2))

    def test_plan_relocation_ondemand_keeps_and_compacts(self):
        placements, requeued = plan_relocation(
            {0: 5, 3: 6, 7: 7}, 4, policy="ondemand"
        )
        assert placements[0] == 0 and placements[3] == 3
        assert placements[7] in (1, 2) and not requeued


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

class TestKernels:
    def _case(self, seed, rows, cells):
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, cells, size=rows).astype(np.int32)
        vals = rng.integers(0, 100, size=(rows, 2)).astype(np.int32)
        return ids, vals

    def test_segment_and_masked_paths_agree(self):
        ids, vals = self._case(0, 57, 11)
        a = np.asarray(reduce_by_cell(ids, vals, 11, impl="segment"))
        b = np.asarray(reduce_by_cell(ids, vals, 11, impl="masked"))
        ref = np.zeros((11, 2), np.int64)
        np.add.at(ref, ids, vals)
        np.testing.assert_array_equal(a, ref)
        np.testing.assert_array_equal(b, ref)

    def test_pallas_interpret_matches_ref(self):
        import jax.numpy as jnp

        from repro.kernels import ref as kref
        from repro.kernels import segment_reduce as sr

        ids, vals = self._case(1, 37, 9)
        ids = np.sort(ids)
        got = sr.segment_sum(
            jnp.asarray(vals), jnp.asarray(ids), 9, interpret=True,
            block_rows=8,
        )
        want = kref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(ids), 9)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        rng = np.random.default_rng(2)
        table = rng.integers(0, 10, size=(6, 3)).astype(np.int32)
        tid = rng.integers(0, 6, size=17).astype(np.int32)
        rows = rng.integers(0, 5, size=(17, 3)).astype(np.int32)
        got = sr.scatter_add(
            jnp.asarray(table), jnp.asarray(tid), jnp.asarray(rows),
            interpret=True, block_rows=4,
        )
        want = kref.scatter_add_ref(
            jnp.asarray(table), jnp.asarray(tid), jnp.asarray(rows)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_ops_segment_sum_is_order_blind_and_sorted_path_matches(self):
        """ops.segment_sum must give ref-equal sums for UNSORTED ids on
        every dispatch path; the sorted-precondition fast path
        (segment_sum_sorted, the keyed device path) must agree once ids
        are sorted."""
        import jax.numpy as jnp

        from repro.kernels import ops
        from repro.kernels import ref as kref
        from repro.kernels import segment_reduce as sr

        ids, vals = self._case(3, 41, 7)  # deliberately unsorted
        want = np.asarray(
            kref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(ids), 7)
        )
        got = np.asarray(ops.segment_sum(jnp.asarray(vals),
                                         jnp.asarray(ids), 7))
        np.testing.assert_array_equal(got, want)
        order = np.argsort(ids, kind="stable")
        got_sorted = np.asarray(
            sr.segment_sum_sorted(
                jnp.asarray(vals[order]), jnp.asarray(ids[order]), 7
            )
        )
        np.testing.assert_array_equal(got_sorted, want)

    def test_empty_and_bad_impl(self):
        out = np.asarray(
            reduce_by_cell(np.zeros(0, np.int32), np.zeros((0, 2), np.int32),
                           4)
        )
        np.testing.assert_array_equal(out, np.zeros((4, 2)))
        with pytest.raises(ValueError, match="impl"):
            reduce_by_cell(np.zeros(1, np.int32), np.zeros((1, 2), np.int32),
                           1, impl="nope")


# ---------------------------------------------------------------------------
# windows vs the serial oracle (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestWindowsBitExact:
    CHUNK = 16

    def _run_executor(self, spec, items, schedule, impl, degree=2):
        ad = KeyedWindowAdapter(spec, num_slots=NUM_SLOTS, impl=impl)
        ex = StreamExecutor(ad, degree=degree, chunk_size=self.CHUNK)
        chunks = [
            items[i: i + self.CHUNK] for i in range(0, len(items), self.CHUNK)
        ]
        outs = ex.run(chunks, schedule=schedule)
        return ex, outs

    @pytest.mark.parametrize("kind", ["tumbling", "sliding", "session"])
    @pytest.mark.parametrize("impl", ["segment", "masked"])
    def test_grow_shrink_nondivisible_degrees_bit_exact(self, kind, impl):
        """Mid-stream grow (2->3->7) and shrink (7->2) at degrees that do
        NOT divide num_slots=20, bit-exact vs the serial fold."""
        spec = _spec_for(kind)
        items = synthetic_keyed_items(
            11 * self.CHUNK + 9, num_keys=9, disorder=6, seed=13
        )
        ex, outs = self._run_executor(
            spec, items, {2: 3, 5: 7, 8: 2}, impl
        )
        o_em, o_open, o_late = semantics.keyed_windows(
            kind, _triples(items), **spec.oracle_kwargs(self.CHUNK)
        )
        assert _emissions(outs) == o_em
        assert _state_rows(ex.state) == [tuple(t) for t in o_open]
        late_rows = [
            tuple(int(x) for x in row)
            for o in outs
            for row in zip(*(o["late"][k]
                             for k in ("key", "value", "ts", "start")))
        ]
        assert late_rows == o_late
        assert int(ex.state["late_count"]) == len(o_late)
        assert all(
            r.protocol == "S2-slotmap-handoff" for r in ex.metrics.resizes
        )

    @settings(max_examples=6, deadline=None)
    @given(
        st.sampled_from(["tumbling", "sliding", "session"]),
        st.integers(0, 10_000),
        st.integers(0, 10),
        st.sampled_from([(2, 5), (3, 7), (6, 4)]),
    )
    def test_property_random_streams_and_resizes(
        self, kind, seed, disorder, degrees
    ):
        """Property: random keyed streams with bounded disorder, random
        grow/shrink between non-divisor degrees, both hot paths agree with
        the oracle on emissions, late records, and final state."""
        spec = _spec_for(kind)
        items = synthetic_keyed_items(
            8 * self.CHUNK + 5, num_keys=7, disorder=disorder, seed=seed
        )
        d0, d1 = degrees
        o_em, o_open, o_late = semantics.keyed_windows(
            kind, _triples(items), **spec.oracle_kwargs(self.CHUNK)
        )
        for impl in ("segment", "masked"):
            ex, outs = self._run_executor(
                spec, items, {3: d1, 6: d0}, impl, degree=d0
            )
            assert _emissions(outs) == o_em
            assert _state_rows(ex.state) == [tuple(t) for t in o_open]

    def test_late_policy_drop_suppresses_side_output(self):
        spec = WindowSpec("tumbling", size=7, lateness=0, late_policy="drop")
        items = synthetic_keyed_items(64, num_keys=5, disorder=9, seed=5)
        ex, outs = self._run_executor(spec, items, None, "segment")
        assert all(len(o["late"]["key"]) == 0 for o in outs)
        # ...but the oracle-visible accounting is still kept in state
        o_em, _, o_late = semantics.keyed_windows(
            "tumbling", _triples(items), size=7,
            watermark_every=self.CHUNK, lateness=0, late_policy="drop",
        )
        assert len(o_late) > 0  # the stream really had late items
        assert int(ex.state["late_count"]) == len(o_late)
        assert _emissions(outs) == o_em

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            WindowSpec("tumbling", size=0)
        with pytest.raises(ValueError):
            WindowSpec("sliding", size=8, slide=9)
        with pytest.raises(ValueError):
            WindowSpec("session", gap=0)
        with pytest.raises(ValueError):
            WindowSpec("hopping", size=4)
        with pytest.raises(ValueError):
            WindowSpec("tumbling", size=4, late_policy="retract")


# ---------------------------------------------------------------------------
# runtime: autoscaler clamp + live stream + supervisor/checkpoint coverage
# ---------------------------------------------------------------------------

class TestKeyedRuntime:
    def test_autoscaler_clamps_to_feasible_degrees(self):
        """Block ownership (16 slots): policy pressure toward an infeasible
        rung (3) must be clamped to the divisor ladder instead of raising
        in the executor (the pre-fix failure mode).  Uses the pattern's
        feasible_degrees hook through a stub executor (the real SPMD resize
        path is covered in tests/runtime_checks.py)."""
        import jax.numpy as jnp

        from repro.core import patterns
        from repro.runtime import MetricsBus, PartitionedAdapter

        pat = patterns.PartitionedState(
            f=lambda x, s: x + s,
            ns=lambda x, s: s + x,
            h=lambda x: (x.astype(jnp.int32) * 7) % 16,
            num_slots=16,
        )
        assert pat.feasible_degrees(6) == [1, 2, 4]
        ad = PartitionedAdapter(pat, jnp.zeros((16,), jnp.int32))
        assert ad.feasible_degrees(12, [1, 2, 3, 4, 6, 12]) == [1, 2, 4]

        class _StubExecutor:
            degree = 2
            chunk_size = 12
            chunks_done = 0
            metrics = MetricsBus()
            adapter = ad
            resized_to = None

            def feasible_degrees(self, candidates):
                return self.adapter.feasible_degrees(self.chunk_size,
                                                     candidates)

            def set_degree(self, n, reason=""):
                self.resized_to = self.degree = n
                return None

        class _Q:
            depth, high_watermark, low_watermark = 99, 8, 1

        ex = _StubExecutor()
        sc = Autoscaler(QueueDepthPolicy(), [1, 2, 3, 4], cooldown_chunks=0)
        d = sc.maybe_scale(ex, queue=_Q())
        assert d is not None and d.proposed == 4  # 3 skipped: not feasible
        assert ex.resized_to == 4
        # slotmap ownership makes every degree feasible — the clamp is a noop
        pat_sm = patterns.PartitionedState(
            f=pat.f, ns=pat.ns, h=pat.h, num_slots=16, ownership="slotmap"
        )
        assert pat_sm.feasible_degrees(6) == [1, 2, 3, 4, 5, 6]

    def test_keyed_adapter_feasible_degrees_are_all(self):
        ad = KeyedWindowAdapter(
            WindowSpec("tumbling", size=4), num_slots=NUM_SLOTS
        )
        ex = StreamExecutor(ad, degree=1, chunk_size=16)
        assert ex.feasible_degrees([1, 2, 3, 6, 7]) == [1, 2, 3, 6, 7]

    def test_live_stream_queue_autoscaler_bit_exact(self):
        """Source -> backpressure queue -> chunker -> executor with the
        queue-depth autoscaler resizing mid-stream: still oracle-exact."""
        spec = WindowSpec("tumbling", size=6, lateness=4, late_policy="side")
        CH = 16
        items = synthetic_keyed_items(12 * CH, num_keys=8, disorder=4, seed=11)
        ad = KeyedWindowAdapter(spec, num_slots=NUM_SLOTS, impl="segment")
        ex = StreamExecutor(ad, degree=2, chunk_size=CH)
        scaler = Autoscaler(
            QueueDepthPolicy(), candidates=[2, 3, 7], cooldown_chunks=1
        )
        src = BoundedSource(items)
        q = BackpressureQueue(capacity=6 * CH, high_watermark=3 * CH,
                              low_watermark=CH // 2)
        chunker = Chunker(CH)
        outs, pend, t = [], None, 0
        while not (src.exhausted and q.depth == 0):
            pend = pump(src, ConstantRate(3 * CH), q, t, pending=pend)
            q.observe()
            while chunker.ready(q):
                scaler.maybe_scale(ex, queue=q)
                outs.append(ex.process(chunker.next_chunk(q)))
            t += 1
        o_em, o_open, _ = semantics.keyed_windows(
            "tumbling", _triples(items), **spec.oracle_kwargs(CH)
        )
        assert _emissions(outs) == o_em
        assert _state_rows(ex.state) == [tuple(t) for t in o_open]
        assert ex.metrics.resizes, "backlog never triggered a resize"
        # the ladder's non-divisor rungs (3, 7) must actually be reachable
        assert any(r.n_new in (3, 7) for r in ex.metrics.resizes)

    def test_supervisor_checkpoint_replay_covers_keyed_store(self, tmp_path):
        """Failure -> rollback to checkpoint -> BoundedSource.seek replay:
        the keyed store round-trips through repro.checkpoint and the
        replayed run is bit-exact vs the oracle."""
        spec = WindowSpec("session", gap=6, lateness=5, late_policy="side")
        CH, NCH = 16, 6
        items = synthetic_keyed_items(CH * NCH, num_keys=7, disorder=5,
                                      seed=3)
        src = BoundedSource(items)

        def chunk_fn(i):
            src.seek(i * CH)
            return src.take(CH)

        ad = KeyedWindowAdapter(spec, num_slots=10, impl="segment")
        ex = StreamExecutor(ad, degree=3, chunk_size=CH)
        sup = Supervisor(
            ex, chunk_fn, num_chunks=NCH, ckpt_dir=str(tmp_path),
            ckpt_every=2, failure_plan=FailurePlan(fail_at=3, recover_after=2),
        )
        outs = sup.run()
        o_em, o_open, _ = semantics.keyed_windows(
            "session", _triples(items), **spec.oracle_kwargs(CH)
        )
        assert _emissions([outs[i] for i in range(NCH)]) == o_em
        assert _state_rows(ex.state) == [tuple(t) for t in o_open]
        kinds = [e.kind for e in sup.events]
        assert "failure" in kinds and "shrink" in kinds and "grow" in kinds
