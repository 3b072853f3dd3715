"""Structured span tracing for the runtime's hot paths.

A :class:`Tracer` records **nested spans** — ``span("chunk") >
span("route") > ...`` — plus instant events (resizes, failures) and counter
samples (queue depth, occupancy), all stamped by a pluggable clock
(:class:`~repro.obs.clock.WallClock` for real runs,
:class:`~repro.obs.clock.LogicalClock` for bit-deterministic simulated
traces).  Finished spans are flat records ``(name, t0, t1, tid, depth,
args)``; nesting is carried by the per-thread depth counter and, in the
Chrome trace-event export (:mod:`repro.obs.export`), by timestamp
containment on the same track — exactly what Perfetto renders as a flame
chart.

Overhead contract
    The **disabled** path is :data:`NULL_TRACER`: ``span()`` returns one
    shared no-op context manager, so an instrumented hot path pays a single
    attribute load + call per stage and allocates nothing — the fused-plane
    benchmark gates this against the un-instrumented PR 5 baselines.  The
    **enabled** path allocates one small object per span and reads the
    clock twice (and, on a wall clock, enters one profiler annotation);
    ``benchmarks/keyed_fused.py`` reports (and CI bounds) the measured
    enabled/disabled ratio.

JAX programs, collector passes and the profiler's clock
    An enabled tracer on a :class:`~repro.obs.clock.WallClock` also enters a
    ``jax.profiler.TraceAnnotation`` for every span, so a profile taken with
    ``jax.profiler.start_trace`` shows the program's spans on its host plane,
    on the device ops' clock.  It records one ``jax.trace`` / ``jax.lower`` /
    ``jax.compile`` span for each program JAX traces, lowers or builds (from
    the ``jax.monitoring`` duration events; ``jax.compile`` carries
    ``cached``: a persistent-cache hit built it), and one ``gc`` span for
    each pass of Python's collector, on the thread and under the span open
    there; nothing is recorded on a thread with no open span.  Tracers are
    held weakly by process-wide listeners, installed once, and JAX's only
    once the process has imported JAX.  A :class:`~repro.obs.clock.
    LogicalClock` tracer gets none of this, so simulated traces stay
    byte-identical.

Event buffers are bounded (``max_events``): a long-running serving process
keeps the newest events and counts the drop, it never grows without limit.
Drops are counted **per kind** (``dropped_spans`` / ``dropped_instants`` /
``dropped_counters``) and :meth:`Tracer.export_drops` publishes them as
registry counters so buffer saturation is visible in the metrics snapshot
instead of silent.

A :class:`FlightRecorder` is the complementary bound: a ring that keeps the
**newest** events (the main buffers keep the oldest), so the moments just
before a failure survive even on a saturated tracer.  The supervisor dumps
it as a Chrome-trace "black box" artifact on worker failure and
checkpoint-restore.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

from repro.obs.clock import WallClock

# The record types are plain __slots__ classes, not dataclasses: a frozen
# dataclass pays ~1.5us of object.__setattr__ per construction, which lands
# INSIDE the parent span (the record is built after t1 is read) and was the
# dominant part of both the enabled-tracer overhead and the stage-coverage
# gap in the fused-plane benchmark.


class SpanRecord:
    """One finished span (``ph:"X"`` complete event in the export)."""

    __slots__ = ("name", "t0", "t1", "tid", "depth", "args")

    def __init__(self, name, t0, t1, tid, depth, args=None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid    # dense per-tracer thread id (0 = first thread seen)
        self.depth = depth  # nesting depth within its thread at entry
        self.args = args

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:
        return (f"SpanRecord(name={self.name!r}, t0={self.t0}, t1={self.t1},"
                f" tid={self.tid}, depth={self.depth}, args={self.args})")


class InstantRecord:
    """A point event (``ph:"i"``): resize, failure, checkpoint, ..."""

    __slots__ = ("name", "t", "tid", "args")

    def __init__(self, name, t, tid, args=None):
        self.name = name
        self.t = t
        self.tid = tid
        self.args = args

    def __repr__(self) -> str:
        return (f"InstantRecord(name={self.name!r}, t={self.t},"
                f" tid={self.tid}, args={self.args})")


class CounterRecord:
    """A counter-track sample (``ph:"C"``) — Perfetto draws these as a
    stacked area series, e.g. queue depth or per-shard occupancy over
    time."""

    __slots__ = ("name", "t", "values")

    def __init__(self, name, t, values):
        self.name = name
        self.t = t
        self.values = values

    def __repr__(self) -> str:
        return (f"CounterRecord(name={self.name!r}, t={self.t},"
                f" values={self.values})")


class _ActiveSpan:
    """Context manager for one live span (enabled tracer only)."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_depth", "_state",
                 "_outer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args):
        self._tracer = tracer
        self._name = name
        self._args = args

    def note(self, **args) -> None:
        """Add args that are known only once the span's work is done."""
        if self._args is None:
            self._args = args
        else:
            self._args.update(args)

    def __enter__(self) -> "_ActiveSpan":
        tr = self._tracer
        ann = None
        if tr._hooked:
            cls = _jax_annotation()
            if cls is not None:
                ann = cls(self._name)
        self._ann = ann
        state = tr._thread_state()
        self._state = state
        self._depth = state[1]
        self._t0 = tr.clock.now()
        self._outer = state[2]
        state[2] = self
        state[1] += 1
        if ann is not None:
            ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tr = self._tracer
        t1 = tr.clock.now()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        state = self._state
        state[1] -= 1
        state[2] = self._outer
        tr._append(
            tr.spans,
            SpanRecord(self._name, self._t0, t1, state[0], self._depth,
                       self._args),
        )
        if tr._pending:
            tr._flush_pending()


class Tracer:
    """Collect spans / instants / counter samples against one clock.

    Thread-safe by construction: each thread gets its own dense ``tid`` and
    depth counter (the executor's pipeline prepare worker shows up as its
    own Perfetto track), and buffer appends hold a lock only long enough to
    append-or-drop.
    """

    enabled = True

    def __init__(self, *, clock=None, max_events: int = 1_000_000,
                 recorder: Optional["FlightRecorder"] = "default"):  # type: ignore[assignment]
        self.clock = clock if clock is not None else WallClock()
        self.max_events = max_events
        self.spans: List[SpanRecord] = []
        self.instants: List[InstantRecord] = []
        self.counters: List[CounterRecord] = []
        self.dropped_spans = 0
        self.dropped_instants = 0
        self.dropped_counters = 0
        # every enabled tracer feeds the process-wide black box by default
        # (pass recorder=None to opt out); the ring keeps NEWEST events, so
        # it still sees what a saturated main buffer drops
        self.recorder = FLIGHT_RECORDER if recorder == "default" else recorder
        #: explicit track labels (``tid -> name``) for tracks reserved via
        #: :meth:`alloc_track`; the Chrome-trace export names them verbatim
        self.track_names: Dict[int, str] = {}
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_tid = 0
        self._n_events = 0
        #: JAX-build and collector spans waiting for the next span's exit
        #: (a collector pass may start inside ``_append``'s lock)
        self._pending: List[SpanRecord] = []
        self._hooked = isinstance(self.clock, WallClock)
        if self._hooked:
            _HOOKED.add(self)
            _install_gc_hook()

    @property
    def dropped(self) -> int:
        """Total events dropped by the bounded buffers (all kinds)."""
        return self.dropped_spans + self.dropped_instants + self.dropped_counters

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **args) -> _ActiveSpan:
        """``with tracer.span("route", cells=n): ...`` — one nested span."""
        return _ActiveSpan(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        self._append(
            self.instants,
            InstantRecord(name, self.clock.now(), self._thread_state()[0],
                          args or None),
        )

    def counter(self, name: str, **values) -> None:
        """Sample one counter track: ``tracer.counter("queue", depth=7)``."""
        self._append(
            self.counters,
            CounterRecord(name, self.clock.now(), values),
        )

    # -- external event sources (cross-process timelines) --------------------
    def alloc_track(self, name: str) -> int:
        """Reserve a dense ``tid`` for an **external** event source — e.g.
        one shard-host process of the distributed keyed plane — so its spans
        render as their own named Perfetto track.  The reserved tid is never
        handed to a local thread (it comes from the same counter
        :meth:`_thread_state` draws from)."""
        with self._lock:
            tid = self._next_tid
            self._next_tid += 1
            self.track_names[tid] = name
        return tid

    def record_span(
        self, name: str, t0: float, t1: float, *, tid: int, depth: int = 0,
        **args,
    ) -> None:
        """Append a span timed by someone else (a worker process stamping
        ``time.perf_counter`` — ``CLOCK_MONOTONIC``, shared across processes
        on the same Linux host, so cross-process spans land on one coherent
        timeline).  Feeds the flight recorder exactly like locally-timed
        spans."""
        self._append(
            self.spans, SpanRecord(name, t0, t1, tid, depth, args or None)
        )

    # -- internals -----------------------------------------------------------
    def _thread_state(self) -> list:
        """``[tid, depth, innermost open span, recent hook spans]`` for the
        calling thread (created on first use)."""
        state = getattr(self._local, "state", None)
        if state is None:
            with self._lock:
                state = [self._next_tid, 0, None, deque(maxlen=4096)]
                self._next_tid += 1
            self._local.state = state
        return state

    def _record_hook(self, name: str, secs: float, args: dict) -> None:
        """One span of ``secs`` that ends now, under the span open on the
        calling thread: a JAX build or a collector pass.  An earlier such
        span that it contains (a trace nested in a trace, a collector pass
        inside a compile) moves one level down, so self times stay exact."""
        state = getattr(self._local, "state", None)
        if state is None or state[2] is None:
            return
        t1 = self.clock.now()
        t0 = max(t1 - secs, state[2]._t0)
        depth = state[1]
        inside = []
        for rec in reversed(state[3]):  # in the order they ended
            if rec.t1 < t0:
                break
            if rec.depth < depth:
                continue
            if rec.t0 < t0:
                # it ended after this one began by the two clocks' jitter
                # alone (one thread runs them in turn): start after it
                t0 = rec.t1
                break
            inside.append(rec)
        for rec in inside:
            if rec.t0 >= t0:
                rec.depth += 1
        rec = SpanRecord(name, t0, t1, state[0], depth, args)
        state[3].append(rec)
        self._pending.append(rec)

    def _flush_pending(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        for rec in pending:
            self._append(self.spans, rec)

    def _append(self, buf: List, rec) -> None:
        recorder = self.recorder
        if recorder is not None:
            # before the drop check: the black box keeps newest events even
            # when the main buffer is saturated
            recorder.push(rec)
        with self._lock:
            if self._n_events >= self.max_events:
                if type(rec) is SpanRecord:
                    self.dropped_spans += 1
                elif type(rec) is InstantRecord:
                    self.dropped_instants += 1
                else:
                    self.dropped_counters += 1
                return
            self._n_events += 1
            buf.append(rec)

    # -- inspection ----------------------------------------------------------
    def reset(self) -> None:
        """Drop buffered events (benchmarks reset after warmup)."""
        with self._lock:
            self._pending = []
            self.spans.clear()
            self.instants.clear()
            self.counters.clear()
            self.dropped_spans = 0
            self.dropped_instants = 0
            self.dropped_counters = 0
            self._n_events = 0

    def export_drops(self, registry) -> None:
        """Publish per-kind drop counts as registry counters
        (``obs.tracer.dropped_spans`` / ``..._instants`` / ``..._counters``),
        so buffer saturation shows up in the metrics snapshot."""
        registry.counter("obs.tracer.dropped_spans").value = self.dropped_spans
        registry.counter("obs.tracer.dropped_instants").value = self.dropped_instants
        registry.counter("obs.tracer.dropped_counters").value = self.dropped_counters

    def total_by_name(self) -> Dict[str, Tuple[int, float]]:
        """``name -> (count, total duration)`` over the buffered spans."""
        out: Dict[str, Tuple[int, float]] = {}
        for s in self.spans:
            n, tot = out.get(s.name, (0, 0.0))
            out[s.name] = (n + 1, tot + s.duration)
        return out


class _NullSpan:
    """Shared no-op context manager: the disabled hot path's whole cost."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def note(self, **args) -> None:
        return None


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every operation is a no-op returning shared
    singletons, so instrumented code pays only a branchless call.  Carries a
    real :class:`~repro.obs.clock.WallClock` so code that reads
    ``tracer.clock`` for its own timing keeps working when tracing is off."""

    enabled = False

    def __init__(self):
        self.clock = WallClock()
        self.spans: List[SpanRecord] = []
        self.instants: List[InstantRecord] = []
        self.counters: List[CounterRecord] = []
        self.track_names: Dict[int, str] = {}
        self.dropped = 0

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        return None

    def alloc_track(self, name: str) -> int:
        return 0

    def record_span(
        self, name: str, t0: float, t1: float, *, tid: int = 0,
        depth: int = 0, **args,
    ) -> None:
        return None

    def counter(self, name: str, **values) -> None:
        return None

    def reset(self) -> None:
        return None

    def total_by_name(self) -> Dict[str, Tuple[int, float]]:
        return {}

    def export_drops(self, registry) -> None:
        return None


#: the process-wide disabled tracer — instrumented modules default to this
NULL_TRACER = NullTracer()


# -- JAX builds, collector passes and profiler annotations -------------------
# The listeners are process-wide (``jax.monitoring`` and ``gc.callbacks``
# are), so one set serves every enabled wall-clock tracer, held weakly.

_HOOKED: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_INSTALL_LOCK = threading.Lock()
#: ``jax.profiler.TraceAnnotation`` once the JAX listeners are installed
_annotation_cls = None
_gc_installed = False
_gc_t0: Optional[float] = None
#: a persistent-cache hit seen since the last backend-compile event
_cache_hit = False

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_jax_duration(event, secs, fun_name="", **_):
    global _cache_hit
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    args = {"fun": fun_name}
    if name == "jax.compile":
        args["cached"] = _cache_hit
        _cache_hit = False
    for tr in list(_HOOKED):
        tr._record_hook(name, secs, args)


def _on_jax_event(event, **_):
    global _cache_hit
    if event == _CACHE_HIT_EVENT:
        _cache_hit = True


def _on_gc(phase, info):
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        secs = time.perf_counter() - _gc_t0
        _gc_t0 = None
        for tr in list(_HOOKED):
            tr._record_hook("gc", secs, {"generation": info["generation"]})


def _install_gc_hook() -> None:
    global _gc_installed
    with _INSTALL_LOCK:
        if not _gc_installed:
            gc.callbacks.append(_on_gc)
            _gc_installed = True


def _jax_annotation():
    """``jax.profiler.TraceAnnotation``, installing the JAX listeners on the
    first call after the process has imported JAX; None before (a process
    that never imports JAX builds no programs to record)."""
    global _annotation_cls
    if _annotation_cls is None and "jax" in sys.modules:
        with _INSTALL_LOCK:
            if _annotation_cls is None:
                import jax

                jax.monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
                jax.monitoring.register_event_listener(_on_jax_event)
                _annotation_cls = jax.profiler.TraceAnnotation
    return _annotation_cls


class FlightRecorder:
    """Bounded ring of the **newest** spans / instants / counter samples,
    plus a short ring of metrics snapshots — the runtime's black box.

    The main tracer buffers keep the *oldest* ``max_events`` events and count
    drops; the recorder inverts that, so the timeline leading *into* a
    failure is always available.  :meth:`dump` writes a Chrome-trace artifact
    (the recorder duck-types the `Tracer` surface `chrome_trace` reads), and
    the supervisor calls it on worker failure and checkpoint-restore.
    """

    def __init__(self, capacity: int = 4096, metrics_capacity: int = 16):
        self.capacity = capacity
        self.spans = deque(maxlen=capacity)
        self.instants = deque(maxlen=capacity)
        self.counters = deque(maxlen=capacity)
        self.metrics_ring = deque(maxlen=metrics_capacity)
        self.dropped = 0   # rings overwrite, they never silently drop

    def push(self, rec) -> None:
        """Called by `Tracer._append` for every event (even dropped ones)."""
        if type(rec) is SpanRecord:
            self.spans.append(rec)
        elif type(rec) is InstantRecord:
            self.instants.append(rec)
        else:
            self.counters.append(rec)

    def sample_metrics(self, registry, t: Optional[float] = None) -> None:
        """Append one registry snapshot to the (short) metrics ring."""
        self.metrics_ring.append({"t": t, "snapshot": registry.snapshot()})

    def reset(self) -> None:
        self.spans.clear()
        self.instants.clear()
        self.counters.clear()
        self.metrics_ring.clear()

    def __len__(self) -> int:
        return len(self.spans) + len(self.instants) + len(self.counters)

    def dump(self, path: str, *, registry=None,
             process_name: str = "blackbox") -> dict:
        """Write the ring as a Chrome-trace JSON "black box" and return the
        document.  ``registry`` adds a final metrics snapshot; the rolling
        :attr:`metrics_ring` rides along under ``otherData``."""
        from repro.obs.export import write_trace

        doc = write_trace(path, self, registry=registry,
                          process_name=process_name,
                          extra={"metrics_ring": list(self.metrics_ring)})
        return doc


#: the process-wide black box every enabled `Tracer` feeds by default
FLIGHT_RECORDER = FlightRecorder()
