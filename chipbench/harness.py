"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the per-layer reduction.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is data found by name: ``BENCHMARK.json`` names the cell, its
configuration file and its traffic mix (``chipbench/traffic/<mix>.json``),
and each per-layer metric is read by ``chipbench/metrics/<name>.py`` (or,
for a name with a suffix such as ``.sat``, by the file of the part before
the first dot).  The system under test is driven through its normal entry:
``StreamExecutor.process`` over ``KeyedWindowAdapter(fused=True,
backend="device_table")``.
"""

from __future__ import annotations

import copy
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import bidstream, devtrace, reference
from chipbench import spans as spans_mod

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")
TRACE_DIR = os.path.join(BENCH_DIR, ".out", "trace")


# -- the benchmark's data, by name ----------------------------------------------

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str):
    """``(benchmark, cell, configuration, traffic)`` of the cell ``name``."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that a cell reports."""
    e2e_here = {
        m["name"] for m in bench["end_to_end"]
        if cell_name in m.get("workloads", [cell_name])
    }
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def load_metric(name: str, metrics_dir: str = METRICS_DIR) -> Callable:
    """The ``read`` function of the per-layer metric ``name``: the file
    ``<name>.py``, or else the file of the name's part before its first
    dot, so that one reader can serve ``compiles_in_window.sat`` and
    ``compiles_in_window.rate``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(metrics_dir, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in {metrics_dir}")


def chip_problem(chips: int) -> Optional[str]:
    """Why this process cannot run a cell on ``chips`` TPU chips with the
    Pallas kernels compiled, or None when it can."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return (f"the cell needs {chips} TPU chip(s); JAX finds {len(devices)} "
                f"{devices[0].platform} device(s)")
    if not ops.compiled_kernels():
        return "the Pallas kernels would not run compiled"
    load_peaks(devices[0].device_kind)  # an unknown device kind is an error
    return None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` at the checkout's root (a fixed
    path, so every run of the checkout finds what earlier runs wrote)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return peaks[device_kind]


# -- compile events --------------------------------------------------------------

class CompileCounter:
    """While entered, counts the XLA programs JAX builds (compiled, or
    loaded from the persistent cache), their seconds and names, and the
    persistent cache's hits."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.programs, self.secs, self.hits = 0, 0.0, 0
        #: ``(name, from the cache)`` of each program, in order
        self.built: List[tuple] = []
        self._hit = False

    def _on_duration(self, event, secs, fun_name="", **_):
        if event == self.COMPILE_EVENT:
            self.programs += 1
            self.secs += secs
            self.built.append((fun_name, self._hit))
            self._hit = False

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT_EVENT:
            self.hits += 1
            self._hit = True

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class GcPauses:
    """While entered, the longest pause of Python's garbage collector and
    the count of full (generation 2) collections: a host stall the window
    shows can then be told apart from one in the program's own work."""

    def __init__(self):
        self.longest, self.full, self._t = 0.0, 0, None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.longest = max(self.longest, time.perf_counter() - self._t)
            self.full += info.get("generation") == 2

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)


# -- what a per-layer reader reads ---------------------------------------------

@dataclasses.dataclass
class Window:
    """The measured window as the per-layer readers see it."""

    t0: float                      # perf_counter seconds
    t1: float
    chunks: int                    # chunks completed inside the window
    cells: np.ndarray              # live cells of each of those chunks
    compiles: int                  # programs compiled inside the window
    spans: list                    # the program's tracer spans (traced runs)
    trace: Optional[devtrace.DeviceTrace]
    peaks: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# -- the plane ------------------------------------------------------------------

#: the keys of a configuration's ``window`` group that each kind of window
#: takes, under the names of ``WindowSpec``'s arguments
WINDOW_PARAMS = {
    "tumbling": {"size": "size_ms"},
    "sliding": {"size": "size_ms", "slide": "slide_ms"},
    "session": {"gap": "gap_ms"},
}


def window_spec(window: dict, *, late_policy: str):
    """The program's ``WindowSpec`` of a configuration's ``window`` group."""
    from repro.keyed import WindowSpec

    return WindowSpec(
        window["kind"], lateness=window["lateness_ms"], late_policy=late_policy,
        **{arg: window[key] for arg, key in WINDOW_PARAMS[window["kind"]].items()},
    )


def horizon_ms(window: dict) -> int:
    """The event time after which the first state can fire: a window's
    size, or a session's gap."""
    return window["gap_ms"] if window["kind"] == "session" else window["size_ms"]


def build_plane(config: dict, *, late_policy: str, tracer=None):
    from repro.keyed import KeyedWindowAdapter
    from repro.runtime import StreamExecutor

    p = config["plane"]
    adapter = KeyedWindowAdapter(
        window_spec(config["window"], late_policy=late_policy),
        num_slots=p["num_slots"], impl="segment",
        backend="device_table", capacity=config["capacity"],
        max_probes=p["max_probes"], fused=True,
    )
    return StreamExecutor(
        adapter, degree=p["shards"], chunk_size=p["chunk"], tracer=tracer
    )


def _rows_off(got: np.ndarray, want: np.ndarray) -> int:
    """Rows in which two row arrays differ, a missing or extra row counting
    as one."""
    n = min(len(got), len(want))
    differ = int(np.any(got[:n] != want[:n], axis=1).sum()) if n else 0
    return differ + abs(len(got) - len(want))


def _cols(outs, channel, names) -> np.ndarray:
    parts = [np.stack([np.asarray(o[channel][c], np.int64) for c in names], axis=1)
             for o in outs]
    return np.concatenate(parts) if parts else np.zeros((0, len(names)), np.int64)


def check(outs, snapshot, items, config) -> Dict[str, dict]:
    """Compare what the timed path produced with the plain reference: every
    emission, every late record and the final barrier snapshot."""
    w, chunk = config["window"], config["plane"]["chunk"]
    if w["kind"] == "session":
        em, late, open_state = reference.keyed_sessions(
            items["key"], items["value"], items["ts"], gap=w["gap_ms"],
            lateness=w["lateness_ms"], chunk=chunk,
        )
    else:
        em, late, open_state = reference.keyed_windows(
            items["key"], items["value"], items["ts"], size=w["size_ms"],
            slide=w["slide_ms"] if w["kind"] == "sliding" else w["size_ms"],
            lateness=w["lateness_ms"], chunk=chunk,
        )
    got_open = np.stack(
        [np.asarray(snapshot[k], np.int64)
         for k in ("w_key", "w_start", "w_end", "w_value", "w_count")], axis=1,
    )
    return {
        "emission_rows_off": {
            "value": _rows_off(_cols(outs, "emissions",
                                     ("key", "start", "end", "value", "count")), em),
            "limit": 0,
        },
        "late_rows_off": {
            "value": _rows_off(_cols(outs, "late", ("key", "value", "ts", "start")),
                               late),
            "limit": 0,
        },
        "open_rows_off": {"value": _rows_off(got_open, open_state), "limit": 0},
        "late_count_off": {
            "value": abs(int(snapshot["late_count"]) - len(late)), "limit": 0,
        },
    }


# -- one run ----------------------------------------------------------------------

class _Source:
    """The cell's stream and the plane it feeds: ``process(k)`` sends chunk
    ``k`` through ``StreamExecutor.process`` and keeps the chunk and the
    output for the check.  Chunks generated ahead wait in ``pool``."""

    def __init__(self, stream, ex):
        self.stream, self.ex = stream, ex
        self.pool: Dict[int, np.ndarray] = {}
        self.items: List[np.ndarray] = []
        self.outs: List[dict] = []

    def chunk(self, k: int) -> np.ndarray:
        if k not in self.pool:
            self.pool[k] = self.stream.chunk(k)
        return self.pool[k]

    def process(self, k: int) -> None:
        c = self.pool.pop(k) if k in self.pool else self.stream.chunk(k)
        self.outs.append(self.ex.process(c))
        self.items.append(c)


def _rehearse(src: _Source, k: int, n: int) -> None:
    """Send chunks ``k .. k + n`` through a copy of the plane, whose state
    is the plane's own, so that JAX builds every program those chunks need
    (the program compiles one for each new count of cells) before the
    window opens.  The chunks stay in the pool for the window."""
    # the copy shares the tracer (it holds a lock); its spans end before
    # the window opens, and the readers read only the window's
    shadow = copy.deepcopy(src.ex, {id(src.ex.tracer): src.ex.tracer})
    for j in range(k, k + n):
        shadow.process(src.chunk(j))
    del shadow
    gc.collect()


def _saturate(src: _Source, k: int, seconds: float, t0: float):
    """Closed loop: the next chunk starts when the last returns, until the
    window has lasted ``seconds``; every chunk started is finished and
    counted.  Returns the return times (s after ``t0``)."""
    done = []
    while not done or done[-1] < seconds:
        src.process(k)
        k += 1
        done.append(time.perf_counter() - t0)
    return done


def chunk_cells(items, config) -> np.ndarray:
    """The distinct cells, or session fragments, that each chunk of
    ``items`` sends to the plane."""
    w, chunk = config["window"], config["plane"]["chunk"]
    if w["kind"] == "session":
        _, cells = reference.session_shapes(
            items["key"], items["ts"], gap=w["gap_ms"], lateness=w["lateness_ms"],
            chunk=chunk,
        )
    else:
        _, cells = reference.chunk_shapes(
            items["key"], items["ts"], size=w["size_ms"],
            slide=w["slide_ms"] if w["kind"] == "sliding" else w["size_ms"],
            lateness=w["lateness_ms"], chunk=chunk,
        )
    return cells


def _rows(ex) -> Dict[str, int]:
    """The plane's standing rows on the device tier and in the host spill
    tier, from the program's own health gauges."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    ex.adapter.export_health(reg)
    return {k: int(reg.gauge(f"keyed.plane.{k}_rows").value)
            for k in ("resident", "spill")}


def _require_device_tier(ex, name: str, window: dict) -> None:
    """Raise when the plane holds open rows and none of them on the device
    tier: a cell measures the state on the device, and a plane that keeps it
    all in the host store would run out a run's time instead."""
    rows = _rows(ex)
    if rows["spill"] and not rows["resident"]:
        raise RuntimeError(
            f"configuration {name}: after the first chunk the plane keeps none "
            f"of its open rows of {window['kind']} windows on the device tier "
            f"({rows['resident']} resident rows, {rows['spill']} in the host "
            f"store); nothing is measured")


def warmup_chunks(stream, config: dict, traffic: dict) -> int:
    """Chunks of set-up before the window: the stream runs for
    ``warmup_windows`` horizons of event time (window sizes, or session
    gaps), and then for the lateness and the jitter, so that every window
    that ends by then has fired and the window starts on the standing state
    of a running deployment."""
    w = config["window"]
    fire_ms = (traffic["warmup_windows"] * horizon_ms(w) + w["lateness_ms"]
               + config["jitter_ms"])
    return math.ceil(fire_ms / stream.chunk_ms()) + 1


def apply_overrides(config: dict, traffic: dict, overrides: Optional[dict]) -> None:
    """Update the configuration's keys and groups, and the traffic mix under
    ``"traffic"``, in place."""
    for key, value in (overrides or {}).items():
        if key == "traffic":
            traffic.update(value)
        elif isinstance(value, dict):
            config[key].update(value)
        else:
            config[key] = value


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, log: Callable[[str], None] = print,
             late_policy: Optional[str] = None,
             overrides: Optional[dict] = None,
             on_trace: Optional[Callable[[Window, float, str], None]] = None) -> dict:
    """Run cell ``name`` once and return its result line as a dict.

    ``t_start`` is the ``perf_counter`` reading at the start of the process,
    so that ``setup_s`` covers the imports.  ``late_policy`` replaces the
    configuration's (the control runs the program's ``"drop"`` path), and
    ``overrides`` updates the configuration's keys and groups, and the
    traffic mix under ``"traffic"`` (tests shrink a cell with it).  A traced
    run hands ``on_trace`` the window, the anchor's ``perf_counter`` reading
    and the path of the profile, before the profile is deleted (the
    recorder keeps them)."""
    import jax

    bench, cell, config, traffic = load_cell(name)
    apply_overrides(config, traffic, overrides)
    if traffic["loop"] != "saturate":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    dev = jax.devices()[0]
    stream = bidstream.BidStream.for_config(config, seed)
    chunk = stream.chunk_size
    warm = warmup_chunks(stream, config, traffic)
    policy = late_policy or config["window"]["late_policy"]
    log(f"cell {name}: config {cell['config']}, traffic {cell['traffic']}, "
        f"seed {seed}, {config['tps']} events/s of event time, "
        f"{stream.chunk_ms():.3f} ms of it a chunk, {warm} warm-up chunks of "
        f"{chunk}, late policy {policy}")

    with CompileCounter() as compiles:
        tracer = None
        if trace:
            from repro.obs import Tracer

            tracer = Tracer(recorder=None)
        src = _Source(stream, build_plane(config, late_policy=policy, tracer=tracer))
        took = []
        for k in range(warm):
            t = time.perf_counter()
            src.process(k)
            took.append(time.perf_counter() - t)
            if k == 0:
                _require_device_tier(src.ex, cell["config"], config["window"])
        warm_built = (compiles.programs, compiles.secs)
        # a chunk's time once the shapes repeat gives the chunks the window
        # will take; a copy of the plane runs them, and more, first
        per_chunk = float(np.median(took[-min(20, warm):]))
        ahead = math.ceil(1.3 * seconds / per_chunk) + 2
        _rehearse(src, warm, ahead)
        missed = sorted({n for n, hit in compiles.built if not hit})
        log(f"set-up: {warm} warm-up chunks (median of the last {min(20, warm)}: "
            f"{per_chunk:.6f}s), {warm_built[0]} programs built in "
            f"{warm_built[1]:.3f}s; then {ahead} window chunks rehearsed on a "
            f"copy of the plane, {compiles.programs - warm_built[0]} programs "
            f"built in {compiles.secs - warm_built[1]:.3f}s; in all "
            f"{compiles.hits} from the cache (compiled: {', '.join(missed) or 'none'})")
        rows0 = _rows(src.ex)

        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            anchor = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
                pass
        before = (compiles.programs, compiles.secs, compiles.hits)
        t0 = time.perf_counter()
        with GcPauses() as gc_pauses:
            done = _saturate(src, warm, seconds, t0)
        t1 = t0 + done[-1]
        in_window = [compiles.programs - before[0], compiles.secs - before[1],
                     compiles.hits - before[2]]
        if trace:
            jax.profiler.stop_trace()
    setup_s = t0 - t_start
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    rows1 = _rows(src.ex)
    took = np.diff([0.0] + done)
    slow = int(np.argmax(took))
    log(f"window: {len(done)} chunks ({len(done) - ahead:+d} against the rehearsal), "
        f"{len(done) * chunk} bids in {t1 - t0:.6f}s; median chunk "
        f"{np.median(took):.6f}s, slowest {took[slow]:.6f}s (window chunk {slow}, "
        f"returned at {done[slow]:.3f}s); longest GC pause {gc_pauses.longest:.6f}s, "
        f"{gc_pauses.full} full collections; inside it {in_window[0]} programs built in "
        f"{in_window[1]:.3f}s, {in_window[2]} of them from the cache"
        + (f" ({', '.join(sorted({n for n, _ in compiles.built[before[0]:]}))})"
           if in_window[0] else ""))
    rows = config["capacity"] * config["plane"]["shards"]
    log(f"table: {rows} rows; standing rows on the device tier {rows0['resident']} "
        f"({100 * rows0['resident'] / rows:.1f}%) at the window's start, "
        f"{rows1['resident']} ({100 * rows1['resident'] / rows:.1f}%) at its end; "
        f"in the host spill tier {rows0['spill']} and {rows1['spill']}")

    snapshot = src.ex.snapshot_barrier()
    spans = list(tracer.spans) if tracer else []
    if trace:
        own = spans_mod.self_seconds(spans, t0 + done[slow] - took[slow], t0 + done[slow])
        log("slowest chunk, self time by span: " + ", ".join(
            f"{k} {v:.6f}s" for k, v in sorted(own.items(), key=lambda r: -r[1])))
    src.ex = None  # free the plane before the reference runs
    src.pool.clear()
    items = np.concatenate(src.items)
    t = time.perf_counter()
    checks = check(src.outs, snapshot, items, config)
    log(f"reference: {len(items)} bids checked in {time.perf_counter() - t:.3f}s")

    n_due = len(done) * chunk
    values = {"setup_s": setup_s, "events_per_s": n_due / (t1 - t0)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": int(n_due), "failed": 0}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, name, "end_to_end")
        }
    else:
        xplane = devtrace.find_xplane(TRACE_DIR)
        dtrace = devtrace.load(xplane, anchor)
        cells = chunk_cells(items, config)
        win = Window(
            t0=t0, t1=t1, chunks=len(done), cells=cells[warm:warm + len(done)],
            compiles=in_window[0], spans=spans, trace=dtrace,
            peaks=load_peaks(dev.device_kind),
        )
        if on_trace:
            on_trace(win, anchor, xplane)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        result["metrics"] = {}
        for m in cell_metrics(bench, name, "per_layer"):
            v = load_metric(m["name"])(win)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = devtrace.busy_s(dtrace, t0, t1)
        device["window_s"] = t1 - t0
        ops = devtrace.op_seconds(dtrace, t0, t1)
        gaps = devtrace.label_gaps(devtrace.idle_gaps(dtrace, t0, t1), spans)
        result["breakdown"] = {
            "device_ops": sorted(ops.items(), key=lambda r: -r[1])[:10],
            "idle_gaps": sorted(gaps.items(), key=lambda r: -r[1])[:10],
        }
    result["device"] = device
    result["check"] = checks
    return result
