#!/usr/bin/env python3
"""Run the keyed state plane end to end on one TPU chip, and check it.

Usage (from the repository root, on a machine with a TPU)::

    python chip_smoke.py

The run drives the main path through its normal entry points:
``StreamExecutor`` -> ``KeyedWindowAdapter(backend="device_table",
fused=True, impl="segment")`` -> ``BatchedWindowTable``, over a seeded keyed
stream (:func:`repro.keyed.synthetic_keyed_items`) with sliding windows and
bounded disorder.  Mid-stream the plane resizes 8 -> 5 shards (5 does not
divide the 64 slots) and back to 8, and takes a checkpoint barrier whose
snapshot is restored before the stream goes on.  Emissions, late records
and the final barrier snapshot must equal the serial oracle
:func:`repro.core.semantics.keyed_windows` bit for bit.

Every line before the last is labelled with the device kind: the sizes, the
per-chunk wall time, the compiles (count and seconds) and the host<->device
bytes of each chunk (both read from the program's tracer spans:
``jax.compile``, and the ``bytes`` of the ``*.ship`` / ``*.wait`` spans),
and the standing and spilled rows at the end.  The last line of standard
output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero without that line when JAX finds no TPU, when the
Pallas kernels would not run compiled (reference or interpret mode), or
when any check fails.  It starts no other process.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

#: the full-size run: 2^21 table rows over 8 shards, more than 2^19 standing
#: (key, window) rows at the end.  Lateness below the disorder makes a few
#: records late at each window boundary, so the late channel is checked too;
#: the final watermark then closes a window that ends exactly at the stream's
#: end, so the stream stops three quarters into a slide, and 2^20 keys keep
#: the four open windows above 2^19 distinct cells (541,364 at this seed).
FULL = dict(
    n_w=8, resize_to=5, num_slots=64, capacity=2**18, max_probes=16,
    size=2**18, slide=2**16, lateness=32, num_keys=2**20, disorder=64,
    chunk=2**14, num_chunks=35, seed=0,
    min_standing=2**19, min_table_rows=2**21,
)

def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _fire_rows(outs, channel, cols):
    parts = [np.stack([o[channel][c] for c in cols], axis=1) for o in outs]
    return np.concatenate(parts) if parts else np.zeros((0, len(cols)), np.int64)


def _n_cells(keys, starts) -> int:
    """Distinct ``(key, window start)`` cells among assignments."""
    if not len(keys):
        return 0
    return len(np.unique(np.stack([keys, starts], axis=1), axis=0))


def _chunk_totals(spans) -> dict:
    """What a chunk's spans say: the programs JAX built (compiled, or
    loaded from the persistent cache) and their seconds, and the bytes
    shipped to the device and brought back."""
    built = [s for s in spans if s.name == "jax.compile"]
    return {
        "compiles": len(built),
        "compile_s": sum(s.t1 - s.t0 for s in built),
        "cache_hits": sum(bool(s.args["cached"]) for s in built),
        "h2d": sum(s.args["bytes"] for s in spans if s.name.endswith(".ship")),
        "d2h": sum(s.args["bytes"] for s in spans if s.name.endswith(".wait")),
    }


def run(cfg: dict, *, log=print) -> dict:
    """Drive the plane over ``cfg``'s stream, print per-chunk lines through
    ``log``, and check every output against the oracle.  Returns a summary;
    raises ``AssertionError`` on the first check that fails."""
    import jax

    from repro.core import semantics
    from repro.keyed import KeyedWindowAdapter, WindowSpec, synthetic_keyed_items
    from repro.keyed.windows import expand_panes
    from repro.obs import Tracer
    from repro.runtime import StreamExecutor

    n_chunks, chunk = cfg["num_chunks"], cfg["chunk"]
    spec = WindowSpec(
        "sliding", size=cfg["size"], slide=cfg["slide"],
        lateness=cfg["lateness"], late_policy="side",
    )
    t0 = time.perf_counter()
    items = synthetic_keyed_items(
        n_chunks * chunk, num_keys=cfg["num_keys"], disorder=cfg["disorder"],
        seed=cfg["seed"],
    )
    log(f"data: {len(items)} items in {n_chunks} chunks of {chunk}, "
        f"made in {time.perf_counter() - t0:.3f}s")
    adapter = KeyedWindowAdapter(
        spec, num_slots=cfg["num_slots"], impl="segment",
        backend="device_table", capacity=cfg["capacity"],
        max_probes=cfg["max_probes"], fused=True,
    )
    tracer = Tracer(recorder=None)
    ex = StreamExecutor(adapter, degree=cfg["n_w"], chunk_size=chunk, tracer=tracer)
    schedule = {n_chunks // 3: cfg["resize_to"], 2 * n_chunks // 3: cfg["n_w"]}
    barrier_at = n_chunks // 2
    outs = []
    totals = {"wall": 0.0, "compiles": 0, "compile_s": 0.0, "cache_hits": 0,
              "h2d": 0, "d2h": 0}
    for i in range(n_chunks):
        if i in schedule:
            t = time.perf_counter()
            rec = ex.set_degree(schedule[i], reason="smoke schedule")
            log(f"resize {rec.n_old}->{rec.n_new} before chunk {i}: "
                f"{rec.handoff_items} slots, {rec.handoff_rows} rows, "
                f"{rec.handoff_bytes} bytes moved in "
                f"{time.perf_counter() - t:.3f}s")
        if i == barrier_at:
            t = time.perf_counter()
            snap = ex.snapshot_barrier()
            ex.state = snap  # restore: the next chunk re-attaches from it
            log(f"checkpoint barrier before chunk {i}: "
                f"{len(snap['w_key'])} rows snapshotted and restored in "
                f"{time.perf_counter() - t:.3f}s")
        part = items[i * chunk:(i + 1) * chunk]
        panes = expand_panes(
            spec, part["key"], part["value"], part["ts"],
            np.arange(len(part), dtype=np.int64),
        )
        tracer.reset()
        t = time.perf_counter()
        out = jax.block_until_ready(ex.process(part))
        wall = time.perf_counter() - t
        got = _chunk_totals(tracer.spans)
        outs.append(out)
        late = out["late"]
        live = len(panes[0]) - len(late["key"])
        # a late assignment's cell is closed, so no live assignment shares it
        cells = _n_cells(panes[0], panes[4]) - _n_cells(late["key"], late["start"])
        rows = ex.degree * cfg["capacity"]
        totals["wall"] += wall
        for k, v in got.items():
            totals[k] += v
        log(f"chunk {i}: shards={ex.degree} table_rows={rows} "
            f"assignments={live} cells={cells} "
            f"emitted={len(out['emissions']['key'])} "
            f"late={len(late['key'])} wall={wall:.6f}s "
            f"compiles={got['compiles']} compile_s={got['compile_s']:.3f} "
            f"h2d_bytes={got['h2d']} d2h_bytes={got['d2h']}")
    state = ex.snapshot_barrier()

    t = time.perf_counter()
    o_em, o_open, o_late = semantics.keyed_windows(
        "sliding",
        zip(items["key"].tolist(), items["value"].tolist(), items["ts"].tolist()),
        **spec.oracle_kwargs(chunk),
    )
    log(f"oracle: {len(o_em)} emissions, {len(o_open)} open windows, "
        f"{len(o_late)} late in {time.perf_counter() - t:.3f}s")

    fire = ("key", "start", "end", "value", "count")
    got_em = _fire_rows(outs, "emissions", fire)
    want_em = np.asarray(o_em, np.int64).reshape(-1, 5)
    _check(np.array_equal(got_em, want_em), "emissions differ from the oracle")
    got_late = _fire_rows(outs, "late", ("key", "value", "ts", "start"))
    want_late = np.asarray(o_late, np.int64).reshape(-1, 4)
    _check(np.array_equal(got_late, want_late), "late records differ from the oracle")
    got_open = np.stack(
        [np.asarray(state[k], np.int64)
         for k in ("w_key", "w_start", "w_end", "w_value", "w_count")], axis=1,
    )
    want_open = np.asarray(o_open, np.int64).reshape(-1, 5)
    _check(np.array_equal(got_open, want_open), "barrier snapshot differs from the oracle")
    _check(int(state["late_count"]) == len(o_late), "late count differs")
    _check(len(got_em) > 0, "no window closed during the run")
    _check(len(got_late) > 0, "no record was late: the late channel went unchecked")
    _check(len(ex.metrics.resizes) >= 2, "the schedule's resizes did not happen")

    standing = int(np.asarray(state["w_resident"], np.int64).sum())
    spill = len(got_open) - standing
    table_rows = ex.degree * cfg["capacity"]
    log(f"oracle check: bit-exact (emissions={len(got_em)}, "
        f"late={len(got_late)}, open={len(got_open)})")
    log(f"state: standing_rows={standing} spill_rows={spill} "
        f"table_rows={table_rows} shards={ex.degree}")
    log(f"totals: chunk_wall_s={totals['wall']:.6f} compiles={totals['compiles']} "
        f"compile_s={totals['compile_s']:.3f} cache_hits={totals['cache_hits']} "
        f"h2d_bytes_per_chunk={totals['h2d'] // n_chunks} "
        f"d2h_bytes_per_chunk={totals['d2h'] // n_chunks}")
    _check(standing >= cfg["min_standing"],
           f"{standing} standing rows, fewer than {cfg['min_standing']}")
    _check(table_rows >= cfg["min_table_rows"],
           f"{table_rows} table rows, fewer than {cfg['min_table_rows']}")
    return dict(
        emissions=len(got_em), late=len(got_late), open=len(got_open),
        standing=standing, spill=spill, table_rows=table_rows, **totals,
    )


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro import compile_cache
    from repro.kernels import ops

    if not ops.compiled_kernels():
        print("chip_smoke: the Pallas kernels would not run compiled",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    label = f"[{dev.device_kind} x{len(devices)}]"

    def log(msg):
        print(f"{label} {msg}", flush=True)

    log(f"compile cache: {cache_dir}")
    log("config: " + " ".join(f"{k}={v}" for k, v in FULL.items()))
    t = time.perf_counter()
    run(FULL, log=log)
    log(f"total wall: {time.perf_counter() - t:.3f}s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
