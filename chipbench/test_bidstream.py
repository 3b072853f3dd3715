"""The bid generator: deterministic per seed, Beam's hot-key shares, key
ranges and hot-key cadence, keys that collide by chance, and cell counts
that differ from chunk to chunk and from seed to seed."""

import glob
import json
import os

import numpy as np
import pytest

from chipbench import bidstream, harness, reference

BENCH = harness.load_benchmark()
#: every configuration file, also one that no cell of BENCHMARK.json uses yet
CONFIGS = sorted(os.path.basename(f)[:-5]
                 for f in glob.glob(os.path.join(harness.BENCH_DIR, "configs", "*.json")))


def _config(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _stream(cell, seed):
    _, _, config, traffic = harness.load_cell(cell)
    return bidstream.BidStream.for_config(config, seed), config, traffic


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_same_seed_same_stream_and_other_seed_other_keys(cell):
    a, config, _ = _stream(cell, 2**31 + 11)
    b, _, _ = _stream(cell, 2**31 + 11)
    c, _, _ = _stream(cell, 12)
    for k in (0, 7):
        x, y, z = a.chunk(k), b.chunk(k), c.chunk(k)
        assert np.array_equal(x, y)
        assert not np.array_equal(x["key"], z["key"])
        assert not np.array_equal(x["value"], z["value"])
        assert not np.array_equal(x["ts"], z["ts"])
        assert np.abs(x["ts"] - z["ts"]).max() <= 2 * config["jitter_ms"]


@pytest.mark.parametrize("name", CONFIGS)
def test_hot_key_share_and_active_set(name):
    config = _config(name)
    st = bidstream.BidStream.for_config(config, 5)
    n = st.chunk_size
    hot_share = 1 - 1 / st.hot_ratio
    for k in (0, 3, 40):
        ch = st.chunk(k)
        ev = bidstream.bid_event(np.arange(k * n, (k + 1) * n))
        hot = ch["key"] == st.hot_key(ev)
        # each bid is hot with probability 1 - 1/ratio: within 5 sigma
        assert abs(hot.sum() - n * hot_share) < 5 * np.sqrt(n * hot_share * (1 - hot_share))
        lo, span = st.uniform_range(ev[~hot])
        uni = ch["key"][~hot]
        assert np.all(uni >= lo) and np.all(uni < lo + span)
        # a draw over ~100 auctions or ~1000 people repeats keys by chance
        assert len(np.unique(uni)) < len(uni)
        assert np.all((ch["value"] >= 1) & (ch["value"] <= 10**6))
        jit = ch["ts"] - (ev * 1000) // st.tps
        assert np.abs(jit).max() <= config["jitter_ms"]


@pytest.mark.parametrize("field,bids", [("auction", (1518, 1564)), ("bidder", (4600,))])
def test_hot_key_moves_at_beams_cadence(field, bids):
    """The hot auction moves every 100 auctions (3 per 50 events: 33 or 34
    epochs of 46 bids); the hot bidder every 100 people (4,600 bids)."""
    st = bidstream.BidStream(seed=0, chunk_size=1024, tps=10_000, field=field,
                             hot_ratio=2, active=100, jitter_ms=0)
    h = st.hot_key(bidstream.bid_event(np.arange(200_000)))
    moves = np.flatnonzero(np.diff(h)) + 1
    assert set(np.diff(moves).tolist()) == set(bids)
    assert np.all(np.diff(h[moves]) == 100)
    assert np.all(h % 100 == (0 if field == "auction" else 1))


def test_event_numbers_and_id_counts_follow_beams_proportions():
    ev = bidstream.bid_event(np.arange(92))
    assert ev[:46].tolist() == list(range(4, 50))
    assert ev[46:].tolist() == list(range(54, 100))
    # before event 104: 3 people (0, 50, 100) and 6 auctions (1-3, 51-53)
    assert bidstream.last_person(np.array([104]))[0] == 2
    assert bidstream.last_auction(np.array([104]))[0] == 8


@pytest.mark.parametrize("name", CONFIGS)
def test_cell_counts_vary_from_chunk_to_chunk_and_seed_to_seed(name):
    """The stream is not shaped to the program: the count of distinct
    (key, window) cells, or of session fragments, which sets the shapes the
    program compiles, differs between chunks and between seeds."""
    counts = []
    config = _config(name)
    for seed in (3, 2**31 + 9):
        st = bidstream.BidStream.for_config(config, seed)
        items = np.concatenate([st.chunk(k) for k in range(10, 30)])
        cells = harness.chunk_cells(items, config)
        assert len(set(cells.tolist())) > 5
        counts.append(cells)
    assert not np.array_equal(counts[0], counts[1])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_warmup_ends_once_its_windows_have_fired(cell):
    st, config, traffic = _stream(cell, 2**31 + 3)
    warm = harness.warmup_chunks(st, config, traffic)
    ts = np.concatenate([st.chunk(k)["ts"] for k in range(warm)])
    wm = reference.watermarks(ts, st.chunk_size, config["window"]["lateness_ms"])
    until = traffic["warmup_windows"] * harness.horizon_ms(config["window"])
    assert wm[-2] >= until > wm[-int(200 / st.chunk_ms()) - 3]


@pytest.mark.parametrize("name,traffic,params,warm", [
    ("nexmark_q12_tumble", "saturate_midwindow", {"size": 10000}, 679),
    ("nexmark_q5_hop", "saturate", {"size": 10000, "slide": 2000}, 171),
    ("nexmark_q11_session", "saturate", {"gap": 10000}, 454),
])
def test_window_spec_and_warmup_follow_the_window_kind(name, traffic, params, warm):
    """Each kind takes its own parameters from the configuration, and set-up
    runs for its horizon: a window's size, or a session's gap."""
    from repro.keyed import WindowSpec

    config = _config(name)
    with open(os.path.join(harness.BENCH_DIR, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    w = config["window"]
    assert harness.window_spec(w, late_policy="side") == WindowSpec(
        w["kind"], lateness=25, late_policy="side", **params)
    st = bidstream.BidStream.for_config(config, 1)
    assert harness.warmup_chunks(st, config, mix) == warm
