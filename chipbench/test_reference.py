"""The plain reference agrees with the repository's serial oracle, late
records included, on tumbling, sliding and session windows."""

import numpy as np
import pytest

from chipbench import bidstream, reference
from repro.core import semantics

#: Beam's auctions at 1,750 events/s: a chunk of 64 bids spans about 40 ms
CONFIG = {"key": "auction", "tps": 1750, "hot_auction_ratio": 2, "hot_bidders_ratio": 4,
          "num_in_flight_auctions": 100, "num_active_people": 1000,
          "jitter_ms": 50, "plane": {"chunk": 64}}


@pytest.mark.parametrize("kind,size,slide", [("sliding", 400, 80), ("tumbling", 400, 400)])
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_matches_serial_oracle(kind, size, slide, seed):
    st = bidstream.BidStream.for_config(CONFIG, seed)
    items = np.concatenate([st.chunk(k) for k in range(40)])
    em, late, open_state = reference.keyed_windows(
        items["key"], items["value"], items["ts"], size=size, slide=slide,
        lateness=25, chunk=64)
    o_em, o_open, o_late = semantics.keyed_windows(
        kind, zip(items["key"].tolist(), items["value"].tolist(), items["ts"].tolist()),
        size=size, slide=slide, gap=0, watermark_every=64, lateness=25,
        late_policy="side", early_every=0)
    assert len(late) > 0 and len(em) > 0 and len(open_state) > 0
    assert np.array_equal(em, np.asarray(o_em, np.int64).reshape(-1, 5))
    assert np.array_equal(late, np.asarray(o_late, np.int64).reshape(-1, 4))
    assert np.array_equal(open_state, np.asarray(o_open, np.int64).reshape(-1, 5))


def test_chunk_shapes_count_live_assignments_and_distinct_cells():
    st = bidstream.BidStream.for_config(CONFIG, 1)
    items = np.concatenate([st.chunk(k) for k in range(12)])
    live, cells = reference.chunk_shapes(
        items["key"], items["ts"], size=400, slide=80, lateness=25, chunk=64)
    wm = reference.watermarks(items["ts"], 64, 25)
    assert live.sum() < 5 * len(items)  # some assignments are late
    for k in range(12):
        part = items[k * 64:(k + 1) * 64]
        _, key, _, _, s = reference.assignments(part["key"], part["value"], part["ts"], 400, 80)
        ok = (s + 400 > wm[k - 1]) if k else np.ones(len(s), bool)
        assert live[k] == ok.sum()
        assert cells[k] == len(set(zip(key[ok].tolist(), s[ok].tolist())))


def _sessions_both_ways(items, *, gap, lateness, chunk):
    got = reference.keyed_sessions(items["key"], items["value"], items["ts"],
                                   gap=gap, lateness=lateness, chunk=chunk)
    o_em, o_open, o_late = semantics.keyed_windows(
        "session", zip(items["key"].tolist(), items["value"].tolist(), items["ts"].tolist()),
        gap=gap, watermark_every=chunk, lateness=lateness, late_policy="side",
        early_every=0)
    want = (np.asarray(o_em, np.int64).reshape(-1, 5), np.asarray(o_late, np.int64).reshape(-1, 4),
            np.asarray(o_open, np.int64).reshape(-1, 5))
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    return got


def _items(rows):
    return np.array(rows, bidstream.ITEM_DTYPE)


#: hand-made streams of (key, value, ts) in chunks of two, gap 10, each with
#: the lateness it runs under and a row its outcome must hold, as
#: ("emission" | "late" | "open", row)
SESSION_CASES = {
    # [0, 10) and [15, 25) in chunk 0, bridged by [8, 18) in chunk 1
    "bridged": ([(1, 1, 0), (1, 2, 15), (1, 4, 8), (2, 1, 3), (3, 1, 200), (3, 1, 205)],
                100, ("emission", (1, 0, 25, 7, 3))),
    # [2, 12) and [20, 30) both overlap [0, 34), though not each other
    "inside_one_session": ([(1, 1, 0), (1, 1, 8), (1, 1, 16), (1, 1, 24), (1, 1, 2),
                            (1, 1, 20), (2, 1, 500), (2, 1, 600)],
                           100, ("emission", (1, 0, 34, 6, 6))),
    # an item exactly gap after a session opens a new one
    "exactly_gap": ([(1, 1, 0), (1, 2, 10), (2, 1, 200), (2, 1, 300)],
                    100, ("emission", (1, 10, 20, 2, 1))),
    # 90 + 10 <= 100, the watermark of chunk 0: late; 95 + 10 > 100: live
    "late": ([(1, 1, 100), (2, 1, 50), (1, 5, 90), (2, 3, 95)],
             0, ("late", (1, 5, 90, 90))),
    # [0, 10) fires at the watermark 15; [7, 17) would have overlapped it
    "after_a_fire": ([(1, 1, 0), (2, 1, 15), (1, 2, 7), (2, 1, 16)],
                     0, ("open", (1, 7, 17, 2, 1))),
}


@pytest.mark.parametrize("case", [*SESSION_CASES, 0, 2**31 + 5, 7])
def test_sessions_match_serial_oracle(case):
    if isinstance(case, int):  # a seed of the jittered bid stream
        st = bidstream.BidStream.for_config(CONFIG, case)
        items = np.concatenate([st.chunk(k) for k in range(40)])
        em, late, open_state = _sessions_both_ways(items, gap=40, lateness=25, chunk=64)
        assert len(late) > 0 and len(em) > 0 and len(open_state) > 0
        assert em[:, 4].max() > 1  # sessions merge many bids
        return
    rows, lateness, (channel, row) = SESSION_CASES[case]
    em, late, open_state = _sessions_both_ways(_items(rows), gap=10, lateness=lateness,
                                               chunk=2)
    got = {"emission": em, "late": late, "open": open_state}[channel]
    assert tuple(row) in set(map(tuple, got.tolist()))


def test_session_shapes_count_live_items_and_gap_chain_fragments():
    # gap 10, chunks of four.  Chunk 0: bidder 1 at 0, 5 (5 apart: one
    # fragment) and 15 (10 after 5: a second), bidder 2 at 3: 4 live, 3
    # fragments; the watermark is 15.  Chunk 1: 4 + 10 <= 15 is late;
    # bidder 3 at 20, 29, 29: 3 live, 1 fragment.  Chunk 2: bidder 3 at 40,
    # bidder 4 at 41, 42 and 52 (10 after 42: a second fragment): 4 live, 3.
    items = _items([(1, 1, 0), (1, 1, 5), (1, 1, 15), (2, 1, 3),
                    (1, 1, 4), (3, 1, 20), (3, 1, 29), (3, 1, 29),
                    (3, 1, 40), (4, 1, 41), (4, 1, 42), (4, 1, 52)])
    live, frags = reference.session_shapes(items["key"], items["ts"], gap=10, lateness=0,
                                           chunk=4)
    assert live.tolist() == [4, 3, 4] and frags.tolist() == [3, 1, 3]
