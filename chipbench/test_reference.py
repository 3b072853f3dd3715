"""The plain reference agrees with the repository's serial oracle, late
records included, on tumbling and sliding windows."""

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
