"""The benchmark's bid generator: a NEXmark bid stream, a pure function of
(configuration, seed, chunk index).

It follows the Beam NEXmark generator (``GeneratorConfig``,
``AuctionGenerator``, ``PersonGenerator``, ``BidGenerator``):

* events are numbered ``0, 1, 2, ...`` and every 50 of them are 1 person,
  3 auctions and 46 bids (``personProportion``, ``auctionProportion``,
  ``bidProportion``); the stream holds the bids, each with its event number;
* a bid's auction is the current hot auction with probability
  ``1 - 1/hot_auction_ratio``: the first id of the newest batch of 100
  auctions; otherwise it is drawn uniformly from the newest
  ``num_in_flight_auctions`` auctions and the 10 ids after them
  (``AUCTION_ID_LEAD``);
* a bid's bidder is the current hot bidder with probability
  ``1 - 1/hot_bidders_ratio``: the second id of the newest batch of 100
  people; otherwise it is drawn uniformly from the newest
  ``num_active_people`` people and the 10 ids after them
  (``PERSON_ID_LEAD``);
* so keys repeat and collide by chance, the hot key moves as new ids are
  created (every 100 auctions, 1,533 bids; every 100 people, 4,600 bids),
  and the count of distinct keys differs from chunk to chunk and seed to
  seed, as it does in the source;
* the value is the price in whole dollars, ``round(10 ** (6 u))`` for a
  uniform ``u`` (Beam's price in cents, over 100);
* the event time of event ``n`` is ``n * 1000 / tps`` ms (Beam's
  inter-event delay at a fixed rate), plus a jitter drawn uniformly from
  ``[-jitter_ms, jitter_ms]``, which Beam does not have (each configuration
  lists it under ``assumed``).

The seed draws every random choice: hot or uniform, the uniform key, the
price and the jitter, from a generator seeded by ``(seed, chunk index)``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Beam ``GeneratorConfig``: ids start here, and the share of each event kind
FIRST_AUCTION_ID = 1000
FIRST_PERSON_ID = 1000
PERSON_PROPORTION, AUCTION_PROPORTION, BID_PROPORTION = 1, 3, 46
TOTAL_PROPORTION = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION
#: Beam ``BidGenerator``: the hot key is taken from the newest batch of this many ids
HOT_BATCH = 100
#: Beam ``AuctionGenerator.AUCTION_ID_LEAD`` and ``PersonGenerator.PERSON_ID_LEAD``
ID_LEAD = 10

ITEM_DTYPE = np.dtype([("key", np.int64), ("value", np.int64), ("ts", np.int64)])


def bid_event(i: np.ndarray) -> np.ndarray:
    """Event number of the ``i``-th bid (bids are events 4..49 of every 50)."""
    i = np.asarray(i, np.int64)
    return (TOTAL_PROPORTION * (i // BID_PROPORTION)
            + PERSON_PROPORTION + AUCTION_PROPORTION + i % BID_PROPORTION)


def last_person(n: np.ndarray) -> np.ndarray:
    """Beam ``lastBase0PersonId`` for the bid event ``n``."""
    return (n // TOTAL_PROPORTION) * PERSON_PROPORTION + PERSON_PROPORTION - 1


def last_auction(n: np.ndarray) -> np.ndarray:
    """Beam ``lastBase0AuctionId`` for the bid event ``n``."""
    return (n // TOTAL_PROPORTION) * AUCTION_PROPORTION + AUCTION_PROPORTION - 1


@dataclasses.dataclass(frozen=True)
class BidStream:
    """One cell's stream.  ``chunk(k)`` returns the ``k``-th chunk of
    ``chunk_size`` bids as a record array with ``key``, ``value`` and
    ``ts`` (ms) columns; ``key`` is the auction (``field == "auction"``)
    or the bidder (``field == "bidder"``)."""

    seed: int
    chunk_size: int
    tps: int             # events (of all kinds) per second of event time
    field: str
    hot_ratio: int
    active: int          # in-flight auctions, or active people
    jitter_ms: int

    @classmethod
    def for_config(cls, config: dict, seed: int) -> "BidStream":
        field = config["key"]
        if field not in ("auction", "bidder"):
            raise ValueError(f"key must be 'auction' or 'bidder', got {field!r}")
        auction = field == "auction"
        return cls(
            seed=int(seed), chunk_size=int(config["plane"]["chunk"]),
            tps=int(config["tps"]), field=field,
            hot_ratio=int(config["hot_auction_ratio" if auction else "hot_bidders_ratio"]),
            active=int(config["num_in_flight_auctions" if auction else "num_active_people"]),
            jitter_ms=int(config["jitter_ms"]),
        )

    def chunk_ms(self) -> float:
        """Event time that one chunk spans, in ms."""
        return self.chunk_size * TOTAL_PROPORTION / BID_PROPORTION * 1000.0 / self.tps

    def hot_key(self, n: np.ndarray) -> np.ndarray:
        """The hot key at bid event ``n``."""
        if self.field == "auction":
            return (last_auction(n) // HOT_BATCH) * HOT_BATCH + FIRST_AUCTION_ID
        return (last_person(n) // HOT_BATCH) * HOT_BATCH + 1 + FIRST_PERSON_ID

    def uniform_range(self, n: np.ndarray):
        """``(lo, span)``: bid event ``n`` draws its uniform key from
        ``lo + [0, span)``."""
        if self.field == "auction":
            hi = last_auction(n)
            lo = np.maximum(hi - self.active, 0)
            return lo + FIRST_AUCTION_ID, hi - lo + 1 + ID_LEAD
        people = last_person(n) + 1
        active = np.minimum(people, self.active)
        return people - active + FIRST_PERSON_ID, active + ID_LEAD

    def chunk(self, k: int) -> np.ndarray:
        n_bids = self.chunk_size
        n = bid_event(np.arange(k * n_bids, (k + 1) * n_bids, dtype=np.int64))
        rng = np.random.default_rng([int(self.seed) % 2**63, int(k)])
        hot = rng.integers(0, self.hot_ratio, size=n_bids) > 0
        lo, span = self.uniform_range(n)
        uniform = lo + (rng.random(n_bids) * span).astype(np.int64)
        out = np.empty(n_bids, ITEM_DTYPE)
        out["key"] = np.where(hot, self.hot_key(n), uniform)
        out["value"] = np.maximum(
            np.rint(10.0 ** (6.0 * rng.random(n_bids))), 1
        ).astype(np.int64)
        jitter = rng.integers(-self.jitter_ms, self.jitter_ms + 1, size=n_bids)
        out["ts"] = (n * 1000) // self.tps + jitter
        return out
