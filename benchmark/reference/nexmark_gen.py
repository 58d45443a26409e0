"""The benchmark's own copy of the Nexmark generator, numeric part.

Copied from `risingwave_tpu/connectors/nexmark.py` as it stood at PR 24
(events are a pure function of the event index: splitmix64 over the
index), cut to the columns the references read. It imports nothing of
the program, so a later change to the connector that alters the data
makes `correct` come out false instead of moving the yardstick with it.
`selfcheck/test_generator_copy.py` holds this copy against the connector.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
PROPORTION_DENOMINATOR = 50
PROPORTION = {"person": PERSON_PROPORTION, "auction": AUCTION_PROPORTION,
              "bid": BID_PROPORTION}

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
BASE_TIME_MS = 1_436_918_400_000     # 2015-07-15 00:00:00 UTC


@dataclass
class GeneratorConfig:
    """The numeric knobs of the connector's NexmarkConfig, at its defaults;
    a configuration file's `generator` object overrides them by name."""

    seed: int = 0x5EED0
    min_event_gap_in_ns: int = 100_000
    active_people: int = 1000
    in_flight_auctions: int = 100
    hot_seller_ratio: int = 4
    hot_auction_ratio: int = 2
    hot_bidder_ratio: int = 4


_SM_GAMMA = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (x + _SM_GAMMA) * np.uint64(1)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def _rng_u64(idx: np.ndarray, stream: int, seed: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = idx.astype(np.uint64) * np.uint64(PROPORTION_DENOMINATOR + 7) \
            + np.uint64(stream) + (np.uint64(seed) << np.uint64(20))
    return _splitmix64(x)


def _uniform(idx: np.ndarray, stream: int, seed: int) -> np.ndarray:
    return (_rng_u64(idx, stream, seed) >> np.uint64(11)).astype(
        np.float64) / float(1 << 53)


def _epoch_offset(event_idx):
    return (event_idx // PROPORTION_DENOMINATOR,
            event_idx % PROPORTION_DENOMINATOR)


def _max_person_base0(event_idx):
    ep, off = _epoch_offset(event_idx)
    return ep * PERSON_PROPORTION + np.minimum(off, PERSON_PROPORTION - 1)


def _max_auction_base0(event_idx):
    ep, off = _epoch_offset(event_idx)
    return (ep * AUCTION_PROPORTION
            + np.clip(off - PERSON_PROPORTION, 0, AUCTION_PROPORTION - 1))


def _event_timestamp_us(event_idx, cfg: GeneratorConfig):
    ns = event_idx.astype(np.int64) * np.int64(cfg.min_event_gap_in_ns)
    return np.int64(BASE_TIME_MS) * 1000 + ns // 1000


def person_event_index(k):
    return (k // PERSON_PROPORTION) * PROPORTION_DENOMINATOR \
        + k % PERSON_PROPORTION


def auction_event_index(k):
    return (k // AUCTION_PROPORTION) * PROPORTION_DENOMINATOR \
        + PERSON_PROPORTION + k % AUCTION_PROPORTION


def bid_event_index(k):
    return (k // BID_PROPORTION) * PROPORTION_DENOMINATOR \
        + PERSON_PROPORTION + AUCTION_PROPORTION + k % BID_PROPORTION


def _price(idx, stream: int, seed: int):
    return np.maximum(
        1, (np.power(10.0, _uniform(idx, stream, seed) * 6.0) * 100.0)
    ).astype(np.int64)


def _recent_person(idx, hot_stream: int, cold_stream: int, hot_ratio: int,
                   cfg: GeneratorConfig):
    """Hot person with probability 1 - 1/ratio, else uniform over the
    last `active_people` (sellers and bidders share the rule)."""
    s = cfg.seed
    max_person = _max_person_base0(idx)
    hot = _uniform(idx, hot_stream, s) < 1.0 - 1.0 / max(hot_ratio, 1)
    hot_id = (max_person // cfg.active_people) * cfg.active_people + 1
    window = np.minimum(max_person + 1, cfg.active_people)
    cold_id = max_person - (
        _rng_u64(idx, cold_stream, s) % window.astype(np.uint64)
    ).astype(np.int64)
    return np.where(hot, np.minimum(hot_id, max_person),
                    cold_id) + FIRST_PERSON_ID


def gen_bids(k: np.ndarray, cfg: GeneratorConfig) -> Dict[str, np.ndarray]:
    """auction, bidder, price, date_time of the bids with ordinals `k`."""
    idx = bid_event_index(k)
    s = cfg.seed
    max_auction = _max_auction_base0(idx)
    hot_a = _uniform(idx, 1, s) < 1.0 - 1.0 / max(cfg.hot_auction_ratio, 1)
    hot_auction = (max_auction // cfg.in_flight_auctions) \
        * cfg.in_flight_auctions
    window_a = np.minimum(max_auction + 1, cfg.in_flight_auctions)
    cold_auction = max_auction - (
        _rng_u64(idx, 2, s) % window_a.astype(np.uint64)).astype(np.int64)
    return {
        "auction": np.where(hot_a, hot_auction, cold_auction)
        + FIRST_AUCTION_ID,
        "bidder": _recent_person(idx, 3, 4, cfg.hot_bidder_ratio, cfg),
        "price": _price(idx, 5, s),
        "date_time": _event_timestamp_us(idx, cfg),
    }


def gen_auctions(k: np.ndarray,
                 cfg: GeneratorConfig) -> Dict[str, np.ndarray]:
    """id, seller, initial_bid, reserve, date_time of the auctions `k`."""
    idx = auction_event_index(k)
    s = cfg.seed
    initial_bid = _price(idx, 13, s)
    return {
        "id": k + FIRST_AUCTION_ID,
        "seller": _recent_person(idx, 11, 12, cfg.hot_seller_ratio, cfg),
        "initial_bid": initial_bid,
        "reserve": initial_bid + _price(idx, 14, s),
        "date_time": _event_timestamp_us(idx, cfg),
    }


_FIRST_NAMES = ["Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate",
                "Julie", "Sarah", "Deiter", "Walter"]
_LAST_NAMES = ["Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
               "Smith", "Jones", "Noris"]
_NAME_POOL = np.array([f + " " + l for f in _FIRST_NAMES
                       for l in _LAST_NAMES], dtype=object)


def gen_persons(k: np.ndarray,
                cfg: GeneratorConfig) -> Dict[str, np.ndarray]:
    """id, name, date_time of the persons with ordinals `k`."""
    idx = person_event_index(k)
    s = cfg.seed
    fi = _rng_u64(idx, 21, s) % np.uint64(len(_FIRST_NAMES))
    li = _rng_u64(idx, 22, s) % np.uint64(len(_LAST_NAMES))
    return {
        "id": k + FIRST_PERSON_ID,
        "name": _NAME_POOL[fi * np.uint64(len(_LAST_NAMES)) + li],
        "date_time": _event_timestamp_us(idx, cfg),
    }


GENERATORS = {"bid": gen_bids, "auction": gen_auctions,
              "person": gen_persons}


def prefix(table: str, rows: int, cfg: GeneratorConfig):
    """The first `rows` rows a one-split reader of `table` produces."""
    return GENERATORS[table](np.arange(rows, dtype=np.int64), cfg)
