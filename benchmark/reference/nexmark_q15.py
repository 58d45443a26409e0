"""Plain reference for NEXmark q15, "bidding statistics report": per
calendar day of `date_time`, the bids, the distinct bidders and the
distinct auctions, each in total and by three price ranks (`price <
10000`, `10000 <= price < 1000000`, `price >= 1000000`). Thirteen
columns: the day, four counts, four `count(DISTINCT bidder)`, four
`count(DISTINCT auction)`; the ranked ones are upstream's `FILTER (WHERE
...)`, which decides whether a bid counts for that column and nothing
else.

The served view reads `bid` through one source reader; the reference
recounts over exactly the prefix its checkpoint covers, with numpy sets
(`np.unique` of the ids that pass a rank's filter). The day is formatted
from `date_time` itself: microseconds since the epoch floored to whole
days, printed as a `datetime64[D]`, which is `YYYY-MM-DD` in UTC. No
`strftime` or `to_char` of the program's is involved.

What the deployment keeps: the DDL declares no watermark, so a day is
never closed and a pair is never dropped. The aggregate keeps one row a
day in its value state, and per distinct column ONE dedup table of
(day, value) pairs, each with a count per call that is DISTINCT on that
column (upstream's `aggregation/distinct.rs`). The view's largest state
table is therefore the dedup table of `auction`: the generator makes
three auctions for every person and half the bids go to the last hundred
auctions, so distinct (day, auction) pairs outnumber distinct (day,
bidder) pairs about three to one (0.065 against 0.022 a bid), and both
outnumber the one row a day of the value state and of the view.
`resident_rows` is that table's rows. A layout that gave every filtered
call a dedup table of its own would have the same largest table; the
self-check (`selfcheck/test_q15.py`) therefore also holds the number of
dedup tables and their count columns.
"""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import GeneratorConfig, prefix

DAY_US = 86_400_000_000
RANK1_BELOW = 10_000
RANK3_FROM = 1_000_000


def _prefix(readers, cfg: GeneratorConfig):
    if len(readers) != 1 or readers[0]["table"] != "bid":
        raise ValueError(f"q15 reads bid through one reader, got "
                         f"{readers}")
    return prefix("bid", readers[0]["rows"], cfg)


def _day_runs(bids):
    """`date_time` does not decrease with the ordinal, so the days are
    runs: (day number of each run, start of each run, one past the end
    of the last)."""
    day = bids["date_time"] // DAY_US
    starts = np.flatnonzero(np.r_[True, day[1:] != day[:-1]])
    return day[starts], starts, len(day)


def day_text(day_number: int) -> str:
    return str(np.datetime64(int(day_number), "D"))


def pair_counts(readers, cfg: GeneratorConfig):
    """(distinct (day, bidder) pairs, distinct (day, auction) pairs) of
    the prefix: the rows of the two dedup tables."""
    bids = _prefix(readers, cfg)
    if not len(bids["date_time"]):
        return 0, 0
    _days, starts, end = _day_runs(bids)
    bounds = list(starts) + [end]
    bidders = auctions = 0
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        bidders += len(np.unique(bids["bidder"][lo:hi]))
        auctions += len(np.unique(bids["auction"][lo:hi]))
    return bidders, auctions


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "bid", "side": None, "rows": n}]. Rows of
    the view: (day, total_bids, rank1_bids, rank2_bids, rank3_bids,
    total_bidders, rank1_bidders, rank2_bidders, rank3_bidders,
    total_auctions, rank1_auctions, rank2_auctions, rank3_auctions)."""
    bids = _prefix(readers, cfg)
    out = collections.Counter()
    if not len(bids["date_time"]):
        return out
    days, starts, end = _day_runs(bids)
    bounds = list(starts) + [end]
    for day, lo, hi in zip(days.tolist(), bounds[:-1], bounds[1:]):
        price = bids["price"][lo:hi]
        ranks = (np.ones(hi - lo, dtype=bool),
                 price < RANK1_BELOW,
                 (price >= RANK1_BELOW) & (price < RANK3_FROM),
                 price >= RANK3_FROM)
        row = [day_text(day)]
        row += [int(r.sum()) for r in ranks]
        for ids in (bids["bidder"][lo:hi], bids["auction"][lo:hi]):
            row += [len(np.unique(ids[r])) for r in ranks]
        out[tuple(row)] += 1
    return out


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """Rows of the view's largest state table: the dedup table of
    `auction`, one row per distinct (day, auction) of the prefix (why
    that table: the module docstring)."""
    return pair_counts(readers, cfg)[1]
