"""Plain reference for RisingWave's NEXmark q101: every auction of the
auction reader's prefix with its item name and the largest price among
the bids of the bid reader's prefix that name it, `None` where no bid
does (the LEFT OUTER JOIN's NULL-padded row), over exactly the prefixes
the two readers' checkpoints cover. No bid is dropped by time: the view
has no BETWEEN, a bid counts from the barrier that brings both it and
its auction.

The join is an index (auction ids are FIRST_AUCTION_ID + ordinal) and
one row an auction only because ids are unique: the module asserts
it."""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import FIRST_AUCTION_ID, GeneratorConfig, prefix
from nexmark_q9 import auction_strings

COLUMNS = ("auction_id", "auction_item_name", "current_highest_bid")


def _prefixes(readers):
    rows = {r["table"]: r["rows"] for r in readers}
    if sorted(rows) != ["auction", "bid"] or len(readers) != 2:
        raise ValueError(f"q101 reads auction and bid once each, "
                         f"got {readers}")
    return rows


def highest_bids(n_auc: int, n_bid: int, cfg: GeneratorConfig):
    """(ids of the first `n_auc` auctions, the largest price bid on
    each within the first `n_bid` bids, 0 where there is none)."""
    ids = prefix("auction", n_auc, cfg)["id"]
    assert np.array_equal(
        ids, FIRST_AUCTION_ID + np.arange(n_auc, dtype=np.int64)), \
        "auction ids are not FIRST_AUCTION_ID + ordinal: not unique"
    bids = prefix("bid", n_bid, cfg)
    assert n_bid == 0 or bids["price"].min() >= 1, \
        "a price below 1: 0 cannot stand for 'no bid'"
    k = bids["auction"] - FIRST_AUCTION_ID
    known = (k >= 0) & (k < n_auc)
    best = np.zeros(n_auc, dtype=np.int64)
    np.maximum.at(best, k[known], bids["price"][known])
    return ids, best


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "auction"|"bid", "side": ..., "rows": n}],
    one reader per table. Rows of the view: `COLUMNS`, the third `None`
    for an auction no bid of the prefix names."""
    rows = _prefixes(readers)
    n_auc = rows["auction"]
    ids, best = highest_bids(n_auc, rows["bid"], cfg)
    names = auction_strings(n_auc, cfg)["item_name"]
    return collections.Counter(zip(
        ids.tolist(), names.tolist(),
        [p if p else None for p in best.tolist()]))


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """Every table of the plan holds one row an auction. The view's own
    table and the join's auction side hold every auction of the auction
    prefix, matched or not (a LEFT OUTER JOIN drops none): they are the
    largest while the auction reader is not behind the bids. The
    aggregate's table and the join's other side hold one row an auction
    that a bid of the bid prefix names, in the auction prefix or not
    yet: were the bid reader far ahead, they would be the larger, so
    the larger of the two counts is what the deployment keeps."""
    rows = _prefixes(readers)
    named = np.unique(prefix("bid", rows["bid"], cfg)["auction"])
    return max(rows["auction"], len(named))
