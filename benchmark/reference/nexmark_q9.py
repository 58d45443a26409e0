"""Plain reference for NEXmark q9, "winning bids": per auction the bid
with the largest price among the bids made while it was open
(`B.date_time BETWEEN A.date_time AND A.expires`), the earliest of them
on a tie, as the auction's nine columns and the bid's four, over exactly
the prefixes the two readers' checkpoints cover. An auction with no bid
inside its window has no row.

`ROW_NUMBER() OVER (PARTITION BY A.id ORDER BY B.price DESC,
B.date_time ASC) <= 1` names one row only where (price, date_time)
decides: the generator's event times are distinct, and the module
asserts it. TIMESTAMP columns are microseconds since the epoch, as the
benchmark's pgwire client reads them."""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import (
    FIRST_AUCTION_ID, GeneratorConfig, _rng_u64, auction_event_index,
    prefix,
)
from nexmark_q4 import auction_window

# the connector's pool of items (`risingwave_tpu/connectors/nexmark.py`
# `_ITEMS`, as it stood at PR 45); `selfcheck/test_q9.py` holds the two
# together
ITEMS = np.array(["toaster", "chair", "sofa", "bicycle", "kettle", "lamp",
                  "drill", "camera", "guitar", "skates"], dtype=object)
NICE = np.array(["Nice " + item for item in ITEMS.tolist()], dtype=object)

COLUMNS = ("id", "item_name", "description", "initial_bid", "reserve",
           "date_time", "expires", "seller", "category",
           "auction", "bidder", "price", "bid_date_time")


def auction_strings(n: int, cfg: GeneratorConfig):
    """`item_name` and `description` of the first `n` auctions, by the
    connector's rule (`gen_auctions`, strings on): one draw picks the
    item, and the description is "Nice " + that item."""
    idx = auction_event_index(np.arange(n, dtype=np.int64))
    pick = _rng_u64(idx, 17, cfg.seed) % np.uint64(len(ITEMS))
    return {"item_name": ITEMS[pick], "description": NICE[pick]}


def _prefixes(readers):
    rows = {r["table"]: r["rows"] for r in readers}
    if sorted(rows) != ["auction", "bid"] or len(readers) != 2:
        raise ValueError(f"q9 reads auction and bid once each, "
                         f"got {readers}")
    return rows


def winners(n_auc: int, n_bid: int, cfg: GeneratorConfig):
    """(auction ordinals that have a winning bid, the bid ordinal of
    each), ascending by auction."""
    aucs = prefix("auction", n_auc, cfg)
    expires = auction_window(n_auc, cfg)["expires"]
    bids = prefix("bid", n_bid, cfg)
    ts = bids["date_time"]
    assert np.all(np.diff(ts) > 0), \
        "bids share an event time: (price, date_time) names no one row"
    # auction ids are FIRST_AUCTION_ID + ordinal: the join is an index
    k = bids["auction"] - FIRST_AUCTION_ID
    b = np.flatnonzero((k >= 0) & (k < n_auc))
    k = k[b]
    b = b[(ts[b] >= aucs["date_time"][k]) & (ts[b] <= expires[k])]
    k = bids["auction"][b] - FIRST_AUCTION_ID
    # by auction, then price descending, then time ascending: the first
    # of an auction's run is its winner
    order = np.lexsort((ts[b], -bids["price"][b], k))
    k, b = k[order], b[order]
    first = np.flatnonzero(np.concatenate([[True], k[1:] != k[:-1]])) \
        if len(k) else np.zeros(0, dtype=np.int64)
    return k[first], b[first]


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "auction"|"bid", "side": ..., "rows": n}],
    one reader per table. Rows of the view: `COLUMNS`."""
    rows = _prefixes(readers)
    n_auc = rows["auction"]
    a, b = winners(n_auc, rows["bid"], cfg)
    aucs = dict(prefix("auction", n_auc, cfg))
    aucs.update(auction_window(n_auc, cfg))
    aucs.update(auction_strings(n_auc, cfg))
    bids = prefix("bid", rows["bid"], cfg)
    cols = [aucs[c][a].tolist() for c in COLUMNS[:9]]
    cols += [bids[c][b].tolist()
             for c in ("auction", "bidder", "price", "date_time")]
    return collections.Counter(zip(*cols))


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """The DDL declares no watermark, so nothing may be dropped: the
    join's bid side, the view's largest state table, keeps every bid
    of the bid prefix."""
    return _prefixes(readers)["bid"]
