"""Plain reference for NEXmark q4, "average price for a category": per
auction the largest price among the bids made while it was open
(`B.date_time BETWEEN A.date_time AND A.expires`), and per category the
average of those, over exactly the prefixes the two readers'
checkpoints cover. An auction with no bid inside its window has no row
in the inner query and is not counted.

The average is the float64 nearest to the exact quotient of the exact
integer sum by the count (Python's `int / int`): what `AVG(bigint)`
is held to here, where upstream returns `numeric`."""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import (
    FIRST_AUCTION_ID, PROPORTION_DENOMINATOR, GeneratorConfig, _rng_u64,
    auction_event_index, prefix,
)

FIRST_CATEGORY_ID = 10
NUM_CATEGORIES = 5


def auction_window(n: int, cfg: GeneratorConfig):
    """`expires` and `category` of the first `n` auctions: the two
    columns the generator copy lacks, by the connector's rule
    (`risingwave_tpu/connectors/nexmark.py` `gen_auctions`, as it
    stood at PR 31): an auction stays open for 1 to 11 twentieths of a
    second of event time at the 100 us gap, and never less than one
    second."""
    idx = auction_event_index(np.arange(n, dtype=np.int64))
    lifetime_us = ((_rng_u64(idx, 15, cfg.seed) % np.uint64(11)
                    + np.uint64(1)).astype(np.int64)
                   * np.int64(max(cfg.min_event_gap_in_ns, 1))
                   * PROPORTION_DENOMINATOR // 1000 * 20)
    date_time = prefix("auction", n, cfg)["date_time"]
    return {
        "expires": date_time + np.maximum(lifetime_us, 1_000_000),
        "category": FIRST_CATEGORY_ID + (
            _rng_u64(idx, 16, cfg.seed) % np.uint64(NUM_CATEGORIES)
        ).astype(np.int64),
    }


def _prefixes(readers):
    rows = {r["table"]: r["rows"] for r in readers}
    if sorted(rows) != ["auction", "bid"] or len(readers) != 2:
        raise ValueError(f"q4 reads auction and bid once each, "
                         f"got {readers}")
    return rows


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "auction"|"bid", "side": ..., "rows": n}],
    one reader per table. Rows of the view: (category, avg)."""
    rows = _prefixes(readers)
    n_auc = rows["auction"]
    aucs = prefix("auction", n_auc, cfg)
    extra = auction_window(n_auc, cfg)
    bids = prefix("bid", rows["bid"], cfg)
    # auction ids are FIRST_AUCTION_ID + ordinal: the join is an index
    k = bids["auction"] - FIRST_AUCTION_ID
    known = (k >= 0) & (k < n_auc)
    k, price, ts = k[known], bids["price"][known], bids["date_time"][known]
    inside = (ts >= aucs["date_time"][k]) & (ts <= extra["expires"][k])
    k, price = k[inside], price[inside]
    final = np.zeros(n_auc, dtype=np.int64)      # prices are >= 1
    np.maximum.at(final, k, price)
    sold = final > 0
    total = collections.Counter()
    count = collections.Counter()
    for cat, value in zip(extra["category"][sold].tolist(),
                          final[sold].tolist()):
        total[cat] += value                      # Python ints: exact
        count[cat] += 1
    return collections.Counter(
        {(cat, total[cat] / count[cat]): 1 for cat in total})


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """The DDL declares no watermark, so nothing may be dropped: the
    join's bid side, the view's largest state table, keeps every bid
    of the bid prefix."""
    return _prefixes(readers)["bid"]
