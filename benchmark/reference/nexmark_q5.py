"""Plain reference for NEXmark q5, "hot items": per 10 s window that
slides by 2 s, the auctions with the most bids. As upstream writes it
the view joins two readings of the same counts: `AuctionBids`, the
count per (window_start, auction), and `MaxBids`, the largest count of
each window, on `window_start` and `num >= maxn`.

The served view reads `bid` through two source readers, one under each
side of the join, and a checkpoint may cover different prefixes of the
two. The reference takes each prefix as the checkpoint gives it: a
group of the left prefix is in the view when its count is at least the
largest count the right prefix gives for the same window. Every tied
auction is a row; a window the right prefix has not reached gives none.

A bid is in the five windows that cover it: `window_start` is its
`date_time` floored to the slide, less 0, 2, 4, 6 and 8 s.
"""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import GeneratorConfig, prefix

SLIDE_US = 2_000_000
SIZE_US = 10_000_000
UNITS = SIZE_US // SLIDE_US


def window_counts(n: int, cfg: GeneratorConfig):
    """(window_start, auction, count) of the distinct groups of the
    first `n` bids, as three arrays sorted by window and auction."""
    if n == 0:
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty, empty
    bids = prefix("bid", n, cfg)
    base = bids["date_time"] // SLIDE_US * SLIDE_US
    starts = np.concatenate([base - i * SLIDE_US for i in range(UNITS)])
    auction = np.tile(bids["auction"], UNITS)
    order = np.lexsort((auction, starts))
    starts, auction = starts[order], auction[order]
    first = np.flatnonzero(np.r_[True, (starts[1:] != starts[:-1])
                                 | (auction[1:] != auction[:-1])])
    count = np.diff(np.r_[first, len(starts)])
    return starts[first], auction[first], count


def _prefixes(readers):
    by_side = {r["side"]: r["rows"] for r in readers}
    if sorted(by_side) != ["left", "right"] or \
            any(r["table"] != "bid" for r in readers):
        raise ValueError(f"q5 reads bid through a left and a right "
                         f"reader, got {readers}")
    return by_side["left"], by_side["right"]


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "bid", "side": "left"|"right", "rows": n}].
    The left input of the join is `AuctionBids`, the right one
    `MaxBids`. Rows of the view: (auction, num)."""
    n_left, n_right = _prefixes(readers)
    ws, auction, num = window_counts(n_left, cfg)
    rws, _rauction, rnum = window_counts(n_right, cfg)
    if not len(ws) or not len(rws):
        return collections.Counter()
    # windows are runs of the sorted groups
    runs = np.flatnonzero(np.r_[True, rws[1:] != rws[:-1]])
    maxn = np.maximum.reduceat(rnum, runs)
    keys = rws[runs]
    pos = np.searchsorted(keys, ws)
    known = pos < len(keys)
    known[known] = keys[pos[known]] == ws[known]
    hot = np.zeros(len(ws), dtype=bool)
    hot[known] = num[known] >= maxn[pos[known]]
    return collections.Counter(zip(auction[hot].tolist(),
                                   num[hot].tolist()))


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """The DDL declares no watermark, so no window may be dropped: each
    counting aggregate, and the join's count side, keeps one row per
    distinct (window_start, auction) of the prefix of the reader that
    feeds it. The view's largest state table is one of those, under
    the reader that is furthest on."""
    return len(window_counts(max(_prefixes(readers)), cfg)[0])
