"""Plain reference for NEXmark q7, full form: the bids that equal the
highest price of their 10 s tumbling window.

The served view reads `bid` through two source readers, one for the join
side (`b`) and one under the aggregate (`m`); a checkpoint may cover
different prefixes of the two. The reference takes each prefix as the
checkpoint gives it: a bid of the join-side prefix is in the view when
its price equals the maximum over the aggregate-side prefix's bids of
the same window.
"""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import GeneratorConfig, prefix

WINDOW_US = 10_000_000


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "bid", "side": "left"|"right", "rows": n}].
    The left input of the join is `b`, the right one the aggregate."""
    by_side = {r["side"]: r["rows"] for r in readers}
    if sorted(by_side) != ["left", "right"] or \
            any(r["table"] != "bid" for r in readers):
        raise ValueError(f"q7 reads bid through a left and a right "
                         f"reader, got {readers}")
    bids = prefix("bid", max(by_side.values()), cfg)
    win = bids["date_time"] // WINDOW_US * WINDOW_US
    # date_time does not decrease with the ordinal: windows are runs
    n_agg, n_join = by_side["right"], by_side["left"]
    if n_agg == 0 or n_join == 0:
        return collections.Counter()
    agg_win = win[:n_agg]
    starts = np.flatnonzero(np.r_[True, agg_win[1:] != agg_win[:-1]])
    wmax = np.maximum.reduceat(bids["price"][:n_agg], starts)
    wkeys = agg_win[starts]
    pos = np.searchsorted(wkeys, win[:n_join])
    known = pos < len(wkeys)
    known[known] = wkeys[pos[known]] == win[:n_join][known]
    top = np.zeros(n_join, dtype=bool)
    top[known] = bids["price"][:n_join][known] == wmax[pos[known]]
    return collections.Counter(zip(
        bids["auction"][:n_join][top].tolist(),
        bids["price"][:n_join][top].tolist(),
        bids["bidder"][:n_join][top].tolist(),
        bids["date_time"][:n_join][top].tolist()))


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """The DDL declares no watermark, so a late bid may still join a
    window long past: the join keeps every bid of its side's prefix."""
    return next(r["rows"] for r in readers if r["side"] == "left")
