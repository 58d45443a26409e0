"""Plain reference for NEXmark q5 under the benchmark's own watermark:
`WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND` on `bid`
(NEXmark's `ddl_gen.sql`), the view `nexmark-q5`'s letter for letter.

**The view.** The generator is in order, so no bid is late, none is
dropped, and the answer equals `nexmark_q5.reference` over the same
prefixes: a closed window keeps its rows in the view, only the state
behind it goes. `reference` asserts that no `date_time` is under the
watermark when it arrives and recomputes q5 as `nexmark_q5` does.

**The state.** A reader whose checkpoint covers `n` bids has announced
the watermark `wm = max(date_time[:n]) - 4 s`. A 10 s window that slides
by 2 s is closed once `window_start + 10 s <= wm`, so an operator fed
by that reader keeps the groups with

    window_start >= floor(wm / 2 s) * 2 s - 8 s        (`bound`)

as `HopWindowExecutor` derives the watermark of `window_start` from the
one of `date_time`, and upstream with it. Per state table of the view
(`resident_by_table`):

  AuctionBids        one row per (window_start, auction) of the left
                     prefix at or above the left bound
  CountBids          the same for the right prefix and the right bound
  MaxBids            one row per window_start of the right prefix at or
                     above the right bound
  MaxBids.values     its value multiset: one row per distinct
                     (window_start, count) of the right prefix's groups
  join.left          AuctionBids' rows at or above the join's bound
  join.right         MaxBids' rows at or above the join's bound

The join cleans both sides to the watermark both inputs have reached,
the smaller of the two bounds (upstream's `hash_join.rs` does the same:
a row may go only when neither side can still match it). With the two
readers in lockstep the bounds are equal. No operator lags a barrier:
each cleans, at the barrier that seals an epoch, to the watermark that
epoch's rows announced.

`resident_rows` is the largest of these, which is what the harness
reads from `rw_state_topology`. A table that is not cleaned reads above
it, one cleaned too far below it.
"""

from __future__ import annotations

import collections

import numpy as np

from nexmark_gen import GeneratorConfig, prefix
from nexmark_q5 import SLIDE_US, UNITS, _prefixes, window_counts
from nexmark_q5 import reference as _q5_reference

DELAY_US = 4_000_000


def _bids_in_order(n: int, cfg: GeneratorConfig) -> None:
    """No bid of the prefix is late: each `date_time` is at or above
    the watermark every bid before it announced."""
    if n:
        ts = prefix("bid", n, cfg)["date_time"]
        assert (ts >= np.maximum.accumulate(ts) - DELAY_US).all(), \
            "a bid under the watermark: the reference takes none as late"


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """Rows of the view, (auction, num), over the prefixes the
    checkpoint covers: `nexmark_q5.reference`'s, no row being late."""
    for n in _prefixes(readers):
        _bids_in_order(n, cfg)
    return _q5_reference(readers, cfg)


def bound(n: int, cfg: GeneratorConfig):
    """The smallest `window_start` the watermark of the first `n` bids
    has not closed; None before the first bid (no watermark yet)."""
    if n == 0:
        return None
    wm = int(prefix("bid", n, cfg)["date_time"].max()) - DELAY_US
    return wm // SLIDE_US * SLIDE_US - (UNITS - 1) * SLIDE_US


def _at_or_above(values: np.ndarray, low) -> np.ndarray:
    return np.ones(len(values), dtype=bool) if low is None \
        else values >= low


def resident_by_table(readers, cfg: GeneratorConfig) -> dict:
    """Rows each state table of the view keeps at the checkpoint."""
    n_left, n_right = _prefixes(readers)
    lws, _lauction, _lnum = window_counts(n_left, cfg)
    rws, _rauction, rnum = window_counts(n_right, cfg)
    lb, rb = bound(n_left, cfg), bound(n_right, cfg)
    # the join waits for both inputs: no watermark before both have one
    jb = None if lb is None or rb is None else min(lb, rb)
    rkeep = _at_or_above(rws, rb)
    windows = np.unique(rws[rkeep])
    return {
        "AuctionBids": int(_at_or_above(lws, lb).sum()),
        "CountBids": int(rkeep.sum()),
        "MaxBids": len(windows),
        "MaxBids.values": len(np.unique(
            np.stack([rws[rkeep], rnum[rkeep]], axis=1), axis=0))
        if rkeep.any() else 0,
        "join.left": int(_at_or_above(lws, jb).sum()),
        "join.right": int(_at_or_above(windows, jb).sum()),
    }


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """Rows of the view's largest state table."""
    return max(resident_by_table(readers, cfg).values())
