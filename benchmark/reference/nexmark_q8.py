"""Plain reference for NEXmark q8: the persons who opened an auction in
the 10 s tumbling window in which they registered. Both sides are
deduplicated before the join (upstream's GROUP BY), so the view holds one
row per person and window, however many auctions that person opened."""

from __future__ import annotations

import collections

from nexmark_gen import GeneratorConfig, prefix

WINDOW_US = 10_000_000


def _prefixes(readers):
    rows = {r["table"]: r["rows"] for r in readers}
    if sorted(rows) != ["auction", "person"] or len(readers) != 2:
        raise ValueError(f"q8 reads person and auction once each, "
                         f"got {readers}")
    return rows


def reference(readers, cfg: GeneratorConfig) -> collections.Counter:
    """`readers`: [{"table": "person"|"auction", "side": ..., "rows": n}],
    one reader per table."""
    rows = _prefixes(readers)
    aucs = prefix("auction", rows["auction"], cfg)
    pers = prefix("person", rows["person"], cfg)
    sellers = set(zip(
        aucs["seller"].tolist(),
        (aucs["date_time"] // WINDOW_US * WINDOW_US).tolist()))
    return collections.Counter(set(
        (pid, str(name), w) for pid, name, w in zip(
            pers["id"].tolist(), pers["name"].tolist(),
            (pers["date_time"] // WINDOW_US * WINDOW_US).tolist())
        if (pid, w) in sellers))


def resident_rows(readers, cfg: GeneratorConfig) -> int:
    """The DDL declares no watermark, so no window is ever closed: the
    deduplicated person side keeps one row per person of its prefix
    (a person registers once: ids are distinct)."""
    return _prefixes(readers)["person"]
