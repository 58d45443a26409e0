"""The benchmark's copy of the generator gives, for one seed, the same
columns as `risingwave_tpu/connectors/nexmark.py` does today. When the
connector changes its data on purpose, this check fails and `correct`
fails with it: the copy is the yardstick and stays."""

import numpy as np

import nexmark_gen
from risingwave_tpu.connectors import nexmark as conn

SEED = 2_147_483_659        # above 2**31, as the driver's seeds are


def test_copy_matches_connector():
    k = np.concatenate([np.arange(0, 5000), np.arange(400_000, 405_000)]
                       ).astype(np.int64)
    theirs_cfg = conn.NexmarkConfig(seed=SEED)
    ours_cfg = nexmark_gen.GeneratorConfig(seed=SEED)
    for table, theirs_fn in (("bid", conn.gen_bids),
                             ("auction", conn.gen_auctions),
                             ("person", conn.gen_persons)):
        theirs = theirs_fn(k, theirs_cfg)
        ours = nexmark_gen.GENERATORS[table](k, ours_cfg)
        assert ours, table
        for col, values in ours.items():
            assert np.array_equal(values, theirs[col]), (table, col)


def test_defaults_match_connector():
    theirs = conn.NexmarkConfig()
    ours = nexmark_gen.GeneratorConfig()
    for field in ("seed", "min_event_gap_in_ns", "active_people",
                  "in_flight_auctions", "hot_seller_ratio",
                  "hot_auction_ratio", "hot_bidder_ratio"):
        assert getattr(ours, field) == getattr(theirs, field), field
    assert nexmark_gen.PROPORTION == {
        "person": conn.PERSON_PROPORTION,
        "auction": conn.AUCTION_PROPORTION, "bid": conn.BID_PROPORTION}


def test_reader_prefix_is_the_first_rows_of_a_split_reader():
    cfg = conn.NexmarkConfig(seed=SEED, table_type="auction",
                             max_chunk_size=512)
    reader = conn.NexmarkSplitReader(cfg)
    chunks = [reader.next_chunk() for _ in range(3)]
    ids = np.array([row[0] for c in chunks for row in c.to_pylist()])
    ours = nexmark_gen.prefix("auction", reader.offset,
                              nexmark_gen.GeneratorConfig(seed=SEED))
    assert reader.offset == 1536
    assert np.array_equal(ids, ours["id"])
