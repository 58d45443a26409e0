"""Nexmark q1 end-to-end: the first full pipeline.

q1 (currency conversion, stateless):
    SELECT auction, bidder, 0.908 * price AS price, date_time FROM bid;

Reference parity: e2e_test/streaming/nexmark/q1 semantics; pipeline shape
mirrors §3.1-3.2 of SURVEY.md — source → project → materialize driven by
the barrier loop, results read from the MV's committed snapshot. The plan
itself lives in risingwave_tpu.models.nexmark.
"""

import asyncio
import decimal

import numpy as np

from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
from risingwave_tpu.models.nexmark import build_q1, drive_to_completion
from risingwave_tpu.state.store import MemoryStateStore


def test_q1_end_to_end():
    n_epochs = 100
    cfg = NexmarkConfig(event_num=50 * n_epochs, max_chunk_size=512)
    pipeline = build_q1(MemoryStateStore(), cfg)
    n_bids = 46 * n_epochs
    asyncio.run(drive_to_completion(pipeline, {1: n_bids}))
    loop, mv_table = pipeline.loop, pipeline.mv_table
    assert len(loop.stats.completed_epochs) >= 2

    # read the MV snapshot, compare against a direct-computed oracle
    from risingwave_tpu.state.state_table import to_logical_row
    got = [to_logical_row(row, mv_table.schema)
           for _pk, row in mv_table.iter_rows()]
    k = np.arange(n_bids, dtype=np.int64)
    bids = gen_bids(k, cfg)
    rate = decimal.Decimal("0.908")
    expect = {
        (int(a), int(b), (rate * p).quantize(decimal.Decimal("0.0001")),
         int(t))
        for a, b, p, t in zip(bids["auction"], bids["bidder"],
                              map(decimal.Decimal, map(int, bids["price"])),
                              bids["date_time"])
    }
    got_set = {(r[0], r[1], r[2].quantize(decimal.Decimal("0.0001")), r[3])
               for r in got}
    assert len(got) == n_bids
    assert got_set == expect
