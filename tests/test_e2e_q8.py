"""Nexmark q8 end-to-end: windowed person⋈auction join on the device.

q8 (who has entered the system and created an auction in the same
10s window):

    SELECT P.id, P.name, P.starttime FROM
      (person, TUMBLE 10s) P JOIN
      (SELECT seller, starttime FROM auction TUMBLE 10s GROUP BY ...) A
      ON P.id = A.seller AND P.starttime = A.starttime

Pipeline: two sources → projects → auction-side HashAgg dedup → inner
HashJoin (device matcher) → materialize. Reference parity:
e2e_test/streaming/nexmark/q8 semantics; dedup via GROUP BY matches the
reference plan (agg update pairs degrade to Delete+Insert through the
join, leaving the match multiset unchanged). The plan itself lives in
risingwave_tpu.models.nexmark.
"""

import asyncio

import numpy as np

from risingwave_tpu.connectors.nexmark import (
    NexmarkConfig, gen_auctions, gen_persons,
)
from risingwave_tpu.models.nexmark import (
    DEFAULT_WINDOW, build_q8, drive_to_completion,
)
from risingwave_tpu.state.store import MemoryStateStore

WINDOW = DEFAULT_WINDOW


def q8_oracle(cfg, n_persons, n_auctions):
    kp = np.arange(n_persons, dtype=np.int64)
    persons = gen_persons(kp, cfg)
    ka = np.arange(n_auctions, dtype=np.int64)
    auctions = gen_auctions(ka, cfg)
    p_win = (persons["date_time"] // WINDOW.usecs) * WINDOW.usecs
    a_win = (auctions["date_time"] // WINDOW.usecs) * WINDOW.usecs
    sellers = {(int(s), int(w))
               for s, w in zip(auctions["seller"], a_win)}
    out = set()
    for pid, name, w in zip(persons["id"], persons["name"], p_win):
        if (int(pid), int(w)) in sellers:
            out.add((int(pid), str(name), int(w)))
    return out


def test_q8_end_to_end():
    n_events = 50 * 400
    cfg = NexmarkConfig(event_num=n_events, max_chunk_size=256,
                        min_event_gap_in_ns=50_000_000,  # several windows
                        active_people=40, hot_seller_ratio=2)
    cfg_p = NexmarkConfig(**{**cfg.__dict__, "table_type": "person"})
    cfg_a = NexmarkConfig(**{**cfg.__dict__, "table_type": "auction"})
    n_persons = n_events // 50
    n_auctions = n_events * 3 // 50

    pipeline = build_q8(MemoryStateStore(), cfg_p, cfg_a)
    asyncio.run(drive_to_completion(
        pipeline, {1: n_persons, 2: n_auctions}, max_epochs=200))
    got = {tuple(row) for _pk, row in pipeline.mv_table.iter_rows()}
    expect = q8_oracle(cfg, n_persons, n_auctions)
    assert len(expect) > 10
    assert got == expect
