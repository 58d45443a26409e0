"""Nexmark q7-shaped pipeline end-to-end: the first stateful TPU query.

q7 core (highest bid per 10s tumbling window — the HashAgg-on-TPU
baseline config; full q7's self-join lands with HashJoinExecutor):

    SELECT window_start, MAX(price), COUNT(*) FROM
      TUMBLE(bid, date_time, INTERVAL '10' SECOND)
    GROUP BY window_start;

Reference parity: e2e_test/streaming/nexmark/q7.slt.part semantics;
pipeline shape per SURVEY §3.2 — source → project(tumble) → hash-agg
(device kernel) → materialize, driven by the barrier loop. The plan
itself lives in risingwave_tpu.models.nexmark.
"""

import asyncio
from collections import defaultdict

import numpy as np

from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
from risingwave_tpu.models.nexmark import (
    DEFAULT_WINDOW, build_q7, drive_to_completion,
)
from risingwave_tpu.state.store import MemoryStateStore

WINDOW = DEFAULT_WINDOW


def q7_oracle(cfg, n_bids):
    k = np.arange(n_bids, dtype=np.int64)
    bids = gen_bids(k, cfg)
    w = (bids["date_time"] // WINDOW.usecs) * WINDOW.usecs
    out = defaultdict(lambda: (0, 0))
    for wi, p in zip(w.tolist(), bids["price"].tolist()):
        mx, c = out[wi]
        out[wi] = (max(mx, p), c + 1)
    return dict(out)


def test_q7_end_to_end():
    n_epochs = 40
    # ~3 windows over the whole run: gap 100µs ⇒ 10s window = 100K events
    cfg = NexmarkConfig(event_num=50 * 50 * n_epochs, max_chunk_size=1024,
                        min_event_gap_in_ns=100_000_000)  # 0.1s/event
    pipeline = build_q7(MemoryStateStore(), cfg)
    n_bids = 46 * 50 * n_epochs
    asyncio.run(drive_to_completion(pipeline, {1: n_bids}))
    loop, mv_table = pipeline.loop, pipeline.mv_table
    assert len(loop.stats.completed_epochs) >= 3

    got = {row[0]: (row[1], row[2]) for _pk, row in mv_table.iter_rows()}
    expect = q7_oracle(cfg, n_bids)
    assert len(got) > 3   # several windows
    assert got == expect


def test_q7_watermark_cleaning_bounded_state():
    """Watermark-driven state cleaning end to end (VERDICT r2 #3):
    with a WatermarkFilter generating event-time watermarks and the agg
    retiring closed tumble windows, (a) the MV still matches the oracle
    exactly — nexmark event time is monotone, so no rows are late and
    retirement never changes results — and (b) the agg value-state table
    holds only the open windows at the end, not every window ever seen."""
    from risingwave_tpu.common.types import Interval

    n_epochs = 60
    # gap 0.2s/event ⇒ a 10s window every 50 events: many windows
    cfg = NexmarkConfig(event_num=50 * 30 * n_epochs, max_chunk_size=512,
                        min_event_gap_in_ns=200_000_000)
    pipeline = build_q7(MemoryStateStore(), cfg, rate_limit=2,
                        watermark_delay=Interval(usecs=0))
    n_bids = 46 * 30 * n_epochs
    asyncio.run(drive_to_completion(pipeline, {1: n_bids}))

    got = {row[0]: (row[1], row[2]) for _pk, row in
           pipeline.mv_table.iter_rows()}
    expect = q7_oracle(cfg, n_bids)
    assert len(expect) > 10            # many windows closed over the run
    assert got == expect               # retirement never changed results

    # the agg's VALUE STATE kept only windows at/after the final
    # watermark — closed windows were deleted (mv keeps final results)
    agg_executor = pipeline.actor.consumer.input  # materialize ← agg
    state_rows = list(agg_executor.table.iter_rows())
    assert len(state_rows) < len(expect) / 2, \
        (len(state_rows), len(expect))
    final_wm = agg_executor._cleaned_wm
    assert final_wm is not None
    assert all(row[0] >= final_wm for _pk, row in state_rows)
    # device table occupancy bounded too (survivors only)
    occ = int(np.asarray(agg_executor.kernel.state.table.occ).sum())
    assert occ <= len(state_rows) + 1


def test_q7_on_hummock_with_restart(tmp_path):
    """The full stack: pipeline state checkpoints through HummockLite on
    a local-FS object store; a fresh process-equivalent (new store over
    the same objects, new pipeline) resumes from the committed epoch and
    finishes with exactly the oracle result (recovery.rs semantics)."""
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    root = str(tmp_path / "hummock")
    cfg = NexmarkConfig(event_num=50 * 40, max_chunk_size=256,
                        min_event_gap_in_ns=100_000_000)
    n_bids = 46 * 40

    # phase 1: run HALF the stream, checkpoint, drop everything
    store1 = HummockLite(LocalFsObjectStore(root))
    p1 = build_q7(store1, cfg, rate_limit=1, min_chunks=1)
    asyncio.run(drive_to_completion(p1, {1: n_bids // 2}))
    offset1 = p1.reader.offset
    assert offset1 >= n_bids // 2
    del p1, store1

    # phase 2: recover from the object store, run to completion
    store2 = HummockLite(LocalFsObjectStore(root))
    p2 = build_q7(store2, cfg, rate_limit=1, min_chunks=1)
    asyncio.run(drive_to_completion(p2, {1: n_bids}))
    # the source resumed at (or after) the committed offset, not zero
    assert p2.reader.offset == n_bids

    got = {row[0]: (row[1], row[2]) for _pk, row in p2.mv_table.iter_rows()}
    assert got == q7_oracle(cfg, n_bids)
