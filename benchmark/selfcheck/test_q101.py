"""`nexmark-q101` / `q101_steady` (PR 50): the reference's ids and item
names against the connector, the reference against a brute-force loop
(NULL rows included), a rehearsal of the cell coming out correct with
the two host readers in its traced line, and `correct` coming out false
when it should: whole runs of `run.py` in this process with
`--rehearse` (tiny sizes, the CPU)."""

import collections
import json

import numpy as np
import pytest

import nexmark_gen
import nexmark_q101
import nexmark_q9
import run
from risingwave_tpu.connectors import nexmark as conn

# the device reader needs a device plane: a chip run prints it as well
HOST_READERS = ("outer_join_host_share", "outer_padded_rows_per_left_row")


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 5_000_000_031])
def test_ids_and_item_names_match_the_connector(seed):
    """Over the first auctions and a stretch far in; the driver's seeds
    are above 2**31."""
    n = 405_000
    k = np.concatenate([np.arange(0, 5000), np.arange(400_000, n)]
                       ).astype(np.int64)
    theirs = conn.gen_auctions(k, conn.NexmarkConfig(seed=seed))
    cfg = nexmark_gen.GeneratorConfig(seed=seed)
    ids, _best = nexmark_q101.highest_bids(n, 0, cfg)
    assert np.array_equal(ids[k], theirs["id"])
    assert np.array_equal(
        nexmark_q9.auction_strings(n, cfg)["item_name"][k],
        theirs["item_name"])
    names = [f.name for f in conn.TABLE_SCHEMAS["auction"]]
    assert names[:2] == ["id", "item_name"]


def brute(auctions, bids) -> collections.Counter:
    """(id, item_name) rows and (auction, price) rows in, the view out:
    every bid against every auction."""
    out = collections.Counter()
    for a, name in auctions:
        prices = [p for auction, p in bids if auction == a]
        out[(a, name, max(prices) if prices else None)] += 1
    return out


@pytest.mark.parametrize("n_auction,n_bid", [
    (268, 4096), (80, 4096), (268, 1000), (1, 100), (0, 100), (100, 0)])
def test_reference_against_a_loop(n_auction, n_bid):
    """Equal prefixes, bids whose auction the prefix does not hold yet,
    auctions with no bid (the NULL rows), no auction, no bid."""
    cfg = nexmark_gen.GeneratorConfig(seed=5_000_000_007)
    readers = [{"table": "auction", "side": "left", "rows": n_auction},
               {"table": "bid", "side": "right", "rows": n_bid}]
    aucs = nexmark_gen.prefix("auction", n_auction, cfg)
    names = nexmark_q9.auction_strings(n_auction, cfg)["item_name"]
    bids = nexmark_gen.prefix("bid", n_bid, cfg)
    got = nexmark_q101.reference(readers, cfg)
    assert got == brute(
        list(zip(aucs["id"].tolist(), names.tolist())),
        list(zip(bids["auction"].tolist(), bids["price"].tolist())))
    assert sum(got.values()) == len(got) == n_auction
    assert all(len(r) == 3 for r in got)
    nulls = sum(1 for r in got if r[2] is None)
    if n_bid == 0:
        assert nulls == n_auction
    if (n_auction, n_bid) == (268, 1000):
        assert 100 < nulls < 268             # most bids are yet to come
    named = len(set(bids["auction"].tolist()))
    assert nexmark_q101.resident_rows(readers, cfg) == \
        max(n_auction, named)
    if (n_auction, n_bid) == (80, 4096):
        assert named > n_auction             # the aggregate's the larger


def test_reference_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q101.reference([{"table": "bid", "side": "left",
                                 "rows": 10}],
                               nexmark_gen.GeneratorConfig())


def drive(capsys, *extra):
    rc = run.main(["--workload", "q101_steady", "--seed", "5000000019",
                   "--seconds", "3", "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1]), lines


def test_sound_run_is_correct(capsys):
    result, lines = drive(capsys, "--trace", "0")
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"events_per_s", "barrier_p50_ms",
                                      "barrier_p90_ms", "setup_s"}
    assert any("view nexmark_q101 has " in ln and "reference" in ln
               for ln in lines)


def test_traced_run_prints_the_host_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    assert set(HOST_READERS) <= set(result["metrics"])    # >=: M9
    m = {k: result["metrics"][k]["value"] for k in HOST_READERS}
    assert 0 < m["outer_join_host_share"] < 100
    # nearly every auction is padded and retracted once
    assert 1.5 < m["outer_padded_rows_per_left_row"] <= 2.0


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_control_short_reference_is_not_correct(capsys):
    # 4,096 rows short of each reader: the reference lacks the last
    # 4,096 auctions, and its largest table is then the aggregate's,
    # of the auctions that the bids less 4,096 name
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference")
    assert result["correct"] is False
    assert result["failed"] == 0
    off = next(int(ln.split("off the reference's by ")[1].split(" ")[0])
               for ln in lines if "off the reference's by" in ln)
    assert off > 0       # the bids cut name fewer auctions too
    differing = next(int(ln.split("differing from the reference ")[1]
                         .split(" ")[0]) for ln in lines
                     if "differing from the reference" in ln
                     and "compared:" in ln)
    assert differing >= 4096
