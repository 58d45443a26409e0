"""The clean index of a state table (ISSUE 38): a watermark's range
delete takes the committed rows below the watermark from the table's
own record of its keys, bucketed by the leading pk column's encoded
bytes, and reads the store once, at the table's first clean.

Every case runs over ``MemoryStateStore`` and over ``HummockLite`` on a
temp dir (checkpoints synced, a compaction now and then, so the rows a
clean dooms sit in every layer). The oracle is brute force: the table's
own ``iter_rows`` filtered on the lead, beside a dict the test keeps.
"""

import itertools
import random

import numpy as np
import pytest

from risingwave_tpu.common import DataType, Epoch, EpochPair, Schema
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.state.topology import TOPOLOGY
from risingwave_tpu.utils.metrics import MetricsHistory
from risingwave_tpu.utils.metrics import STREAMING as METRICS

SCHEMA = Schema.of(ts=DataType.TIMESTAMP, k=DataType.INT64,
                   v=DataType.INT64)
STORES = ("memory", "hummock")
_table_ids = itertools.count(938_000)


def _store(kind: str, tmp_path):
    if kind == "memory":
        return MemoryStateStore()
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore
    return HummockLite(LocalFsObjectStore(str(tmp_path)))


class Harness:
    """One table (pk ``ts, k``; vnodes by ``k``), the dict of what it
    should hold, and the epochs."""

    def __init__(self, store, table_id=None, epoch=None, model=None):
        self.store = store
        self.table_id = next(_table_ids) if table_id is None else table_id
        self.table = StateTable(self.table_id, SCHEMA, [0, 1], store,
                                dist_key_indices=[1], sanity_check=True)
        self.table.init_epoch(epoch or EpochPair.new_initial(
            Epoch.from_physical(1)))
        self.model = {} if model is None else model
        self.commits = 0

    @property
    def label(self):
        return f"t{self.table_id}"

    def counter(self, metric):
        return metric.get(table=self.label)

    def insert(self, ts, k, v=0):
        self.table.insert((ts, k, v))
        self.model[(ts, k)] = (ts, k, v)

    def update(self, pk, v):
        old = self.model[pk]
        new = (pk[0], pk[1], v)
        self.table.update(old, new)
        self.model[pk] = new

    def delete(self, pk):
        self.table.delete(self.model.pop(pk))

    def rows(self):
        return dict(self.table.iter_rows())

    def owned(self, pk):
        return bool(self.table.vnodes[self.table._vnode_of_pk(pk)])

    def clean(self, watermark):
        """A clean held to the oracle: the rows gone are the table's
        own rows below the watermark (a NULL lead sorts below all), in
        the vnodes it owns."""
        before = self.rows()
        assert before == self.model
        want = {pk for pk in before
                if (pk[0] is None or pk[0] < watermark) and self.owned(pk)}
        deleted, read = self.table.delete_below_prefix(watermark)
        after = self.rows()
        assert set(before) - set(after) == want
        assert set(after) <= set(before) and deleted == len(want)
        for pk in want:
            del self.model[pk]
        return deleted, read

    def commit(self):
        t = self.table
        sealed = t.epoch.curr
        t.commit(EpochPair(curr=sealed.next(), prev=sealed))
        self.commits += 1
        if not isinstance(self.store, MemoryStateStore):
            self.store.seal_epoch(sealed.value)
            self.store.sync(sealed.value)
            if self.commits % 4 == 0:
                self.store.compact()
        assert self.rows() == self.model
        return sealed


@pytest.fixture(params=STORES)
def h(request, tmp_path):
    return Harness(_store(request.param, tmp_path))


def _fill(h, n=40, spread=10):
    for k in range(n):
        h.insert(1000 + k % spread, k, k)


# -- (a) the cases by name, then random sequences ---------------------------

def test_inserted_and_cleaned_in_one_epoch(h):
    _fill(h)
    h.commit()
    h.clean(1003)                       # seeds
    h.commit()
    h.insert(1001, 500)                 # this epoch's, below the next
    h.insert(1004, 501)
    h.insert(1900, 502)
    deleted, read = h.clean(1005)
    assert read == 0 and (1001, 500) not in h.model
    assert deleted == 1 + 1 + sum(1 for k in range(40)
                                  if 1003 <= 1000 + k % 10 < 1005)
    h.commit()
    assert (1900, 502) in h.rows()


def test_deleted_in_the_epoch_it_would_have_been_cleaned(h):
    _fill(h)
    h.commit()
    h.clean(1001)
    h.commit()
    gone = [pk for pk in sorted(h.model) if pk[0] == 1002][:2]
    for pk in gone:
        h.delete(pk)                    # the operator's own delete
    h.update(next(pk for pk in sorted(h.model) if pk[0] == 1002), 77)
    deleted, _read = h.clean(1003)      # no double delete, no resurrection
    assert deleted == sum(1 for k in range(40)
                          if 1001 <= 1000 + k % 10 < 1003) - len(gone)
    h.commit()
    assert not [pk for pk in h.rows() if pk[0] < 1003]


def test_late_row_under_an_already_cleaned_watermark(h):
    _fill(h)
    h.commit()
    h.clean(1005)
    h.commit()
    h.insert(1002, 900)                 # late: its window is gone
    h.commit()                          # ... and it is committed again
    assert (1002, 900) in h.rows()
    assert h.clean(1005) == (1, 0)      # the same watermark finds it
    h.commit()
    h.insert(1004, 901)
    h.commit()
    assert h.clean(1006)[0] == 1 + sum(1 for k in range(40)
                                       if 1000 + k % 10 == 1005)
    h.commit()


def test_a_null_lead_sorts_below_every_watermark(h):
    _fill(h)
    h.insert(None, 700)
    h.insert(None, 701)
    h.commit()
    h.insert(None, 702)                 # one in the memtable too
    deleted, _read = h.clean(-5)        # below every timestamp held
    assert deleted == 3
    h.commit()
    h.insert(None, 703)
    h.commit()
    assert h.clean(1001)[0] == 1 + 4
    h.commit()


def test_an_unchanged_watermark_deletes_nothing(h):
    _fill(h)
    h.commit()
    assert h.clean(1004)[0] == 16
    assert h.clean(1004) == (0, 0)      # same epoch: the tombstones stand
    h.commit()
    assert h.clean(1004) == (0, 0)
    h.commit()
    assert h.clean(1000) == (0, 0)      # a watermark that went back
    assert len(h.rows()) == 24


@pytest.mark.parametrize("seed", [38, 3800000038, 7])
def test_random_sequences_equal_the_oracle(h, seed):
    rng = random.Random(seed)
    watermark, clock, next_k = 1000, 1000, 0
    cleans = 0
    for _step in range(400):
        roll = rng.random()
        live = sorted(h.model, key=repr)
        if roll < 0.45:
            late = rng.random() < 0.15
            ts = (None if rng.random() < 0.05 else
                  watermark - rng.randint(1, 20) if late else
                  clock + rng.randint(0, 12))
            h.insert(ts, next_k, rng.randint(0, 99))
            next_k += 1
        elif roll < 0.60 and live:
            h.update(rng.choice(live), rng.randint(100, 199))
        elif roll < 0.72 and live:
            h.delete(rng.choice(live))
        elif roll < 0.88:
            h.commit()
            clock += rng.randint(0, 6)
        else:
            if rng.random() < 0.8:
                watermark += rng.randint(0, 8)
            h.clean(watermark)
            cleans += 1
    h.commit()
    assert cleans > 20 and h.counter(METRICS.state_clean_seeds) == 1
    assert h.counter(METRICS.state_clean_index_keys) == len(h.model)


# -- (b) after the seeding clean a clean reads nothing ------------------------

def test_only_the_first_clean_reads_the_store(h, monkeypatch):
    _fill(h, n=60)
    h.commit()
    calls = []
    scan = h.store.iter

    def counted(*args, **kwargs):
        calls.append(args)
        return scan(*args, **kwargs)

    monkeypatch.setattr(h.store, "iter", counted)
    deleted, read = h.table.delete_below_prefix(1002)
    assert len(calls) == 1 and read == 60 and deleted == 12
    assert h.counter(METRICS.state_clean_reads) == 60
    assert h.counter(METRICS.state_clean_seeds) == 1
    sealed = h.table.epoch.curr
    h.table.commit(EpochPair(curr=sealed.next(), prev=sealed))
    cleaned = h.counter(METRICS.state_cleaned_rows)
    for watermark in (1004, 1004, 1007):
        h.table.insert((1001, 10_000 + watermark, 0))     # a late row
        assert h.table.delete_below_prefix(watermark)[1] == 0
        sealed = h.table.epoch.curr
        h.table.commit(EpochPair(curr=sealed.next(), prev=sealed))
    assert len(calls) == 1
    assert h.counter(METRICS.state_clean_reads) == 60
    assert h.counter(METRICS.state_clean_seeds) == 1
    assert h.counter(METRICS.state_cleaned_rows) == cleaned + 30 + 3


# -- (c) a table without an index seeds at its first clean ----------------

def test_a_new_table_over_the_same_store_reseeds(h):
    _fill(h)
    h.commit()
    h.clean(1002)
    h.commit()
    again = Harness(h.store, table_id=h.table_id, epoch=h.table.epoch,
                    model=h.model)
    assert again.table._clean_index is None       # recovery: no new step
    again.insert(1001, 800)
    deleted, read = again.clean(1004)
    assert read == 32 and deleted == 8 + 1
    assert h.counter(METRICS.state_clean_seeds) == 2
    again.commit()
    assert again.clean(1006) == (8, 0)
    again.commit()
    assert h.counter(METRICS.state_clean_seeds) == 2
    books = TOPOLOGY.cleaned_rows_of(h.table_id)
    assert h.counter(METRICS.state_clean_index_keys) == books == 16
    # ... and under these names in a row of rw_metrics_history
    row = {name: v for name, v, _kind in MetricsHistory()._batch_books()}
    assert row[f"state_clean_index.{h.label}.seeds"] == 2
    assert row[f"state_clean_index.{h.label}.keys"] == 16
    assert row[f"state_clean.{h.label}.reads"] == 40 + 32


def test_a_changed_vnode_bitmap_reseeds(h):
    _fill(h, n=120)
    h.commit()
    h.clean(1001)
    h.commit()
    assert h.counter(METRICS.state_clean_index_keys) == 108
    half = np.zeros(256, dtype=bool)
    half[::2] = True
    h.table.update_vnode_bitmap(half)
    assert h.table._clean_index is None
    mine = sum(1 for pk in h.model if h.owned(pk))
    assert 0 < mine < 108
    deleted, read = h.clean(1004)       # the oracle: owned vnodes only
    assert 0 < deleted < 36 and read >= mine
    h.commit()
    assert h.counter(METRICS.state_clean_seeds) == 2
    assert h.counter(METRICS.state_clean_index_keys) == mine - deleted
    h.table.update_vnode_bitmap(np.ones(256, dtype=bool))
    first = deleted
    deleted, read = h.clean(1004)       # the other half's, once owned
    assert deleted == 36 - first and read == len(h.model) + deleted
    h.commit()
    assert h.counter(METRICS.state_clean_seeds) == 3
    assert h.counter(METRICS.state_clean_index_keys) == len(h.model) == 72


# -- (d) a table no watermark cleans holds none --------------------------

def test_a_table_never_cleaned_holds_no_index(h):
    _fill(h)
    h.commit()
    h.update(sorted(h.model)[0], 5)
    h.delete(sorted(h.model)[1])
    h.commit()
    assert h.table._clean_index is None
    assert h.counter(METRICS.state_clean_seeds) == 0
    assert ({"table": h.label} not in
            [labels for labels, _v in METRICS.state_clean_index_keys.series()])
