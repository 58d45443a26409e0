"""StateTable: schema-aware, vnode-partitioned view over the state store.

Reference parity: src/stream/src/common/table/state_table.rs:76 —
write API insert/delete/update (:746,760,773) buffered in a MemTable;
``commit(new_epoch)`` (:901) flushes the buffer at the sealed epoch;
read API get_row (:587) and iterators (:1092); per-table vnode ownership
bitmap + update_vnode_bitmap on scaling (:650).

TPU re-design: this is the *host-side durability seam*. Device-resident
operator state (HBM hash tables) flushes dirty entries through this API at
every barrier; recovery reads it back to rebuild device state. Keys are
2-byte-vnode-prefixed memcomparable bytes; values are host row tuples.

Rows are PHYSICAL tuples: DECIMAL is its scaled int64, timestamps are µs
ints, NULL is None — the exact representation device kernels flush and
recovery re-uploads (no host conversion on the hot path). Present rows to
users via ``to_logical_row``.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.common.hash import (
    VNODE_COUNT, hash_strings_host, vnodes_of_host,
)
from risingwave_tpu.common.types import DataType, Schema, scaled_to_decimal
from risingwave_tpu.state.keycodec import (
    NULL_KEY, decode_memcomparable, encode_fixed_column,
    encode_host_column, encode_memcomparable, encode_vnode_prefix,
    encoded_width,
)
from risingwave_tpu.state.mem_table import KeyOp, MemTable
from risingwave_tpu.state.store import StateStore
from risingwave_tpu.state import topology as _topology
from risingwave_tpu.utils.ledger import staged
from risingwave_tpu.utils.metrics import STREAMING as _METRICS

# which encoder made each state-table key: the columnar one (every bulk
# entry point, counted by the batch) or the scalar one (point operations)
_PK_COLUMNAR = _METRICS.state_pk_keys.labeled(path="columnar")
_PK_ROW = _METRICS.state_pk_keys.labeled(path="row")

# One pk column of a batch as the key encoder takes it: the values (an
# array of the type's np_dtype for a device type, float64 also for the
# floats; any sequence of Python objects for a host type) and their
# validity (None: every value is valid; an invalid slot may hold anything).
KeyColumn = Tuple[Sequence, Optional[np.ndarray]]

# barrier-domain mode (meta/domains.py flips this on when a
# BarrierPlane exists in the process; workers flip it on the first
# domain-protocol inject): commit() then accepts the MONOTONE epoch
# re-anchor a domain merge produces. Off (the default and the
# single-loop oracle arm), strict prev == curr continuity is enforced
# so a missed barrier fails at the fault. Sticky per process.
MONOTONE_REANCHOR = False


def allow_monotone_reanchor(on: bool = True) -> None:
    global MONOTONE_REANCHOR
    MONOTONE_REANCHOR = bool(on)


class StateTable:
    """One logical table of operator state, partitioned by vnode."""

    def __init__(self, table_id: int, schema: Schema,
                 pk_indices: Sequence[int], store: StateStore,
                 dist_key_indices: Optional[Sequence[int]] = None,
                 vnodes: Optional[np.ndarray] = None,
                 sanity_check: bool = True):
        self.table_id = table_id
        self.schema = schema
        self.pk_indices = list(pk_indices)
        self.pk_types = [schema[i].data_type for i in self.pk_indices]
        # dist keys must be a subset of the pk so vnode is derivable from pk
        self.dist_key_indices = (list(dist_key_indices)
                                 if dist_key_indices is not None else [])
        for i in self.dist_key_indices:
            assert i in self.pk_indices, \
                "dist key must be part of the state-table pk"
        # where each dist key sits in the pk
        self._dist_pos = [self.pk_indices.index(i)
                          for i in self.dist_key_indices]
        self.store = store
        self.mem_table = MemTable(sanity_check=sanity_check)
        # staged all-insert chunk batches (write_chunk(defer=True) —
        # the materialize/join emit hot path): encoded keys + physical
        # rows held OUTSIDE the memtable until flush, skipping the
        # per-row op-merge dict entirely. Invariant: staged batches
        # exist only while the memtable is CLEAN — any interleaved
        # read or non-insert write spills them into the memtable
        # first, restoring the exact merge semantics.
        self._staged_keys: List[List[bytes]] = []
        self._staged_vals: List[List[tuple]] = []
        # ownership bitmap: which vnodes this instance owns (scaling swaps it)
        self.vnodes = (np.ones(VNODE_COUNT, dtype=bool)
                       if vnodes is None else np.asarray(vnodes, dtype=bool))
        self.epoch: Optional[EpochPair] = None
        # schema-constant physical row size (None when host-typed
        # fields size per value) — lets the topology books take their
        # bulk-update fast path on the staged all-insert flush shape
        self._fixed_row_nbytes = _topology.fixed_row_nbytes(schema)
        # the clean index: the table's committed keys bucketed by the
        # encoded bytes of the leading pk column (tag and payload), so
        # that a watermark's range delete finds its rows without
        # reading the store. None until the first delete_below_prefix
        # seeds it; commit keeps it from then on; a table no watermark
        # cleans never has one
        self._clean_index: Optional[Dict[bytes, Set[bytes]]] = None
        # where the leading column's bytes end in a key of a non-null
        # lead (None for a varchar or bytea lead: no watermark is one,
        # and such a table is never range-cleaned)
        width = encoded_width(self.pk_types[0]) if self.pk_types else None
        self._lead_end = None if width is None else 2 + width

    # -- epoch lifecycle ------------------------------------------------
    def init_epoch(self, epoch: EpochPair) -> None:
        """Set the epoch at which buffered writes will land (recovery/boot)."""
        self.epoch = epoch

    def flush(self) -> Tuple[List[bytes], List, int]:
        """Drain the buffered ops as staged imms — (keys, values,
        write_epoch) — WITHOUT writing through to the store.
        Extraction point only: ``commit`` below is its one caller
        today (the async checkpoint pipeline decouples at the STORE
        level — HummockLite.build_ssts drains imms, not state tables);
        callers that need to route a flush elsewhere (worker shipping,
        tests) take the staged batch from here."""
        assert self.epoch is not None, "init_epoch first"
        if self._staged_keys:
            if self.mem_table.is_dirty():
                # defensive: the staged-while-clean invariant should
                # make this unreachable — merge order-exactly anyway
                self._spill_staged()
            else:
                kbs, vbs = self._staged_keys, self._staged_vals
                self._staged_keys, self._staged_vals = [], []
                if len(kbs) == 1:
                    return kbs[0], vbs[0], self.epoch.curr.value
                return ([k for b in kbs for k in b],
                        [v for b in vbs for v in b],
                        self.epoch.curr.value)
        keys, vals = self.mem_table.drain_bulk()
        return keys, vals, self.epoch.curr.value

    @staged("state.commit")
    def commit(self, new_epoch: EpochPair) -> int:
        """Flush buffered ops at the sealed (current) epoch; advance.

        Returns the number of flushed entries. state_table.rs:901 analog —
        the caller (actor on barrier) invokes this for every state table,
        then the barrier manager seals the epoch and hands the flush to
        the checkpoint uploader.
        """
        assert self.epoch is not None, "init_epoch first"
        if MONOTONE_REANCHOR:
            # barrier-domain mode (meta/domains.py): ``>`` happens at
            # a domain MERGE/re-anchor — the absorbed chain continues
            # under the merged loop, whose prev is the larger
            # frontier; the buffered writes still flush at the OLD
            # curr, which stays under the cross-domain seal fence
            # until the merged round ends it, so monotone re-anchoring
            # is safe
            assert new_epoch.prev.value >= self.epoch.curr.value, \
                (new_epoch, self.epoch)
        else:
            # strict continuity (the single-loop/off arm): a prev
            # mismatch means a missed barrier — fail at the fault,
            # not at a later opaque sealed-write rejection
            assert new_epoch.prev == self.epoch.curr, \
                (new_epoch, self.epoch)
        keys, vals, epoch = self.flush()
        n = self.store.ingest_keyed(self.table_id, keys, vals, epoch)
        # per-(table, vnode) topology upkeep rides the SAME flush the
        # store ingests — incremental at the write-through point, so
        # reads (rw_state_topology, rescale costing) never scan state
        _topology.TOPOLOGY.record(self.table_id, keys, vals,
                                  self._fixed_row_nbytes)
        if self._clean_index is not None:
            self._index_flushed(keys, vals)
        resident = _topology.TOPOLOGY.cleaned_rows_of(self.table_id)
        if resident is not None:
            # a table a watermark cleans says at every commit what it
            # keeps: the books' total, no scan
            _METRICS.state_resident_rows.set(
                resident, table=f"t{self.table_id}")
        self.epoch = new_epoch
        return n

    # -- key helpers ----------------------------------------------------
    def _vnode_of_pk(self, pk_values: Sequence) -> int:
        if not self._dist_pos:
            return 0  # singleton distribution (VirtualNode::ZERO analog)
        lanes = [_key_lane(pk_values[p], self.pk_types[p])
                 for p in self._dist_pos]
        return int(vnodes_of_host(lanes)[0])

    def _encode_pk(self, pk_values: Sequence) -> bytes:
        """One key by the scalar codec: the point operations' encoder,
        and the reference the columnar encoder is held to."""
        _PK_ROW.inc()
        vnode = self._vnode_of_pk(pk_values)
        return (encode_vnode_prefix(vnode) +
                encode_memcomparable(pk_values, self.pk_types))

    def pk_of(self, row: Sequence) -> tuple:
        return tuple(row[i] for i in self.pk_indices)

    # -- staged-batch spill (write_chunk(defer=True) fast path) ----------
    def is_dirty(self) -> bool:
        return bool(self._staged_keys) or self.mem_table.is_dirty()

    def _spill_staged(self) -> None:
        """Replay staged all-insert batches into the memtable (in
        arrival order) so interleaved reads/non-insert writes see the
        exact per-key merge semantics the fast path skipped."""
        if not self._staged_keys:
            return
        kbs, vbs = self._staged_keys, self._staged_vals
        self._staged_keys, self._staged_vals = [], []
        mt = self.mem_table
        for keys, rows in zip(kbs, vbs):
            if not mt.insert_batch(keys, rows):
                for key, row in zip(keys, rows):
                    mt.insert(key, row)

    # -- write API -------------------------------------------------------
    def insert(self, row: Sequence) -> None:
        self._spill_staged()
        row = tuple(row)
        self.mem_table.insert(self._encode_pk(self.pk_of(row)), row)

    def delete(self, row: Sequence) -> None:
        self._spill_staged()
        row = tuple(row)
        self.mem_table.delete(self._encode_pk(self.pk_of(row)), row)

    def update(self, old_row: Sequence, new_row: Sequence) -> None:
        self._spill_staged()
        old_row, new_row = tuple(old_row), tuple(new_row)
        ok, nk = self._encode_pk(self.pk_of(old_row)), \
            self._encode_pk(self.pk_of(new_row))
        if ok == nk:
            self.mem_table.update(ok, old_row, new_row)
        else:  # pk changed: delete + insert (reference requires same pk; we allow)
            self.mem_table.delete(ok, old_row)
            self.mem_table.insert(nk, new_row)

    @staged("state.clean")
    def delete_below_prefix(self, watermark) -> Tuple[int, int]:
        """Watermark state cleaning (state_table.rs:894 update_watermark):
        delete every row whose FIRST pk column is strictly below the
        watermark. Returns (rows deleted, rows read from the store).

        The committed rows below the watermark are not read back: they
        are rows this table committed, and the clean index holds their
        keys by the leading column's encoded bytes. The buckets that
        sort under the watermark's encoding (bytewise, as the range
        end of a scan would: a NULL lead sorts first) give them; one
        pass over the memtable adds this epoch's inserts below the
        watermark and takes out its deletes; each doomed key gets its
        tombstone through the memtable, at this epoch. Cost is the
        distinct leading values the table holds + O(deleted) + the
        memtable's pass. The keys leave their buckets at the commit
        that flushes the tombstones, like any other delete, so the
        index is the store's keys at the read epoch whatever comes
        between.

        Only the FIRST clean of a table reads the store: one ordered
        scan seeds the index (``_seed_clean_index``), and the books say
        so (``stream_state_clean_reads`` counts what a seeding scan
        read, ``stream_state_clean_seeds`` the scans, beside
        ``_cleaned_rows``). One ``state.clean`` stage of host_emit."""
        assert self._lead_end is not None, \
            "a range delete needs a fixed-width leading key column"
        end_suffix = encode_memcomparable([watermark], [self.pk_types[0]])
        self._spill_staged()
        label = f"t{self.table_id}"
        read = 0
        if self._clean_index is None:
            read = self._seed_clean_index()
            _METRICS.state_clean_seeds.inc(table=label)
            _METRICS.state_clean_reads.inc(float(read), table=label)
        doomed: Set[bytes] = set().union(*(
            keys for lead, keys in self._clean_index.items()
            if lead < end_suffix))
        # a key is its vnode's two bytes, then the pk: below the
        # watermark where what follows the vnode sorts under it
        owned = self.vnodes
        for key, (op, _old, _new) in self.mem_table.items():
            if key[2:] < end_suffix and owned[(key[0] << 8) | key[1]]:
                if op == KeyOp.DELETE:
                    doomed.discard(key)
                else:
                    doomed.add(key)
        self.mem_table.delete_batch(doomed)
        deleted = len(doomed)
        _METRICS.state_cleaned_rows.inc(float(deleted), table=label)
        self.note_cleaned(watermark)
        return deleted, read

    def _seed_clean_index(self) -> int:
        """Build the clean index from the store: ONE ordered scan over
        the table's owned vnodes at the read epoch (this epoch's writes
        are in the memtable and enter at their commit). Returns the
        rows the scan read."""
        self._clean_index = {}
        owned_vnodes = self.owned_vnodes()
        if not owned_vnodes:
            return 0
        last = owned_vnodes[-1] + 1
        keys = [key for key, _row in self.store.iter(
            self.table_id, self._read_epoch(),
            encode_vnode_prefix(owned_vnodes[0]),
            encode_vnode_prefix(last) if last < VNODE_COUNT else None)]
        read = len(keys)
        if len(owned_vnodes) < last - owned_vnodes[0]:
            owned = self.vnodes
            keys = [k for k in keys if owned[(k[0] << 8) | k[1]]]
        self._index_flushed(keys, keys)
        return read

    def _index_flushed(self, keys: List[bytes], vals: List) -> None:
        """The clean index follows a flush (and takes the seeding
        scan's keys the same way): a tombstone leaves its bucket,
        anything else enters it (the same ``bytes`` the store was
        handed). Then the gauge says what the index holds: the books'
        rows for the table."""
        index = self._clean_index
        lead_end = self._lead_end
        for key, val in zip(keys, vals):
            lead = key[2:lead_end] if key[2] else NULL_KEY
            bucket = index.get(lead)
            if val is not None:
                if bucket is None:
                    index[lead] = {key}
                else:
                    bucket.add(key)
            elif bucket is not None:
                bucket.discard(key)
                if not bucket:
                    del index[lead]
        _METRICS.state_clean_index_keys.set(
            sum(map(len, index.values())), table=f"t{self.table_id}")

    def note_cleaned(self, watermark) -> None:
        """The table holds no row below ``watermark`` any more on the
        column its operator cleans it on: the leading key column of
        this range delete, or the join key of a join side's expiry,
        which deletes its rows itself. ``stream_watermark`` and
        ``rw_watermarks`` say so."""
        _METRICS.state_watermark.set(float(watermark),
                                     table=f"t{self.table_id}")
        _topology.TOPOLOGY.note_cleaned(self.table_id, watermark)

    # -- bulk row API (barrier-flush hot path for device operators) -----
    # Each bulk entry point (and write_chunk) is one `state.write` stage
    # of host_emit: key encoding, value rows, memtable insert. The
    # single-row insert/delete/update above are not: row loops call them.
    # ``pk_cols``, where a caller gives it, is the rows' pk columns as it
    # already holds them (one ``KeyColumn`` per pk column, in pk order,
    # equal to the rows' own values); otherwise they are taken from the rows.
    @staged("state.write")
    def insert_rows(self, rows: Sequence[Sequence],
                    pk_cols: Optional[Sequence[KeyColumn]] = None) -> None:
        """Batch insert: vnodes and keys by the column, whatever the
        pk's types (one numpy pass per fixed-width column, one pass of
        the codec per varchar column)."""
        self._spill_staged()
        mt = self.mem_table
        keys = self._encode_pk_rows(rows, pk_cols)
        rows_t = [tuple(r) for r in rows]
        if mt.insert_batch(keys, rows_t):
            return
        for key, row in zip(keys, rows_t):
            mt.insert(key, row)

    @staged("state.write")
    def delete_rows(self, rows: Sequence[Sequence],
                    pk_cols: Optional[Sequence[KeyColumn]] = None) -> None:
        self._spill_staged()
        mt = self.mem_table
        for key, row in zip(self._encode_pk_rows(rows, pk_cols), rows):
            mt.delete(key, tuple(row))

    @staged("state.write")
    def delete_keys(self, pk_cols: Sequence[KeyColumn], n: int) -> None:
        """Batch delete of ``n`` rows by their pk columns alone: the
        caller holds columns and builds no row (a join side's
        watermark expiry). The tombstones carry no old row."""
        self._spill_staged()
        self.mem_table.delete_batch(self._encode_key_columns(pk_cols, n))

    @staged("state.write")
    def update_rows(self, old_rows: Sequence[Sequence],
                    new_rows: Sequence[Sequence],
                    pk_cols: Optional[Sequence[KeyColumn]] = None) -> None:
        """Batch update. With ``pk_cols`` every pair keeps its pk (they
        are the columns of both sides); without, the keys are encoded
        once where the two sides' pk columns are equal, else per side."""
        self._spill_staged()
        mt = self.mem_table
        if pk_cols is None:
            old_pk, new_pk = self._pk_lists(old_rows), self._pk_lists(new_rows)
            ok_keys = self._encode_key_columns(
                self._key_columns_of_lists(old_pk), len(old_rows))
            nk_keys = ok_keys if new_pk == old_pk else \
                self._encode_key_columns(
                    self._key_columns_of_lists(new_pk), len(new_rows))
        else:
            ok_keys = nk_keys = self._encode_key_columns(pk_cols,
                                                         len(old_rows))
        for ok, nk, old, new in zip(ok_keys, nk_keys, old_rows, new_rows):
            old, new = tuple(old), tuple(new)
            if ok == nk:
                mt.update(ok, old, new)
            else:
                mt.delete(ok, old)
                mt.insert(nk, new)

    def _encode_pk_rows(self, rows: Sequence[Sequence],
                        pk_cols: Optional[Sequence[KeyColumn]] = None
                        ) -> List[bytes]:
        """Vnode-prefixed pk keys of row tuples."""
        if pk_cols is None:
            pk_cols = self._key_columns_of_lists(self._pk_lists(rows))
        return self._encode_key_columns(pk_cols, len(rows))

    def _pk_lists(self, rows: Sequence[Sequence]) -> List[list]:
        return [[r[i] for r in rows] for i in self.pk_indices]

    def _key_columns_of_lists(self, pk_lists: Sequence[list]
                              ) -> List[KeyColumn]:
        """Physical pk values by column (None is NULL) → key columns."""
        cols: List[KeyColumn] = []
        for col, dt in zip(pk_lists, self.pk_types):
            valid = None
            if dt.is_device:
                if None in col:
                    valid = np.fromiter((v is not None for v in col),
                                        dtype=bool, count=len(col))
                    col = [0 if v is None else v for v in col]
                # a row's float is a Python float whatever the column's
                # width: float64 keeps the bits the scalar codec packs
                floating = dt in (DataType.FLOAT32, DataType.FLOAT64)
                col = np.asarray(
                    col, dtype=np.float64 if floating else dt.np_dtype)
            cols.append((col, valid))
        return cols

    @staged("state.write")
    def write_chunk(self, chunk: StreamChunk,
                    defer: bool = False) -> None:
        """Apply a visible-row StreamChunk — the barrier-flush hot path.

        Fully vectorized up to the memtable: physical row extraction, vnode
        hashing and pk encoding are whole-column numpy passes; only the
        final dict ops are per-row.

        ``defer=True`` (ISSUE 12): all-insert chunks against a clean
        memtable STAGE as (keys, rows) batches and flow to the store as
        one bulk ingest at flush — no per-row memtable dict ops at all.
        Only callers that trust upstream key discipline (the NO_CHECK
        materialize contract, the join's append-fast state writes) pass
        it: the fast path skips the memtable's double-insert sanity
        check, and duplicate pks within one epoch resolve last-wins at
        the store instead of raising. Any interleaved read, delete, or
        row-API write spills the stage first, so mixed epochs keep the
        exact merge semantics.
        """
        idx, rows, ops = chunk.to_physical_records()
        if not rows:
            return
        keys = self._encode_pks_bulk(chunk, idx)
        is_ins = (ops == int(Op.INSERT)) | (ops == int(Op.UPDATE_INSERT))
        if defer and not self.mem_table.is_dirty() and is_ins.all():
            self._staged_keys.append(keys)
            self._staged_vals.append(rows)
            return
        self._spill_staged()
        mt = self.mem_table
        if is_ins.all() and mt.insert_batch(keys, rows):
            return
        for key, row, ins in zip(keys, rows, is_ins.tolist()):
            if ins:
                mt.insert(key, row)
            else:
                mt.delete(key, row)

    def _encode_pks_bulk(self, chunk: StreamChunk,
                         idx: np.ndarray) -> List[bytes]:
        """Vnode-prefixed pk keys of a chunk's visible rows."""
        cols: List[KeyColumn] = []
        for i in self.pk_indices:
            c = chunk.columns[i]
            cols.append((np.asarray(c.values)[idx],
                         None if c.validity is None
                         else np.asarray(c.validity)[idx]))
        return self._encode_key_columns(cols, len(idx))

    def _encode_key_columns(self, cols: Sequence[KeyColumn],
                            n: int) -> List[bytes]:
        """The one bulk key encoder: pk columns → vnode-prefixed
        memcomparable keys, byte for byte what ``_encode_pk`` gives row
        by row (the keys are the on-disk format, the scan order and the
        vnode partition). What it does with a column depends on the
        column alone: its type and which of its values are NULL.

        Vnodes: one ``vnodes_of_host`` over the dist-key columns, the
        same math as the device dispatch; a device-typed column hashes
        as it is, a NULL as the zero lane (``_key_lane``'s rule), a
        host-typed column through ``hash_strings_host``.

        Bytes: runs of non-null fixed-width columns are packed as one
        byte matrix, the vnode prefix leading the first; a fixed-width
        column with NULLs gets ``0x00`` and no payload in those rows; a
        host-typed column goes through the scalar codec once, as a
        column. A key that is not one run is one join per row."""
        if n == 0:
            return []
        _PK_COLUMNAR.inc(n)
        norm: List[KeyColumn] = []
        for (vals, valid), dt in zip(cols, self.pk_types):
            if valid is not None and valid.all():
                valid = None
            if dt.is_device:
                vals = np.asarray(vals)
                if valid is not None:
                    vals = np.where(valid, vals,
                                    np.zeros((), dtype=vals.dtype))
            elif valid is not None:
                vals = np.array(vals, dtype=object)
                vals[~valid] = None
                valid = None
            norm.append((vals, valid))

        if not self._dist_pos:
            vnodes = np.zeros(n, dtype=np.int64)
        else:
            lanes = [norm[p][0] if self.pk_types[p].is_device
                     else hash_strings_host(norm[p][0], n)
                     for p in self._dist_pos]
            vnodes = vnodes_of_host(lanes).astype(np.int64)

        prefix = np.empty((n, 2), dtype=np.uint8)
        prefix[:, 0] = vnodes >> 8
        prefix[:, 1] = vnodes & 0xFF
        pieces: List[List[bytes]] = []   # per-row bytes, left to right
        run: List[np.ndarray] = [prefix]
        for (vals, valid), dt in zip(norm, self.pk_types):
            if dt.is_device and valid is None:
                run.append(encode_fixed_column(vals, dt))
                continue
            if run:
                pieces.append(_matrix_rows(run))
                run = []
            if dt.is_device:
                piece = _matrix_rows([encode_fixed_column(vals, dt)])
                for j in np.flatnonzero(~valid).tolist():
                    piece[j] = NULL_KEY
            else:
                piece = encode_host_column(vals, dt)
            pieces.append(piece)
        if run:
            pieces.append(_matrix_rows(run))
        if len(pieces) == 1:
            return pieces[0]
        return list(map(b"".join, zip(*pieces)))

    # -- read API --------------------------------------------------------
    def _read_epoch(self) -> int:
        assert self.epoch is not None, "init_epoch first"
        return self.epoch.prev.value

    def get_row(self, pk_values: Sequence) -> Optional[tuple]:
        self._spill_staged()
        key = self._encode_pk(tuple(pk_values))
        present, value = self.mem_table.get(key)
        if present:
            return value
        return self.store.get(self.table_id, key, self._read_epoch())

    def iter_rows(self, vnode: Optional[int] = None,
                  reverse: bool = False
                  ) -> Iterator[Tuple[tuple, tuple]]:
        """Yield (pk, row) in memcomparable pk order (descending with
        `reverse=True` — the backward iterator), memtable merged.

        v0 correctness-first: materializes the committed range then overlays
        buffered ops (the in-memory fake is small; hummock-lite gets a real
        merge iterator).
        """
        if vnode is None:
            start, end = None, None
        else:
            start = encode_vnode_prefix(vnode)
            end = encode_vnode_prefix(vnode + 1) if vnode + 1 < VNODE_COUNT \
                else None
        yield from self._iter_range(start, end, reverse=reverse)

    def iter_prefix(self, prefix_values: Sequence
                    ) -> Iterator[Tuple[tuple, tuple]]:
        """(pk, row) for every pk starting with the given leading pk
        values (state_table.rs:1092 prefix iterators). The prefix must
        cover the dist keys so the vnode is derivable."""
        k = len(prefix_values)
        for i in self.dist_key_indices:
            assert self.pk_indices.index(i) < k, \
                "prefix must include all dist keys"
        vnode = self._vnode_of_pk(
            list(prefix_values) + [None] * (len(self.pk_indices) - k))
        start = (encode_vnode_prefix(vnode) +
                 encode_memcomparable(prefix_values, self.pk_types[:k]))
        yield from self._iter_range(start, _next_prefix(start))

    def _iter_range_raw(self, start: Optional[bytes],
                        end: Optional[bytes], reverse: bool = False
                        ) -> Iterator[Tuple[bytes, tuple]]:
        self._spill_staged()
        merged = {k: v for k, v in self.store.iter(
            self.table_id, self._read_epoch(), start, end)}
        for key, (op, _old, new) in self.mem_table.iter_ops():
            if start is not None and key < start:
                continue
            if end is not None and key >= end:
                continue
            if op == KeyOp.DELETE:
                merged.pop(key, None)
            else:
                merged[key] = new
        for key in sorted(merged, reverse=reverse):
            yield key, merged[key]

    def _iter_range(self, start: Optional[bytes], end: Optional[bytes],
                    reverse: bool = False
                    ) -> Iterator[Tuple[tuple, tuple]]:
        for key, row in self._iter_range_raw(start, end, reverse):
            yield decode_memcomparable(key[2:], self.pk_types), row

    def iter_encoded_range(self, start: Optional[bytes] = None,
                           end: Optional[bytes] = None
                           ) -> Iterator[Tuple[bytes, tuple]]:
        """(full encoded key incl. vnode prefix, row) in byte order —
        the backfill scan order (vnode-major, then memcomparable pk)."""
        yield from self._iter_range_raw(start, end)

    def owned_vnodes(self) -> List[int]:
        return np.flatnonzero(self.vnodes).tolist()

    # -- scaling ---------------------------------------------------------
    def update_vnode_bitmap(self, new_vnodes: np.ndarray) -> np.ndarray:
        """Swap partition ownership at a barrier (state_table.rs:650)."""
        assert not self.is_dirty(), \
            "vnode bitmap swap with dirty memtable"
        prev = self.vnodes
        self.vnodes = np.asarray(new_vnodes, dtype=bool)
        # the clean index held the old ownership's keys: the next
        # clean seeds it anew
        self._clean_index = None
        return prev


def _next_prefix(b: bytes) -> Optional[bytes]:
    """Smallest byte string greater than every string prefixed by b."""
    arr = bytearray(b)
    while arr:
        if arr[-1] != 0xFF:
            arr[-1] += 1
            return bytes(arr)
        arr.pop()
    return None


def _matrix_rows(parts: Sequence[np.ndarray]) -> List[bytes]:
    """uint8 matrices of equal height, side by side → one bytes per row."""
    m = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
    n, width = m.shape
    flat = m.tobytes()
    return [flat[i * width:(i + 1) * width] for i in range(n)]


def _key_lane(v, dt: DataType) -> np.ndarray:
    """One physical scalar → length-1 lane array (device hashing rules).

    NULL hashes as the zero lane — consistent with the bulk encoder's
    treatment of invalid slots, so a NULL dist-key row is addressable."""
    if dt.is_device:
        return np.asarray([0 if v is None else v], dtype=dt.np_dtype)
    return hash_strings_host(np.asarray([v], dtype=object), 1)


def to_logical_row(row: Sequence, schema: Schema) -> tuple:
    """Physical state-table row → logical values (DECIMAL → Decimal)."""
    out = []
    for v, f in zip(row, schema):
        if v is not None and f.data_type == DataType.DECIMAL:
            v = scaled_to_decimal(v)
        out.append(v)
    return tuple(out)
