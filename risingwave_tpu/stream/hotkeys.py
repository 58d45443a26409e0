"""Heavy-hitter telemetry: space-saving sketches over join/agg keys.

"Parallelism won't help, the key is skewed" must be a named,
cross-checked verdict before the autoscaler spends a rescale on it
(ISSUE 16 / ROADMAP item 5). Every hash-join build/probe side and
hash-agg input feeds its chunk key lanes through a space-saving sketch
(Metwally et al.): k counters, an over-full insert evicts the minimum
counter and inherits its count as the new key's error bound. The
classic guarantees carry over: any key with true frequency above
``total/k`` is present, and every counter overestimates by at most its
recorded error — so with k=64 the share estimate for a genuinely hot
key (say the 90%-of-stream ad campaign) is exact to well under the
5pp acceptance bound, because evictions only ever recycle cold
counters.

The vectorization contract: the per-row work is NumPy (hash the
(n, 3·ncols) int32 key lanes to one int64 per row, ``np.unique`` the
visible ones); only the per-*unique* merge is a Python loop, capped at
``_PER_CHUNK`` entries per chunk. Keys stay as opaque hashes plus one
representative lane row on the hot path — decoding through the
executor's KeyCodec happens at read time (rw_hot_keys, ctl, walker).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

# sketch capacity (k) and the per-chunk unique-key merge cap (top-m by
# chunk count; dropping the chunk's own cold tail below m cannot demote
# a sustained heavy hitter)
K = 64
_PER_CHUNK = 128

# rw_hot_keys reports at most this many ranks per input; the walker's
# skew verdict threshold lives in stream/bottleneck.py
TOP_N = 8

# chunks whose unique passes are kept for take_batch (an executor that
# stages by the epoch drains them at every barrier; q4's epochs are 4)
_BATCH_CHUNKS = 256


class _Sketch:
    """One space-saving sketch over a single executor input."""

    __slots__ = ("counts", "errs", "lanes", "total", "codec", "mult",
                 "batch")

    def __init__(self) -> None:
        self.counts: Dict[int, int] = {}   # key hash -> est count
        self.errs: Dict[int, int] = {}     # key hash -> max overcount
        self.lanes: Dict[int, np.ndarray] = {}  # representative row
        self.total = 0                     # all observed rows
        self.codec = None                  # KeyCodec for display
        self.mult: Optional[np.ndarray] = None
        # (key hashes, counts) of every chunk since the last
        # take_batch(): the key skew of the batch a kernel stages
        self.batch: List[Tuple[np.ndarray, np.ndarray]] = []

    def observe(self, key_lanes: np.ndarray, vis: np.ndarray,
                codec) -> None:
        if self.codec is None:
            self.codec = codec
        lanes = key_lanes[vis] if vis is not None else key_lanes
        n = int(lanes.shape[0])
        if n == 0:
            return
        self.total += n
        if self.mult is None or self.mult.shape[0] != lanes.shape[1]:
            # fixed odd multipliers: a cheap universal-ish hash of the
            # (hi, lo, valid) lane columns down to one int64 per row.
            # Powers of one odd constant (wrapping): multiples of it,
            # (2k-1) x G, made two-column keys collide wherever
            # 3 x the first column's step + 9 x the second's was 0
            # (q4's (auction id, category): 772 hashes for 1,141 keys)
            self.mult = np.cumprod(np.full(
                lanes.shape[1], 0x9E3779B97F4A7C15, dtype=np.uint64)
            ).astype(np.int64)
        with np.errstate(over="ignore"):
            hashes = lanes.astype(np.int64) @ self.mult
        uniq, first, cnt = np.unique(hashes, return_index=True,
                                     return_counts=True)
        self.batch.append((uniq, cnt))
        del self.batch[:-_BATCH_CHUNKS]    # an input nobody drains
        if uniq.shape[0] > _PER_CHUNK:
            top = np.argpartition(cnt, -_PER_CHUNK)[-_PER_CHUNK:]
            uniq, first, cnt = uniq[top], first[top], cnt[top]
        counts = self.counts
        for h, idx, c in zip(uniq.tolist(), first.tolist(),
                             cnt.tolist()):
            cur = counts.get(h)
            if cur is not None:
                counts[h] = cur + c
                continue
            if len(counts) < K:
                counts[h] = c
                self.errs[h] = 0
                self.lanes[h] = np.array(lanes[idx])
                continue
            # evict the minimum counter; the newcomer inherits its
            # count as both floor and error bound (space-saving)
            victim = min(counts, key=counts.get)
            floor = counts.pop(victim)
            self.errs.pop(victim, None)
            self.lanes.pop(victim, None)
            counts[h] = floor + c
            self.errs[h] = floor
            self.lanes[h] = np.array(lanes[idx])

    def take_batch(self) -> Optional[Tuple[int, int, int]]:
        """(rows, distinct keys, rows of the most frequent key) over
        the chunks observed since the last call, from the unique
        passes `observe` made anyway; None where there were none."""
        parts, self.batch = self.batch, []
        if not parts:
            return None
        if len(parts) == 1:
            cnt = parts[0][1]
        else:
            _uniq, inv = np.unique(np.concatenate([u for u, _c in parts]),
                                   return_inverse=True)
            cnt = np.bincount(inv, weights=np.concatenate(
                [c for _u, c in parts]))
        return int(cnt.sum()), int(cnt.shape[0]), int(cnt.max())

    def top(self, n: int) -> List[Tuple[int, int, int]]:
        """[(hash, est_count, max_err)] by estimated count."""
        order = sorted(self.counts, key=self.counts.get, reverse=True)
        return [(h, self.counts[h], self.errs.get(h, 0))
                for h in order[:n]]

    def display(self, h: int) -> str:
        lane = self.lanes.get(h)
        if lane is None or self.codec is None:
            return f"#{h & 0xFFFFFFFF:08x}"
        try:
            cols = self.codec.decode(lane.reshape(1, -1))
            parts = []
            for values, valid in cols:
                v = values[0] if len(values) else None
                parts.append("NULL" if (len(valid) and not valid[0])
                             else str(v))
            return "|".join(parts)
        except Exception:               # noqa: BLE001 — display only
            return f"#{h & 0xFFFFFFFF:08x}"


class HotKeys:
    """Process-global registry of per-executor-input sketches."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sketches: Dict[str, _Sketch] = {}     # identity -> sketch
        self._fragment: Dict[str, str] = {}          # identity -> mv
        self._remote: Dict[str, List[tuple]] = {}    # worker -> rows

    # -- hot path -------------------------------------------------------
    def observe(self, identity: str, key_lanes, vis, codec) -> None:
        if key_lanes is None:
            return
        with self._lock:
            sk = self._sketches.get(identity)
            if sk is None:
                sk = self._sketches[identity] = _Sketch()
        sk.observe(np.asarray(key_lanes), vis, codec)

    def take_batch(self, identity: str
                   ) -> Optional[Tuple[int, int, int]]:
        """_Sketch.take_batch of one input, None if it was never
        observed."""
        with self._lock:
            sk = self._sketches.get(identity)
        return None if sk is None else sk.take_batch()

    def bind_fragment(self, identity: str, fragment: str) -> None:
        with self._lock:
            self._fragment[identity] = fragment

    # -- read side ------------------------------------------------------
    def hot_share(self, identity: str,
                  min_share: float = 0.0) -> Optional[Tuple[str, float]]:
        """(display_key, share) of the input's hottest key, if its
        *guaranteed* share (estimate minus error) clears min_share —
        the bottleneck walker's skew test. Conservative on purpose: a
        skew verdict vetoes a scale-up, so it must not fire on an
        overcounted cold key."""
        with self._lock:
            sks = [sk for i, sk in self._sketches.items()
                   if i == identity
                   or i.partition("/")[0] == identity]
        best = None
        for sk in sks:
            if sk.total == 0:
                continue
            top = sk.top(1)
            if not top:
                continue
            h, est, err = top[0]
            share = (est - err) / sk.total
            if share >= min_share and \
                    (best is None or share > best[1]):
                best = (sk.display(h), share)
        return best

    def rows(self) -> List[tuple]:
        """rw_hot_keys payload: (mv, executor, rank, key, est_count,
        share, max_share_err) — local sketches plus drained worker
        rows."""
        rows = self._local_rows()
        with self._lock:
            for remote in self._remote.values():
                rows.extend(remote)
        rows.sort(key=lambda r: (r[0], r[1], r[2]))
        return rows

    def _local_rows(self) -> List[tuple]:
        with self._lock:
            items = list(self._sketches.items())
            frag = dict(self._fragment)
        rows = []
        for identity, sk in items:
            if sk.total == 0:
                continue
            # join inputs suffix the executor identity ("/0", "/1") —
            # the fragment binding is on the base identity
            mv = frag.get(identity) \
                or frag.get(identity.partition("/")[0], "")
            for rank, (h, est, err) in enumerate(sk.top(TOP_N)):
                rows.append((mv, identity, rank, sk.display(h),
                             int(est), round(est / sk.total, 4),
                             round(err / sk.total, 4)))
        return rows

    # -- series lifecycle ----------------------------------------------
    def unregister_fragment(self, fragment: str) -> None:
        with self._lock:
            dead = {i for i, f in self._fragment.items()
                    if f == fragment}
            for i in dead:
                self._fragment.pop(i, None)
            for i in [s for s in self._sketches
                      if s in dead or s.partition("/")[0] in dead]:
                self._sketches.pop(i, None)
            self._remote = {
                w: [r for r in rows if r[0] != fragment]
                for w, rows in self._remote.items()}

    # -- cross-process merge (cluster `signals` drain) -------------------
    def drain_rows(self) -> List[tuple]:
        """Snapshot local rows, already decoded to primitives (an
        executor input lives in one process, so the coordinator can
        union worker snapshots without counter merging)."""
        return self._local_rows()

    def ingest(self, rows, worker: str = "") -> int:
        rows = [tuple(r) for r in rows]
        with self._lock:
            self._remote[worker] = rows
        return len(rows)

    def clear(self) -> None:
        with self._lock:
            self._sketches.clear()
            self._fragment.clear()
            self._remote.clear()


HOTKEYS = HotKeys()


def note_batch_books(kernel: str, identity: str, take_rounds) -> None:
    """One kernel's per-epoch books, written at the barrier from what
    the host already has (rw_metrics_history ``batch_skew.<kernel>.*``
    and ``probe_insert.<kernel>.*``): the key skew of the rows staged,
    from the sketch's unique passes over `identity`'s chunks, and the
    rounds probe_insert's loop took, which each device step returns
    with its insert count (`take_rounds`: the single-chip kernel's
    ``take_probe_rounds``; None for a kernel that has none to read)."""
    from risingwave_tpu.utils.metrics import STREAMING
    skew = HOTKEYS.take_batch(identity)
    if skew is not None:
        rows, distinct, max_key = skew
        STREAMING.batch_skew_rows.inc(rows, kernel=kernel)
        STREAMING.batch_skew_distinct.inc(distinct, kernel=kernel)
        STREAMING.batch_skew_max_key.set(max_key, kernel=kernel)
    elif STREAMING.batch_skew_max_key.get(kernel=kernel):
        STREAMING.batch_skew_max_key.set(0, kernel=kernel)  # none staged
    if take_rounds is not None:
        rounds, batches = take_rounds()
        if batches:
            STREAMING.probe_insert_rounds.inc(rounds, kernel=kernel)
            STREAMING.probe_insert_batches.inc(batches, kernel=kernel)
