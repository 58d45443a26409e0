#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served path starts on the chip.

One process holds the chip. It starts the single-process server through the
same function `python -m risingwave_tpu serve --data-dir D` runs
(`risingwave_tpu.__main__.serving`: hummock-lite on the local FS, Frontend,
recover(), pgwire listener, 0.25 s barrier heartbeat) and speaks pgwire to
it over a TCP socket.

With no arguments (one chip) it

  1. refuses to run unless `jax.devices()[0].platform == "tpu"`;
  2. loads Nexmark through `CREATE SOURCE ... connector='nexmark'` at the
     generator's own proportions (person:auction:bid 1:3:46) and row widths
     (strings on), 1,600,000 events per source (cut from 4,000,000, see
     FULL_EVENTS), data made from `--seed`;
  3. serves q7 (full form), q8 and `pairs` (a GROUP BY bidder, auction
     aggregate whose state grows with the stream, so the growth ladder
     runs; q7's join side is the device table that passes 1M keys), plus
     one small MV over DOUBLE columns (group key, MIN/MAX argument, join
     payload);
  4. after FLUSH reads every MV back over pgwire and compares it, as a
     multiset, with a plain numpy recompute from the generator functions;
  5. restarts the server on the same data dir and reads the same MVs back
     equal (an acknowledged checkpoint is read back; recovery re-uploads
     device state once);
  6. asserts that fusion fired for q7/q8, that no rewrite rule fell back,
     and that kernel recompiles stopped before the end of the load.

`--chips 4` runs only the sharded path and what it is compared with: the
windowed-MAX aggregate moved to a four-device mesh mid-stream by
`ALTER MATERIALIZED VIEW ... SET PARALLELISM = 4`, and q8 in a session built
at parallelism 4, each against the numpy reference and the same MV at
parallelism 1.

`--rehearse` shrinks the sizes and skips the "platform is tpu" check, so the
control flow can be run on the CPU (`JAX_PLATFORMS=cpu`, and for `--chips 4`
`XLA_FLAGS=--xla_force_host_platform_device_count=4`). Nothing it prints is
a device number.

Every phase that fails raises: the exit code is then non-zero and the result
line is not printed. The last line of standard output is the result,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import dataclasses
import datetime
import json
import os
import struct
import sys
import tempfile
import time

import numpy as np

WINDOW_US = 10_000_000
ASKED_EVENTS = 4_000_000         # per source, what ISSUE 22 asks for
# Cut to 1.6M (event.num only; proportions, row widths, chunking and every
# guarantee as asked): with a checkpoint on every barrier the inline LSM
# compaction rewrites the whole, growing level on the commit path (64 s
# per pass in Python at 2M events), so load time grows with the square of
# the stream: 2M events did not finish loading in 900 s in a CPU rehearsal
# in the builder's sandbox (PR 22; sizing only, no device number), and the
# script has 1200 s, compiles included. 1.6M is the smallest round size at
# which one device table (q7's join side, 1,068,567 keys) passes 1M keys.
# Per source: 1.472M bids, 96K auctions, 32K persons.
FULL_EVENTS = 1_600_000
REHEARSE_EVENTS = 250_000         # 25 s of event time: three 10 s windows
FLOAT_ROWS = 20_000
FLOAT_KEYS = 37
CHUNK_ROWS = 4096
# chunks per barrier per source, the one SET (the rehearsal's is small so
# that its few rows still span several barriers)
RATE_LIMIT, REHEARSE_RATE_LIMIT = 32, 2
MIN_DEVICE_KEYS = 1_000_000      # one device hash table must hold this many
LOAD_DEADLINE_S = 900.0

NEXMARK_SOURCE = (
    "CREATE SOURCE {t} WITH (connector='nexmark', "
    "nexmark.table.type='{t}', nexmark.event.num={n}, "
    "nexmark.max.chunk.size={chunk}, nexmark.seed={seed})")

# tests/test_corpus.py: the full q7 (bids that equal their window's max)
Q7 = ("CREATE MATERIALIZED VIEW q7 AS "
      "SELECT b.auction, b.price, b.bidder, b.date_time "
      "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) AS b "
      "JOIN (SELECT MAX(price) AS maxprice, window_start AS ws "
      "      FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
      "      GROUP BY window_start) AS m "
      "ON b.window_start = m.ws AND b.price = m.maxprice")

# tests/test_cluster_sql.py: q8 and the windowed-MAX core of q7
Q8 = ("CREATE MATERIALIZED VIEW {name} AS "
      "SELECT p.id, p.name, p.window_start "
      "FROM TUMBLE(person, date_time, INTERVAL '10' SECOND) AS p "
      "JOIN TUMBLE(auction, date_time, INTERVAL '10' SECOND) AS a "
      "ON p.id = a.seller AND p.window_start = a.window_start")
Q7_CORE = ("CREATE MATERIALIZED VIEW {name} AS "
           "SELECT window_start, MAX(price) AS max_price, COUNT(*) AS cnt "
           "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
           "GROUP BY window_start")

# watermarks retire q7/q8's closed windows, so their resident state stays
# small at any scale; this one keeps every group it has ever seen
PAIRS = ("CREATE MATERIALIZED VIEW pairs AS "
         "SELECT bidder, auction, COUNT(*) AS n, MAX(price) AS top, "
         "SUM(price) AS total FROM bid GROUP BY bidder, auction")

def _datagen_options(event_num: int, seed: int, **fields) -> dict:
    opts = {"connector": "datagen", "datagen.event.num": event_num,
            "datagen.seed": seed}
    for name, props in fields.items():
        for prop, val in props.items():
            opts[f"fields.{name}.{prop}"] = val
    return opts


def float_sources(rows: int, seed: int) -> dict:
    """WITH options of the two datagen sources behind the float MV."""
    return {
        "ticks": _datagen_options(
            rows, seed,
            id={"type": "bigint", "kind": "sequence"},
            k={"type": "bigint", "kind": "sequence", "start": 0,
               "end": FLOAT_KEYS},
            level={"type": "double", "kind": "sequence", "start": -3,
                   "end": 4},
            px={"type": "double", "kind": "random", "min": -1000,
                "max": 1000}),
        "fees": _datagen_options(
            FLOAT_KEYS, seed,
            k={"type": "bigint", "kind": "sequence"},
            fee={"type": "double", "kind": "random", "min": -1,
                 "max": 1}),
    }


def create_source_sql(name: str, options: dict) -> str:
    return (f"CREATE SOURCE {name} WITH ("
            + ", ".join(f"{k}='{v}'" for k, v in options.items()) + ")")


# DOUBLE group key, DOUBLE MIN and MAX arguments, and a join whose both
# sides carry DOUBLE payload columns: the three places the fused prelude
# used to bitcast f64 to a 64-bit integer
FLOAT_MV = ("CREATE MATERIALIZED VIEW fl AS "
            "SELECT t.level, MIN(f.fee) AS lo, MAX(t.px) AS hi, "
            "COUNT(*) AS n FROM ticks AS t JOIN fees AS f ON t.k = f.k "
            "GROUP BY t.level")


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


T0 = time.monotonic()


# -- pgwire client ----------------------------------------------------------


def _parse_ts(text: str) -> int:
    dt = datetime.datetime.fromisoformat(text).replace(
        tzinfo=datetime.timezone.utc)
    return int(dt.timestamp()) * 1_000_000 + dt.microsecond


_BY_OID = {16: lambda s: s == "t", 20: int, 21: int, 23: int, 1700: int,
           700: float, 701: float, 1114: _parse_ts}


class PgClient:
    """Simple-query pgwire v3 client (what psql sends)."""

    def __init__(self, reader, writer):
        self.r, self.w = reader, writer

    @classmethod
    async def connect(cls, port: int) -> "PgClient":
        r, w = await asyncio.open_connection("127.0.0.1", port)
        c = cls(r, w)
        w.write(struct.pack(">II", 8, 80877103))        # SSL probe
        await w.drain()
        if await r.readexactly(1) != b"N":
            raise RuntimeError("server did not decline SSL")
        params = b"user\x00smoke\x00database\x00dev\x00\x00"
        w.write(struct.pack(">II", 8 + len(params), 196608) + params)
        await w.drain()
        await c._until_ready()
        return c

    async def _until_ready(self):
        out = []
        while True:
            hdr = await self.r.readexactly(5)
            body = await self.r.readexactly(
                struct.unpack(">I", hdr[1:5])[0] - 4)
            out.append((hdr[:1], body))
            if hdr[:1] == b"Z":
                return out

    async def query(self, sql: str):
        """Run one statement; rows typed by the RowDescription's OIDs
        (a command returns its tag). ErrorResponse raises."""
        body = sql.encode() + b"\x00"
        self.w.write(b"Q" + struct.pack(">I", len(body) + 4) + body)
        await self.w.drain()
        conv, rows, tag = [], [], None
        for t, p in await self._until_ready():
            if t == b"E":
                raise RuntimeError(f"server error for {sql[:60]!r}: "
                                   f"{p.decode(errors='replace')}")
            if t == b"T":
                n, pos = struct.unpack(">H", p[:2])[0], 2
                for _ in range(n):
                    pos = p.index(b"\x00", pos) + 1
                    oid = struct.unpack(">IHIhih", p[pos:pos + 18])[2]
                    conv.append(_BY_OID.get(oid, str))
                    pos += 18
            elif t == b"D":
                n, pos, row = struct.unpack(">H", p[:2])[0], 2, []
                for i in range(n):
                    ln = struct.unpack(">i", p[pos:pos + 4])[0]
                    pos += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(conv[i](p[pos:pos + ln].decode()))
                        pos += ln
                rows.append(tuple(row))
            elif t == b"C":
                tag = p.rstrip(b"\x00").decode()
        return rows if conv else tag

    async def __aenter__(self) -> "PgClient":
        return self

    async def __aexit__(self, *_exc) -> None:
        # the listener's close() waits for its connections: always hang up
        self.w.write(b"X" + struct.pack(">I", 4))
        self.w.close()


# -- the plain reference: numpy over the generator functions -----------------


def _nexmark_columns(seed: int, events: int):
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig, gen_auctions, gen_bids, gen_persons,
    )
    cfg = NexmarkConfig(event_num=events, seed=seed)
    # the reference reads only the bids' numeric columns, which do not
    # depend on the string switch; the server's sources keep strings on
    bids = gen_bids(np.arange(events * 46 // 50, dtype=np.int64),
                    dataclasses.replace(cfg, generate_strings=False))
    aucs = gen_auctions(np.arange(events * 3 // 50, dtype=np.int64), cfg)
    pers = gen_persons(np.arange(events // 50, dtype=np.int64), cfg)
    return bids, aucs, pers


def _group_starts(*sorted_keys):
    """Start offsets of the runs of equal key tuples in sorted arrays."""
    change = np.zeros(len(sorted_keys[0]), dtype=bool)
    change[0] = True
    for k in sorted_keys:
        change[1:] |= k[1:] != k[:-1]
    return np.flatnonzero(change)


def ref_q7_core(bids) -> collections.Counter:
    win = bids["date_time"] // WINDOW_US * WINDOW_US
    order = np.argsort(win, kind="stable")
    starts = _group_starts(win[order])
    wmax = np.maximum.reduceat(bids["price"][order], starts)
    cnt = np.diff(np.append(starts, len(win)))
    return collections.Counter(zip(win[order][starts].tolist(),
                                   wmax.tolist(), cnt.tolist()))


def ref_q7(bids) -> collections.Counter:
    win = bids["date_time"] // WINDOW_US * WINDOW_US
    order = np.argsort(win, kind="stable")
    starts = _group_starts(win[order])
    wmax = np.maximum.reduceat(bids["price"][order], starts)
    row_max = np.empty_like(wmax, shape=len(win))
    row_max[order] = np.repeat(wmax, np.diff(np.append(starts, len(win))))
    top = bids["price"] == row_max
    return collections.Counter(zip(
        bids["auction"][top].tolist(), bids["price"][top].tolist(),
        bids["bidder"][top].tolist(), bids["date_time"][top].tolist()))


def ref_pairs(bids) -> collections.Counter:
    order = np.lexsort((bids["auction"], bids["bidder"]))
    b, a, p = (bids[c][order] for c in ("bidder", "auction", "price"))
    starts = _group_starts(b, a)
    return collections.Counter(zip(
        b[starts].tolist(), a[starts].tolist(),
        np.diff(np.append(starts, len(b))).tolist(),
        np.maximum.reduceat(p, starts).tolist(),
        np.add.reduceat(p, starts).tolist()))


def ref_q8(aucs, pers) -> collections.Counter:
    """person JOIN auction ON id = seller AND same 10 s window: one output
    row per matching auction."""
    sellers = collections.Counter(zip(
        aucs["seller"].tolist(),
        (aucs["date_time"] // WINDOW_US * WINDOW_US).tolist()))
    out = collections.Counter()
    for pid, name, w in zip(
            pers["id"].tolist(), pers["name"].tolist(),
            (pers["date_time"] // WINDOW_US * WINDOW_US).tolist()):
        n = sellers.get((pid, w), 0)
        if n:
            out[(pid, str(name), w)] = n
    return out


def ref_float(seed: int, rows: int) -> collections.Counter:
    from risingwave_tpu.connectors.datagen import DatagenConfig, gen_rows

    def columns(options):
        cfg = DatagenConfig.from_options(
            {k: str(v) for k, v in options.items()})
        return gen_rows(np.arange(cfg.event_num, dtype=np.int64), cfg)

    src = float_sources(rows, seed)
    ticks, fees = columns(src["ticks"]), columns(src["fees"])
    fee_of = dict(zip(fees["k"].tolist(), fees["fee"].tolist()))
    acc = {}
    for k, level, px in zip(ticks["k"].tolist(), ticks["level"].tolist(),
                            ticks["px"].tolist()):
        fee = fee_of[k]
        lo, hi, n = acc.get(level, (fee, px, 0))
        acc[level] = (min(lo, fee), max(hi, px), n + 1)
    return collections.Counter(
        (level, lo, hi, n) for level, (lo, hi, n) in acc.items())


def check_equal(name: str, got_rows, want: collections.Counter,
                phase: str) -> None:
    got = collections.Counter(got_rows)
    if got != want:
        missing = list((want - got).items())[:3]
        extra = list((got - want).items())[:3]
        raise AssertionError(
            f"{phase}: {name} differs from the reference: "
            f"{sum(got.values())} rows vs {sum(want.values())}; "
            f"missing {missing}, unexpected {extra}")
    say(f"{phase}: {name} equals the reference "
        f"({sum(want.values())} rows, {len(want)} distinct)")


# -- what the process counts ------------------------------------------------


class Counts:
    """Process-wide counters the phases read as deltas."""

    def __init__(self):
        from jax import monitoring
        self.xla_compiles = 0          # persistent-cache misses
        self.cache_hits = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.xla_compiles += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_dur(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    @staticmethod
    def _sum(metric) -> int:
        return int(sum(v for _labels, v in metric.series()))

    def source_rows(self) -> int:
        from risingwave_tpu.utils.metrics import STREAMING
        return self._sum(STREAMING.source_rows)

    def traces(self, kernel: str | None = None) -> int:
        """stream_kernel_recompile_count: jit (re)traces, of one kernel
        label or of all."""
        from risingwave_tpu.utils.metrics import STREAMING
        return int(sum(
            v for labels, v in STREAMING.kernel_recompile.series()
            if kernel is None or labels.get("kernel") == kernel))

    def checkpoints(self) -> int:
        from risingwave_tpu.utils.metrics import STREAMING
        return self._sum(STREAMING.checkpoint_count)


async def wait_loaded(pg: PgClient, hb, counts: Counts, base_rows: int,
                      expect_rows: int, label: str, exact: bool = True):
    """Poll until the sources have produced `expect_rows` more rows and a
    FLUSH later produced none. Returns the (rows, traces) samples.
    `exact=False` admits more rows: a rescheduled job reads again what
    it had read since its last checkpoint."""
    samples = []
    t_start = last_say = time.monotonic()
    while True:
        if hb.done():
            hb.result()
            raise RuntimeError("barrier heartbeat stopped")
        rows = counts.source_rows() - base_rows
        samples.append((rows, counts.traces()))
        now = time.monotonic()
        if rows >= expect_rows:
            await pg.query("FLUSH")
            if counts.source_rows() - base_rows == rows:
                break
        if now - t_start > LOAD_DEADLINE_S:
            raise TimeoutError(
                f"{label}: {rows} of {expect_rows} source rows after "
                f"{LOAD_DEADLINE_S:.0f} s")
        if now - last_say > 15:
            last_say = now
            say(f"{label}: {rows}/{expect_rows} source rows, "
                f"{counts.traces()} kernel traces")
        await asyncio.sleep(0.25)
    if exact and rows != expect_rows:
        raise AssertionError(f"{label}: sources produced {rows} rows, "
                             f"expected {expect_rows}")
    await pg.query("FLUSH")
    say(f"{label}: {rows} source rows in "
        f"{time.monotonic() - t_start:.1f} s (wall, host clock)")
    return samples


def assert_traces_settled(samples, label: str, enforce: bool) -> None:
    """stream_kernel_recompile_count must stop growing towards the end
    of the load. This load's state grows with the stream by design, and
    every growth rung of a device table retraces the programs that take
    it, wherever in the stream it falls; shape churn, the regression this
    guards against, would retrace on every barrier instead. So: while the
    last fifth of the rows was read, at most a quarter of all traces."""
    total = samples[-1][0]
    at = [next(t for r, t in samples if r >= f * total)
          for f in (0.2, 0.4, 0.6, 0.8, 1.0)]
    say(f"{label}: kernel traces at 20/40/60/80/100% of the rows: "
        + "/".join(map(str, at)))
    if enforce and 4 * (at[-1] - at[-2]) > at[-1]:
        raise AssertionError(
            f"{label}: {at[-1] - at[-2]} of {at[-1]} kernel traces fell "
            "in the last fifth of the load: shapes are churning")


async def assert_fusion(pg: PgClient, jobs) -> None:
    rows = await pg.query("SELECT job, rule, fired, detail "
                          "FROM rw_plan_rewrites")
    fallbacks = [r for r in rows if r[3].startswith("FALLBACK")]
    if fallbacks:
        raise AssertionError(f"rewrite rules fell back: {fallbacks}")
    for job in jobs:
        fused = [r for r in rows
                 if r[0] == job and r[1] == "fusion_grouping" and r[2] > 0]
        if not fused:
            raise AssertionError(f"fusion did not fire for {job}: {rows}")
        say(f"fusion fired for {job}: {fused[0][3]}")


def blocking_read_ms(n: int = 40):
    """One blocking device->host read of a small array that is already
    computed: the median and the largest of `n` readings."""
    import jax.numpy as jnp
    times = []
    for i in range(n):
        x = (jnp.arange(8, dtype=jnp.int32) + i).block_until_ready()
        t = time.perf_counter()
        np.asarray(x)
        times.append((time.perf_counter() - t) * 1e3)
    return float(np.median(times)), max(times)


def walk_executors(ex):
    if ex is None:
        return
    yield ex
    for attr in ("input", "left_in", "right_in"):
        yield from walk_executors(getattr(ex, attr, None))


def kernels_of(ex):
    """The device kernels an executor owns."""
    for attr in ("kernel", "_kernel"):
        if getattr(ex, attr, None) is not None:
            yield getattr(ex, attr)
    for side in getattr(ex, "sides", ()):
        yield side.kernel


def all_kernels(fe):
    """(owning MV, kernel) of every device kernel, each once (a
    monitoring wrapper exposes its executor's kernel again)."""
    owner = {m.actor_id: m.name for m in fe.catalog.mvs.values()}
    seen = {}
    for aid, actor in fe.actors.items():
        for ex in walk_executors(actor.consumer):
            for k in kernels_of(ex):
                seen.setdefault(id(k), (owner.get(aid, f"actor {aid}"), k))
    return list(seen.values())


def device_tables(fe):
    """(owning MV, kernel, occupied slots, capacity) of every one-chip
    device hash table, read from the device."""
    import jax.numpy as jnp
    out = []
    for owner, k in all_kernels(fe):
        table = k.state.table if hasattr(k, "state") else k.table.state
        out.append((owner, type(k).__name__, int(jnp.sum(table.occ)),
                    int(table.occ.shape[0])))
    return out


def sharded_kernels(fe, kernel_type):
    return [k for _owner, k in all_kernels(fe)
            if isinstance(k, kernel_type)]


def assert_on_four_devices(kernels, state_attrs, label: str) -> None:
    import jax
    if not kernels:
        raise AssertionError(f"{label}: no sharded kernel in the plan")
    for k in kernels:
        leaves = [a for attr in state_attrs
                  for a in jax.tree.leaves(getattr(k, attr))]
        for a in leaves:
            ids = {d.id for d in a.sharding.device_set}
            if len(ids) != 4:
                raise AssertionError(
                    f"{label}: a state array of {type(k).__name__} lives "
                    f"on devices {sorted(ids)}, not on four")
    say(f"{label}: {len(kernels)} sharded kernel(s), every state array "
        f"on four distinct devices")


# -- the one-chip run ---------------------------------------------------------


async def run_one_chip(args, counts: Counts) -> None:
    import jax

    from risingwave_tpu.__main__ import serving

    events = REHEARSE_EVENTS if args.rehearse else FULL_EVENTS
    rate_limit = REHEARSE_RATE_LIMIT if args.rehearse else RATE_LIMIT
    float_rows = FLOAT_ROWS // 10 if args.rehearse else FLOAT_ROWS
    n_bid, n_auc, n_per = (events * 46 // 50, events * 3 // 50,
                           events // 50)
    say(f"events per source: {events} ({n_bid} bids, {n_auc} auctions, "
        f"{n_per} persons); cut from the scale: "
        + ("rehearsal size" if args.rehearse else
           f"nexmark.event.num {ASKED_EVENTS} -> {FULL_EVENTS}, nothing "
           "else (the inline LSM compaction rewrites the growing state on "
           "the commit path, load time grows with the square of the "
           "stream, and 2M events and up do not fit 1200 s)"))
    med, worst = blocking_read_ms()
    say(f"blocking device->host read of int32[8]: median {med:.4f} ms, "
        f"max {worst:.4f} ms (host clock, 40 readings)")

    say("computing the numpy reference")
    bids, aucs, pers = _nexmark_columns(args.seed, events)
    want = {"q7": ref_q7(bids), "q8": ref_q8(aucs, pers),
            "pairs": ref_pairs(bids),
            "fl": ref_float(args.seed, float_rows)}
    del bids, aucs, pers

    with tempfile.TemporaryDirectory(prefix="rw_smoke_") as data_dir:
        base_rows, base_ckpt = counts.source_rows(), counts.checkpoints()
        async with serving(data_dir, port=0) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            await pg.query(f"SET streaming_rate_limit = {rate_limit}")
            shown = [f"{k}={(await pg.query('SHOW ' + k))[0][0]}"
                     for k in ("stream_fusion", "stream_rewrite_rules")]
            say(f"SET streaming_rate_limit = {rate_limit} (chunks per "
                f"barrier per source, {CHUNK_ROWS}-row chunks); every "
                "other session setting at its default: "
                + ", ".join(shown))
            for t in ("bid", "auction", "person"):
                await pg.query(NEXMARK_SOURCE.format(
                    t=t, n=events, chunk=CHUNK_ROWS, seed=args.seed))
            for name, options in float_sources(float_rows,
                                               args.seed).items():
                await pg.query(create_source_sql(name, options))
            for ddl in (Q7, Q8.format(name="q8"), PAIRS, FLOAT_MV):
                await pg.query(ddl)
                say("created " + ddl.split(" AS ")[0].split()[-1])
            # q7 reads bid twice (join side and aggregate), pairs once
            expect = 3 * n_bid + n_auc + n_per + float_rows + FLOAT_KEYS
            samples = await wait_loaded(pg, hb, counts, base_rows, expect,
                                        "load")
            assert_traces_settled(samples, "load",
                                  enforce=not args.rehearse)
            for name in want:
                check_equal(name, await pg.query(f"SELECT * FROM {name}"),
                            want[name], "served")
            await assert_fusion(pg, ("q7", "q8", "fl"))
            say(f"events ingested: {samples[-1][0]} source rows; "
                f"checkpoints committed: "
                f"{counts.checkpoints() - base_ckpt}")
            tables = device_tables(fe)
            say("resident device keys (occupied slots / capacity of each "
                "device hash table): " + ", ".join(
                    f"{owner} {kind} {occ}/{cap}"
                    for owner, kind, occ, cap in tables))
            grown = max(occ for _o, _k, occ, _c in tables)
            say(f"pairs: {len(want['pairs'])} live keys; the fullest "
                f"device table holds {grown}; hash_agg.grow traced "
                f"{counts.traces('hash_agg.grow')} time(s)")
            if not args.rehearse and grown < MIN_DEVICE_KEYS:
                raise AssertionError(
                    f"no device table holds {MIN_DEVICE_KEYS} keys")
            if not args.rehearse and counts.traces("hash_agg.grow") == 0:
                raise AssertionError("the growth ladder did not run")
        await fe.close()
        say("session closed; restarting on the same data dir")

        t_rec = time.monotonic()
        async with serving(data_dir, port=0) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            say(f"recover() replayed the DDL log and re-uploaded device "
                f"state in {time.monotonic() - t_rec:.1f} s "
                "(wall, host clock)")
            await pg.query("FLUSH")
            for name in want:
                check_equal(name, await pg.query(f"SELECT * FROM {name}"),
                            want[name], "after restart")
        await fe.close()
    stats = jax.devices()[0].memory_stats() or {}
    say("device peak_bytes_in_use: "
        + str(stats.get("peak_bytes_in_use", "not reported")))


# -- the four-chip run: the sharded path and what it is compared with --------


async def run_four_chips(args, counts: Counts) -> None:
    from risingwave_tpu.__main__ import serving
    from risingwave_tpu.parallel.agg import ShardedAggKernel
    from risingwave_tpu.parallel.join import ShardedJoinKernel

    events = REHEARSE_EVENTS if args.rehearse else FULL_EVENTS
    rate_limit = REHEARSE_RATE_LIMIT if args.rehearse else RATE_LIMIT
    n_bid, n_auc, n_per = (events * 46 // 50, events * 3 // 50,
                           events // 50)
    say(f"events per source: {events}; SET streaming_rate_limit = "
        f"{rate_limit}")
    bids, aucs, pers = _nexmark_columns(args.seed, events)
    want_q7, want_q8 = ref_q7_core(bids), ref_q8(aucs, pers)
    del bids, aucs, pers

    def sources(names):
        return [NEXMARK_SOURCE.format(t=t, n=events, chunk=CHUNK_ROWS,
                                      seed=args.seed) for t in names]

    with tempfile.TemporaryDirectory(prefix="rw_smoke_") as d1, \
            tempfile.TemporaryDirectory(prefix="rw_smoke_") as d2:
        # session 1, built at parallelism 1: the references at
        # parallelism 1, and the aggregate that ALTER moves to the mesh
        base = counts.source_rows()
        async with serving(d1, port=0) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            await pg.query(f"SET streaming_rate_limit = {rate_limit}")
            for s in sources(("bid", "auction", "person")):
                await pg.query(s)
            await pg.query(Q7_CORE.format(name="q7_p1"))
            await pg.query(Q8.format(name="q8_p1"))
            await pg.query(Q7_CORE.format(name="q7_mesh"))
            before = 0
            while before < n_bid // 4:
                if hb.done():
                    hb.result()
                await asyncio.sleep(0.05)
                seen = await pg.query("SELECT SUM(cnt) FROM q7_mesh")
                before = int(seen[0][0]) if seen else 0
            await pg.query(
                "ALTER MATERIALIZED VIEW q7_mesh SET PARALLELISM = 4")
            say(f"ALTER ... SET PARALLELISM = 4 on q7_mesh mid-stream "
                f"(after {before} of {n_bid} bids)")
            if before >= n_bid:
                raise AssertionError("the reschedule was not mid-stream")
            assert_on_four_devices(sharded_kernels(fe, ShardedAggKernel),
                                   ("state",), "q7_mesh")
            expect = 2 * n_bid + n_auc + n_per
            await wait_loaded(pg, hb, counts, base, expect,
                              "parallelism 1 + ALTER", exact=False)
            assert_on_four_devices(sharded_kernels(fe, ShardedAggKernel),
                                   ("state",), "q7_mesh after the load")
            p1 = {n: await pg.query(f"SELECT * FROM {n}")
                  for n in ("q7_p1", "q8_p1", "q7_mesh")}
            check_equal("q7_p1", p1["q7_p1"], want_q7, "parallelism 1")
            check_equal("q8_p1", p1["q8_p1"], want_q8, "parallelism 1")
            check_equal("q7_mesh", p1["q7_mesh"], want_q7,
                        "ALTER to the mesh")
            check_equal("q7_mesh", p1["q7_mesh"],
                        collections.Counter(p1["q7_p1"]),
                        "ALTER to the mesh vs parallelism 1")
        await fe.close()

        # session 2, built at parallelism 4: ALTER does not cover join
        # fragments, so the sharded join is planned from the start
        base = counts.source_rows()
        async with serving(d2, port=0, parallelism=4) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            await pg.query(f"SET streaming_rate_limit = {rate_limit}")
            for s in sources(("auction", "person")):
                await pg.query(s)
            await pg.query(Q8.format(name="q8_mesh"))
            assert_on_four_devices(sharded_kernels(fe, ShardedJoinKernel),
                                   ("table", "chains"), "q8_mesh")
            await wait_loaded(pg, hb, counts, base, n_auc + n_per,
                              "parallelism 4")
            assert_on_four_devices(sharded_kernels(fe, ShardedJoinKernel),
                                   ("table", "chains"),
                                   "q8_mesh after the load")
            got = await pg.query("SELECT * FROM q8_mesh")
            check_equal("q8_mesh", got, want_q8, "parallelism 4")
            check_equal("q8_mesh", got, collections.Counter(p1["q8_p1"]),
                        "parallelism 4 vs parallelism 1")
            await assert_fusion(pg, ("q8_mesh",))
        await fe.close()


# -- entry -------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from risingwave_tpu import native
    from risingwave_tpu.utils.jaxtools import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: the platform is {dev['platform']!r}, not a "
              "TPU; this check runs on the chip only (--rehearse runs "
              "the control flow at a tiny size elsewhere)",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {dev['count']}", file=sys.stderr)
        return 2
    say(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})"
        + (" REHEARSAL: no number below is a device number"
           if args.rehearse else ""))
    say(f"compile cache: {cache_dir} held {cached} entries at start "
        f"({'warm' if cached else 'cold'})")
    say("SST codec: " + ("so" if native.lib() is not None else "python"))

    counts = Counts()
    run = run_four_chips if args.chips == 4 else run_one_chip
    asyncio.run(run(args, counts))
    say(f"{'warm' if cached else 'cold'} wall time "
        f"{time.monotonic() - T0:.1f} s (host clock); XLA compiles "
        f"{counts.xla_compiles} ({counts.compile_s:.1f} s), persistent "
        f"cache hits {counts.cache_hits}, kernel traces {counts.traces()}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
