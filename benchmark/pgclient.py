"""Simple-query pgwire v3 client (what psql sends). Copied from
`chip_smoke.PgClient` at PR 24, so that the yardstick does not move when
the smoke does."""

from __future__ import annotations

import asyncio
import datetime
import struct


def _parse_ts(text: str) -> int:
    dt = datetime.datetime.fromisoformat(text).replace(
        tzinfo=datetime.timezone.utc)
    return int(dt.timestamp()) * 1_000_000 + dt.microsecond


_BY_OID = {16: lambda s: s == "t", 20: int, 21: int, 23: int, 1700: int,
           700: float, 701: float, 1114: _parse_ts}


class PgClient:
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
        params = b"user\x00bench\x00database\x00dev\x00\x00"
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
