"""Catalog: named sources / materialized views + id allocation.

Reference parity: src/meta/src/manager/catalog/mod.rs:135 (the meta
CatalogManager) + the frontend's read mirror — collapsed to one
in-process structure for the single-node deployment shape.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from risingwave_tpu.common.types import DataType, Schema


@dataclass
class SourceCatalog:
    name: str
    source_id: int
    schema: Schema
    options: Dict[str, str]
    # WATERMARK FOR <col> AS <col> - INTERVAL ...: (column, delay µs).
    # The one place the planner reads a source's watermark from
    watermark: Optional[Tuple[str, int]] = None


def source_watermark(schema: Schema, options: Dict[str, str],
                     clause: Optional[Tuple[str, int]]
                     ) -> Optional[Tuple[str, int]]:
    """A source's watermark as the catalog keeps it: the DDL's
    ``WATERMARK FOR`` clause, or its older spelling in the WITH list
    (``watermark.column`` / ``watermark.delay``, an alias). The column
    must be a timestamp of the source's schema."""
    from risingwave_tpu.frontend.binder import BindError
    opt_col = options.get("watermark.column")
    if clause is not None and opt_col is not None:
        raise BindError(
            "a source takes WATERMARK FOR or the watermark.column "
            "option, not both")
    if clause is None:
        if opt_col is None:
            return None
        clause = (opt_col.lower(), _interval_option_usecs(
            options.get("watermark.delay", "0 seconds")))
    col, delay = clause
    types = {f.name: f.data_type for f in schema}
    if col not in types:
        raise BindError(
            f"WATERMARK FOR {col}: the source has no such column")
    if types[col] not in (DataType.TIMESTAMP, DataType.TIMESTAMPTZ):
        raise BindError(
            f"WATERMARK FOR {col}: the column must be a timestamp, "
            f"not {types[col].name}")
    return col, int(delay)


def _interval_option_usecs(s: str) -> int:
    """'4 seconds' / '500 milliseconds' / a raw µs number. Shares the
    SQL parser's unit table (one source of truth)."""
    from risingwave_tpu.frontend.binder import BindError
    from risingwave_tpu.frontend.parser import _INTERVAL_UNITS
    parts = str(s).strip().split()
    if len(parts) == 2 and parts[0].isdigit() \
            and parts[1].lower() in _INTERVAL_UNITS:
        return int(parts[0]) * _INTERVAL_UNITS[parts[1].lower()]
    if len(parts) == 1 and parts[0].isdigit():
        return int(parts[0])
    raise BindError(f"bad interval option {s!r}")


@dataclass
class MvCatalog:
    name: str
    table_id: int
    schema: Schema
    pk_indices: List[int]
    definition: str
    actor_id: int = 0
    dependent_sources: List[str] = field(default_factory=list)
    # catalog id-counter value when this MV was planned: a reschedule
    # replans the same definition from the same base so every state
    # table gets its ORIGINAL id back (state survives the replan)
    id_base: int = -1
    # user-facing column count; trailing columns past it are hidden
    # plumbing (_row_id, unprojected group keys) that SELECT * and
    # downstream scopes must not expose (None = all visible)
    n_visible: Optional[int] = None
    # CREATE TABLE jobs share this registry; system catalogs and SHOW
    # split on it
    is_table: bool = False
    # planner-proved append-only changelog (no retractions ever):
    # sinks chained FROM this MV derive their mode from this proof
    # without re-walking the MV's executor tree
    append_only: bool = False

    @property
    def visible_schema(self) -> Schema:
        if self.n_visible is None:
            return self.schema
        return Schema(list(self.schema)[:self.n_visible])


@dataclass
class SinkCatalog:
    name: str
    actor_id: int
    options: Dict[str, str]
    definition: str = ""
    dependent_sources: List[str] = field(default_factory=list)
    # exactly-once epoch-segment sinks (connectors/sink.py): the
    # derived record mode and writer count, kept so ctl/rw_sinks can
    # rebuild the target from options without replanning
    mode: str = ""               # "append" | "upsert" | "" (legacy)
    n_writers: int = 1


class Catalog:
    def __init__(self) -> None:
        self.sources: Dict[str, SourceCatalog] = {}
        self.mvs: Dict[str, MvCatalog] = {}
        self.sinks: Dict[str, SinkCatalog] = {}
        self._next_id = 1

    def next_id(self) -> int:
        i = self._next_id
        self._next_id += 1
        return i

    def _check_free(self, name: str) -> None:
        if name in self.sources or name in self.mvs or name in self.sinks:
            raise ValueError(f"catalog object {name!r} already exists")

    def add_source(self, name: str, schema: Schema,
                   options: Dict[str, str],
                   watermark: Optional[Tuple[str, int]] = None
                   ) -> SourceCatalog:
        self._check_free(name)
        wm = source_watermark(schema, options, watermark)
        sc = SourceCatalog(name, self.next_id(), schema, options, wm)
        self.sources[name] = sc
        return sc

    def add_mv(self, mv: MvCatalog) -> None:
        self._check_free(mv.name)
        self.mvs[mv.name] = mv

    def add_sink(self, sk: SinkCatalog) -> None:
        self._check_free(sk.name)
        self.sinks[sk.name] = sk

    def resolve(self, name: str):
        if name in self.sources:
            return self.sources[name]
        if name in self.mvs:
            return self.mvs[name]
        raise KeyError(f"unknown relation {name!r}")
