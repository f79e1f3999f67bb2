"""Session: the connExecutor analog — full statement dispatch (DDL, DML,
SET/SHOW session vars, SELECT/EXPLAIN) over a mutable MVCC catalog.

Reference: sql/conn_executor.go (execCmd :2408 dispatching statement
kinds), sql/catalog/descs (table descriptors persisted in a system
table), vectorized INSERT (colexec/insert.go), row writers (sql/row),
session vars (sql/vars.go — the three-tier config's middle tier,
SURVEY.md §5.6).

Storage mapping: a table descriptor (id, columns, types, growing string
dictionaries, next rowid) is a JSON value in the descriptor system
keyspace; rows are fixed-width int64 tuples keyed by an int64 primary
key (explicit INT PRIMARY KEY column, else a hidden auto rowid).
Mutations run through kv.Txn — serializable, validated at commit.
"""

from __future__ import annotations

import datetime
import itertools
import json
import struct
import threading
from collections import OrderedDict
from decimal import Decimal, ROUND_HALF_UP
from typing import Dict, List, Optional, Tuple

import numpy as np

from cockroach_tpu.coldata.batch import (
    BOOL, ColType, DATE, DECIMAL, FLOAT, Field, INT, Kind, STRING,
    Schema, VECTOR,
)
from cockroach_tpu.kv.txn import DB, TxnRetryError
from cockroach_tpu.sql import params as _params
from cockroach_tpu.sql import parser as P
from cockroach_tpu.sql.bind import BindError, Binder
from cockroach_tpu.sql.plan import Catalog
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.hlc import Timestamp
from cockroach_tpu.util.settings import Settings

DESC_TABLE = 0xFFE0  # descriptor system keyspace (system.descriptor)

SLOW_QUERY_LATENCY = Settings.register(
    "sql.log.slow_query_latency",
    0.0,
    "statements slower than this (seconds) log a structured SQL_EXEC "
    "slow_query event; 0 disables",
)

SLOW_QUERY_INTERVAL = Settings.register(
    "sql.log.slow_query_interval",
    0.0,
    "minimum seconds between slow_query events for the same statement "
    "fingerprint (rate limit, so high-rate batched workloads can't "
    "flood SQL_EXEC); 0 logs every occurrence",
)

STATEMENT_TIMEOUT = Settings.register(
    "sql.defaults.statement_timeout",
    0.0,
    "default per-statement execution deadline in seconds (overridable "
    "per session via SET statement_timeout); a statement exceeding it "
    "aborts with SQLSTATE 57014 query_canceled; 0 disables",
)

# executions of a fingerprint before its baseline is handed to the
# statement's trace as its usual time (insights' min_samples default)
_USUAL_MIN_SAMPLES = 5

# slow-query rate-limit state: fingerprint -> last log time (monotonic).
# Process-wide, like the log channel it protects.
_slow_log_mu = threading.Lock()
_slow_log_last: Dict[str, float] = {}


class SQLError(Exception):
    """An execution error carrying a PostgreSQL SQLSTATE code — pgwire
    sends `pgcode` in the ErrorResponse 'C' field so drivers can branch
    on the class (40001 -> client retry loop, 53xxx -> resource alarm)
    instead of string-matching Python tracebacks."""

    def __init__(self, pgcode: str, msg: str):
        super().__init__(msg)
        self.pgcode = pgcode


def map_execution_error(e: BaseException) -> Optional[SQLError]:
    """Translate engine-internal failures to wire-facing SQL errors
    (reference: pgerror codes on colexecerror panics). Memory-budget trips
    become 53200 out_of_memory; exhausted restart/retry budgets become
    40001 serialization_failure — the statement is safe for the CLIENT to
    retry; a statement `distsql = always` cannot distribute becomes 0A000.
    Anything else keeps its Python identity (BindError et al. are already
    user-facing)."""
    from cockroach_tpu.exec.fused import Unsupported
    from cockroach_tpu.exec.operators import FlowRestart
    from cockroach_tpu.util.cancel import QueryCancelled
    from cockroach_tpu.util.mon import BudgetExceededError
    from cockroach_tpu.util.retry import RetriesExhausted

    if isinstance(e, QueryCancelled):
        # 57014 query_canceled: CancelRequest or statement_timeout; the
        # statement is dead but the SESSION stays usable
        return SQLError("57014", f"query canceled: {e}")
    if isinstance(e, BudgetExceededError):
        return SQLError("53200", f"out of memory: {e}")
    if isinstance(e, FlowRestart):
        return SQLError(
            "40001",
            f"restart statement: flow restart budget exhausted ({e})")
    if isinstance(e, RetriesExhausted):
        return SQLError("40001", f"restart statement: {e}")
    if isinstance(e, Unsupported):
        # the single-chip ladder answers an Unsupported itself; the one
        # that comes out is the distributed runner's under
        # `distsql = always` (0A000 feature_not_supported)
        return SQLError(
            "0A000", f"distsql = always: the distributed runner does not "
            f"take this statement ({e})")
    return None


def _type_of(name: str) -> ColType:
    if name.startswith("decimal("):
        return DECIMAL(int(name[8:-1]))
    if name.startswith("vector("):
        return VECTOR(int(name[7:-1]))
    return {"int": INT, "float": FLOAT, "date": DATE,
            "string": STRING, "bool": BOOL}[name]


def _type_name(ty: ColType) -> str:
    if ty.kind is Kind.DECIMAL:
        return f"decimal({ty.scale})"
    if ty.kind is Kind.VECTOR:
        return f"vector({ty.dim})"
    return {Kind.INT: "int", Kind.FLOAT: "float", Kind.DATE: "date",
            Kind.STRING: "string", Kind.BOOL: "bool"}[ty.kind]


def _slots_of(tname: str) -> int:
    """Physical int64 slots a value column occupies in the row codec:
    VECTOR(d) packs d float32 bit patterns into d slots (the codec is
    exact int64 lanes; the low 32 bits of each slot carry one lane)."""
    return int(tname[7:-1]) if tname.startswith("vector(") else 1


def _slots_to_f32(rows: np.ndarray) -> np.ndarray:
    """(n, d) int64 slot matrix -> (n, d) float32 (low-32-bit bitcast)."""
    return np.ascontiguousarray(rows.astype(np.uint32)).view(np.float32)


class TableDescriptor:
    def __init__(self, table_id: int, name: str,
                 columns: List[Tuple[str, str]], pk: Optional[str],
                 dicts: Optional[Dict[str, List[str]]] = None,
                 next_rowid: int = 1, row_count: int = 0,
                 indexes: Optional[Dict[str, int]] = None,
                 notnull: Optional[List[str]] = None,
                 dropped: Optional[List[str]] = None,
                 backfilling: Optional[str] = None):
        self.table_id = table_id
        self.name = name
        # secondary indexes: indexed column -> index table id. Entries
        # live at pk64 = (value+2^31) << 32 | rowid (value/rowid must fit
        # 32 bits — the engine key codec is (table u16, pk u64)); fields
        # = [rowid, value]. NULL values have no index entry.
        self.indexes: Dict[str, int] = dict(indexes or {})
        self.columns = columns  # [(name, type_name)] — stored order
        self.pk = pk            # None = hidden rowid
        self.notnull = list(notnull or [])  # declared NOT NULL columns
        # schema-change states (schemachanger/: columns keep their
        # PHYSICAL slot forever; visibility is descriptor state):
        # dropped = slots whose column was ALTER TABLE DROPped;
        # backfilling = an ADDed column not yet public (job running)
        self.dropped = list(dropped or [])
        self.backfilling = backfilling
        self.dicts = dicts or {c: [] for c, t in columns if t == "string"}
        self.next_rowid = next_rowid
        self.row_count = row_count  # stats estimate for join ordering

    def encode(self) -> bytes:
        return json.dumps({
            "table_id": self.table_id, "name": self.name,
            "columns": self.columns, "pk": self.pk, "dicts": self.dicts,
            "next_rowid": self.next_rowid,
            "row_count": self.row_count,
            "indexes": self.indexes,
            "notnull": self.notnull,
            "dropped": self.dropped,
            "backfilling": self.backfilling}, sort_keys=True).encode()

    @staticmethod
    def decode(b: bytes) -> "TableDescriptor":
        d = json.loads(b.decode())
        return TableDescriptor(d["table_id"], d["name"],
                               [tuple(c) for c in d["columns"]],
                               d["pk"], d["dicts"], d["next_rowid"],
                               d.get("row_count", 0),
                               d.get("indexes", {}),
                               d.get("notnull", []),
                               d.get("dropped", []),
                               d.get("backfilling"))

    def nullable(self, cname: str) -> bool:
        return cname != self.pk and cname not in self.notnull

    def visible(self, cname: str) -> bool:
        return cname not in self.dropped and cname != self.backfilling

    def visible_columns(self) -> List[Tuple[str, str]]:
        return [(c, t) for c, t in self.columns if self.visible(c)]

    def schema(self) -> Schema:
        fields = []
        dicts = {}
        for cname, tname in self.visible_columns():
            ty = _type_of(tname)
            ref = None
            if ty.kind is Kind.STRING:
                ref = f"{self.name}.{cname}"
                dicts[ref] = np.asarray(self.dicts[cname], dtype=object)
            fields.append(Field(cname, ty, dict_ref=ref,
                                nullable=self.nullable(cname)))
        return Schema(fields, dicts)

    def value_columns(self) -> List[Tuple[str, str]]:
        """Columns stored in the row value (pk rides the key). The row
        codec appends one extra hidden int64 field: the NULL bitmap
        (bit i = value column i is NULL) — nulls.go's bitmap riding the
        fixed-width tuple. A VECTOR(d) column occupies d consecutive
        slots (one float32 bit pattern per slot) but ONE bitmap bit."""
        return [(c, t) for c, t in self.columns if c != self.pk]

    def value_slots(self) -> int:
        """Total physical int64 slots before the NULL bitmap."""
        return sum(_slots_of(t) for _, t in self.value_columns())

    def slot_offset(self, i: int) -> int:
        """First physical slot of value column i."""
        return sum(_slots_of(t)
                   for _, t in self.value_columns()[:i])

    def field_value(self, fields, i: int):
        """Value column i of a stored row, or None when its NULL bit is
        set (rows written before the bitmap existed have no mask)."""
        nv = self.value_slots()
        mask = fields[nv] if len(fields) > nv else 0
        return None if (mask >> i) & 1 else fields[self.slot_offset(i)]


def _index_pk(value: int, rowid: int) -> int:
    """Index-entry key: (value+2^31) << 32 | rowid — big-endian u64 order
    == (value, rowid) order. Raises BindError outside 32-bit bounds (the
    engine key codec is (table u16, pk u64); composite byte keys are a
    later codec extension)."""
    biased = value + (1 << 31)
    if not (0 <= biased < (1 << 32)):
        raise BindError(f"indexed value {value} outside 32-bit range")
    if not (0 <= rowid < (1 << 32)):
        raise BindError(f"rowid {rowid} outside 32-bit index range")
    return (biased << 32) | rowid


class SessionCatalog(Catalog):
    """Mutable catalog over one MVCCStore; descriptors persisted.

    One catalog is shared by every session of a server: descriptor
    mutations (create/drop/save, id allocation) serialize under `_mu`,
    and DML serializes under the same lock (Session._run_dml holds it)
    because mutations update shared descriptor state in place — string
    dictionaries grow, `next_rowid` bumps — alongside the engine writes.
    Reads (desc lookups, scans) stay lock-free: a dict get is atomic and
    scans read the MVCC engine, which has its own lock."""

    def __init__(self, store: MVCCStore, mesh=None):
        self.store = store
        self.mesh = mesh  # the node's device mesh (Catalog.mesh)
        # RLock: create() calls _next_id() and save() under the lock
        self._mu = threading.RLock()
        self._descs: Dict[str, TableDescriptor] = {}
        # process-wide prepared-statement cache shared by EVERY session
        # of this catalog: a statement warmed on one pgwire connection
        # is warm on all of them — the cross-session seam the serving
        # queue (sql/serving.py) coalesces batches over. Session adopts
        # the (dict, lock) pair wholesale so the per-session code path
        # is identical either way.
        self.shared_prepared = (OrderedDict(), threading.Lock())
        self._load_all()

    # ------------------------------------------------------ descriptors --

    def _key(self, table_id: int) -> bytes:
        return struct.pack(">HQ", DESC_TABLE, table_id)

    def _load_all(self):
        start = struct.pack(">HQ", DESC_TABLE, 0)
        end = struct.pack(">HQ", DESC_TABLE + 1, 0)
        for k in self.store.engine.scan_keys(start, end, Timestamp.MAX):
            hit = self.store.engine.get(k, Timestamp.MAX)
            if hit and hit[0]:
                desc = TableDescriptor.decode(hit[0])
                self._descs[desc.name] = desc

    def save(self, desc: TableDescriptor):
        with self._mu:
            self._descs[desc.name] = desc
            self.store.engine.put(self._key(desc.table_id),
                                  self.store.clock.now(), desc.encode())

    def drop(self, name: str):
        with self._mu:
            desc = self._descs.pop(name)
            # delete the table's DATA too: table ids are reused by
            # create(), and surviving rows would resurrect under the
            # next table's schema
            ts = self.store.clock.now()
            for tid in [desc.table_id] + list(desc.indexes.values()):
                start = struct.pack(">HQ", tid, 0)
                end = struct.pack(">HQ", tid + 1, 0)
                for k in self.store.engine.scan_keys(start, end,
                                                     Timestamp.MAX):
                    self.store.engine.delete(k, ts)
            self.store.engine.delete(self._key(desc.table_id), ts)

    def _next_id(self) -> int:
        with self._mu:
            used = [d.table_id for d in self._descs.values()]
            for d in self._descs.values():
                used.extend(d.indexes.values())
            return max(used, default=0) + 1

    def create(self, name: str, columns: List[Tuple[str, str]],
               pk: Optional[str],
               notnull: Optional[List[str]] = None) -> TableDescriptor:
        with self._mu:
            if name in self._descs:
                raise BindError(f"table {name!r} already exists")
            desc = TableDescriptor(self._next_id(), name, columns, pk,
                                   notnull=notnull)
            self.save(desc)
            return desc

    def desc(self, name: str) -> TableDescriptor:
        if name not in self._descs:
            raise BindError(f"no table {name!r}")
        return self._descs[name]

    # --------------------------------------------------------- Catalog --

    def table_schema(self, name: str) -> Schema:
        return self.desc(name).schema()

    def table_chunks(self, name: str, capacity: int, columns=None):
        desc = self.desc(name)
        all_names = [c for c, _ in desc.columns]
        value_cols = desc.value_columns()
        wanted = list(columns) if columns else all_names
        store = self.store
        tid = desc.table_id
        pk = desc.pk
        n_slots = desc.value_slots()

        nullable = [desc.nullable(c) for c, _ in value_cols]

        def decode_slots(pks, slot_cols, rows):
            """wanted-column chunk out of the positional slot codec —
            shared by the host walk and the resident tier (bit-identical
            by construction: both feed the same slot arrays through it).
            `slot_cols[i]` is the i-th value slot (n_slots of them, plus
            the trailing NULL bitmap at index n_slots)."""
            mask = slot_cols[n_slots]
            out = {}
            off = 0
            for i, (n, t) in enumerate(value_cols):
                s = _slots_of(t)
                if s == 1:
                    out[n] = slot_cols[off]
                else:  # VECTOR(d): d slot columns -> (rows, d) f32
                    out[n] = _slots_to_f32(np.stack(
                        [slot_cols[off + j] for j in range(s)], axis=1))
                off += s
                if nullable[i]:
                    out[n + "__valid"] = ((mask >> i) & 1) == 0
            if pk is not None:
                out[pk] = pks[:rows]
            chunk = {n: out[n] for n in wanted}
            for n in wanted:
                if n + "__valid" in out:
                    chunk[n + "__valid"] = out[n + "__valid"]
            return chunk

        def resident_chunks(rt):
            from cockroach_tpu.util.fault import maybe_fail
            from cockroach_tpu.util.retry import with_retry

            def materialize():
                maybe_fail("scan.resident")
                return rt.scan_columns(store.clock.now())

            pks, vals = with_retry(materialize, name="scan.resident")
            k = int(pks.shape[0])
            for off in range(0, k, capacity):
                rows = min(capacity, k - off)
                sl = vals[:, off:off + capacity]
                yield decode_slots(pks[off:off + capacity],
                                   [sl[j] for j in range(n_slots + 1)],
                                   rows)

        def chunks():
            # device-resident tier first: visibility is the jitted
            # kernel over the table's resident version arrays; the
            # engine walk below stays the backstop
            if getattr(store, "engine", None) is not None:
                from cockroach_tpu.exec import stats as _stats
                from cockroach_tpu.storage import resident as _resident

                rt = _resident.maybe_attach(store, tid, n_slots + 1)
                if rt is not None:
                    try:
                        yield from resident_chunks(rt)
                        return
                    except Exception as e:  # noqa: BLE001 — backstop
                        _stats.add("scan.resident_fallback")
                        if isinstance(e, _resident.ResidentUnavailable):
                            _resident.detach(store, tid)
            # scan values (positional codec, + the trailing NULL bitmap
            # field) + reconstruct the pk column from the key stream
            start_pk = 0
            ts = store.clock.now()
            while True:
                keys = store.engine.scan_keys(
                    struct.pack(">HQ", tid, start_pk),
                    struct.pack(">HQ", tid + 1, 0), ts,
                    max_rows=capacity)
                if not keys:
                    return
                pks = np.asarray([struct.unpack(">HQ", k)[1]
                                  for k in keys], dtype=np.int64)
                res = store.engine.scan_to_cols(
                    struct.pack(">HQ", tid, start_pk),
                    struct.pack(">HQ", tid + 1, 0), ts,
                    n_slots + 1, capacity)
                yield decode_slots(
                    pks, [res.cols[j] for j in range(n_slots + 1)],
                    res.rows)
                if not res.more:
                    return
                start_pk = struct.unpack(">HQ", res.resume_key)[1]

        return chunks

    def scan_cache_key(self, name: str, columns, capacity: int):
        # same content identity as MVCCCatalog: every engine write path
        # (put/delete/ingest — including txn commits that bypass
        # MVCCStore) bumps the per-table version, so a rotated key can
        # never serve a stale image. Descriptor changes (ADD/DROP
        # COLUMN) rotate through the column tuple. The "sess" tag keeps
        # these keys disjoint from raw-MVCCCatalog images of the same
        # table: this chunk stream adds pk + validity lanes.
        prefix = getattr(self.store, "scan_cache_prefix", None)
        if prefix is None:
            # ClusterStore (kv/dtxn.py) has no per-table version seam;
            # replicated-surface scans stay uncached
            return None
        desc = self.desc(name)
        cols = (tuple(columns) if columns
                else tuple(c for c, _ in desc.columns))
        from cockroach_tpu.storage import resident as _resident

        rt = _resident.lookup(self.store, desc.table_id)
        if rt is not None:
            # resident tier: identity is (attach generation, ts-pack
            # base, write version, newest-version bucket) — rotates on
            # every write like the plain key, but rematerializing under
            # the rotated key costs one delta fold + visibility kernel,
            # not an engine walk + re-transfer
            base, bucket = rt.read_bucket(None)
            return prefix(desc.table_id) + (
                "sess", "resident", rt.generation, base,
                self.store.table_version(desc.table_id), bucket,
                int(capacity), cols)
        return prefix(desc.table_id) + (
            "sess", self.store.table_version(desc.table_id),
            int(capacity), cols)

    def serving_image_key(self, name: str,
                          capacity: int) -> Optional[tuple]:
        """The ServingQueue's runner/compatibility key for one table.
        When the table is device-resident this is STABLE ACROSS WRITES —
        (attach generation, capacity) only — because the resident
        serving runner refreshes its image from the delta fold at every
        dispatch; a write therefore no longer tears down the warm
        vmapped program + image. Falls back to the MVCC-versioned
        scan_cache_key (rotate-on-write) when not resident."""
        prefix = getattr(self.store, "scan_cache_prefix", None)
        if prefix is None:
            return None
        desc = self.desc(name)
        from cockroach_tpu.storage import resident as _resident

        rt = _resident.maybe_attach(self.store, desc.table_id,
                                    desc.value_slots() + 1)
        if rt is not None:
            return prefix(desc.table_id) + (
                "sess", "resident-serving", rt.generation,
                int(capacity))
        return self.scan_cache_key(name, None, capacity)

    def resident_serving(self, name: str, cols) -> Optional[dict]:
        """The resident-tier build recipe for a ServingQueue runner over
        `cols` (INT single-slot projections, per match_batchable): the
        attached ResidentTable plus each column's value-slot index and
        NULL-bitmap bit (-1 = NOT NULL), and the bitmap's slot. None
        when the table is not resident or a column can't ride the
        resident image directly."""
        try:
            desc = self.desc(name)
        except Exception:  # noqa: BLE001 — dropped since keyed
            return None
        from cockroach_tpu.storage import resident as _resident

        rt = _resident.maybe_attach(self.store, desc.table_id,
                                    desc.value_slots() + 1)
        if rt is None:
            return None
        value_cols = desc.value_columns()
        slot_of: Dict[str, int] = {}
        bit_of: Dict[str, int] = {}
        off = 0
        for i, (n, t) in enumerate(value_cols):
            s = _slots_of(t)
            if s == 1:
                slot_of[n] = off
                bit_of[n] = i if desc.nullable(n) else -1
            off += s
        slots, bits = [], []
        for c in cols:
            if c == desc.pk:
                slots.append(-1)  # -1 = the image's pk lane itself
                bits.append(-1)
                continue
            if c not in slot_of:
                return None
            slots.append(slot_of[c])
            bits.append(bit_of[c])
        return {"rt": rt, "slots": tuple(slots), "bits": tuple(bits),
                "mask_slot": desc.value_slots()}

    def table_rows(self, name: str) -> int:
        return max(self.desc(name).row_count, 1)

    def table_pk(self, name: str) -> Optional[Tuple[str, ...]]:
        pk = self.desc(name).pk
        return (pk,) if pk else None

    def table_stats(self, name: str):
        from cockroach_tpu.sql.stats import load_stats

        return load_stats(self.store, self.desc(name).table_id)

    def analyze(self, name: str):
        """ANALYZE <table>: sample the table through the catalog chunk
        stream, persist TableStats in the stats system keyspace (the
        reference's CREATE STATISTICS / automatic stats job)."""
        from cockroach_tpu.sql.stats import sample_stats, save_stats

        desc = self.desc(name)
        st = sample_stats(self.table_chunks(name, 1 << 12)(),
                          desc.schema())
        save_stats(self.store, desc.table_id, st)
        desc.row_count = st.row_count
        self.save(desc)
        return st

    # --------------------------------------------------------- indexes --

    def table_indexes(self, name: str) -> Dict[str, int]:
        return dict(self.desc(name).indexes)

    def index_chunks(self, name: str, column: str, lo: int, hi: int,
                     capacity: int, columns=None):
        """Index-join chunk stream (joinReader, rowexec/joinreader.go:74):
        scan the index span [lo, hi] in index order, then fetch each
        matching primary row by rowid — batched point lookups instead of
        a full table scan."""
        desc = self.desc(name)
        idx_id = desc.indexes[column]
        all_names = [c for c, _ in desc.columns]
        value_cols = desc.value_columns()
        wanted = list(columns) if columns else all_names
        store = self.store
        lo_pk = _index_pk(max(lo, -(1 << 31)), 0)
        hi_pk = _index_pk(min(hi, (1 << 31) - 1), (1 << 32) - 1)
        nv = desc.value_slots()

        def chunks():
            from cockroach_tpu.kv.streamer import Streamer

            streamer = Streamer(store)
            ts = store.clock.now()
            start = struct.pack(">HQ", idx_id, lo_pk)
            # an unbounded upper constraint saturates the u64 key space:
            # the exclusive end is then the next table prefix
            end = (struct.pack(">HQ", idx_id + 1, 0)
                   if hi_pk >= (1 << 64) - 1
                   else struct.pack(">HQ", idx_id, hi_pk + 1))
            n_fields = nv + 1  # + NULL bitmap
            while True:
                res = store.engine.scan_to_cols(start, end, ts, 2,
                                                capacity)
                if res.rows == 0 and not res.more:
                    return
                rowids = res.cols[0][:res.rows]
                # kvstreamer-lite: one batched, span-coalesced lookup
                # instead of a get() per index entry
                got = streamer.multi_get(desc.table_id, rowids,
                                         n_fields)
                out_rows = [(int(rid), got[int(rid)])
                            for rid in rowids if int(rid) in got]
                if out_rows:
                    cols_out: Dict[str, np.ndarray] = {}
                    masks = np.asarray(
                        [f[nv] if len(f) > nv else 0
                         for _, f in out_rows], dtype=np.int64)
                    off = 0
                    for i, (n, t) in enumerate(value_cols):
                        s = _slots_of(t)
                        if s == 1:
                            cols_out[n] = np.asarray(
                                [f[off] if off < len(f) else 0
                                 for _, f in out_rows], dtype=np.int64)
                        else:
                            cols_out[n] = _slots_to_f32(np.asarray(
                                [[f[off + j] if off + j < len(f) else 0
                                  for j in range(s)]
                                 for _, f in out_rows], dtype=np.int64))
                        off += s
                        if desc.nullable(n):
                            cols_out[n + "__valid"] = \
                                ((masks >> i) & 1) == 0
                    if desc.pk is not None:
                        cols_out[desc.pk] = np.asarray(
                            [r for r, _ in out_rows], dtype=np.int64)
                    chunk = {n: cols_out[n] for n in wanted}
                    for n in wanted:
                        if n + "__valid" in cols_out:
                            chunk[n + "__valid"] = cols_out[n + "__valid"]
                    yield chunk
                if not res.more:
                    return
                start = res.resume_key

        return chunks


class _TxnReadCatalog(Catalog):
    """Catalog overlay for SELECTs inside an open transaction: tables
    the txn has buffered writes for are served row-at-a-time through
    the txn (read-your-writes + reads recorded for commit validation);
    untouched tables stream through the base catalog's columnar path."""

    def __init__(self, base: SessionCatalog, txn):
        self.base = base
        self.txn = txn

    def table_schema(self, name):
        return self.base.table_schema(name)

    def table_rows(self, name):
        return self.base.table_rows(name)

    def table_pk(self, name):
        return self.base.table_pk(name)

    def table_stats(self, name):
        return self.base.table_stats(name)

    def table_indexes(self, name):
        # index entries are not txn-buffered: disable index plans for
        # tables this txn wrote (correctness over speed inside the txn)
        desc = self.base.desc(name)
        touched = any(t == desc.table_id for (t, _pk) in
                      getattr(self.txn, "_writes", {}))
        return {} if touched else self.base.table_indexes(name)

    def index_chunks(self, *a, **kw):
        return self.base.index_chunks(*a, **kw)

    def table_chunks(self, name, capacity, columns=None):
        desc = self.base.desc(name)
        touched = any(t == desc.table_id for (t, _pk) in
                      getattr(self.txn, "_writes", {}))
        if not touched:
            return self.base.table_chunks(name, capacity, columns)
        txn = self.txn
        value_cols = desc.value_columns()
        all_names = [c for c, _ in desc.columns]
        wanted = list(columns) if columns else all_names
        nv = desc.value_slots()

        def chunks():
            pks = sorted(set(txn.scan_pks(desc.table_id))
                         | set(txn.buffered_pks(desc.table_id)))
            rows = []
            for pk in pks:
                fields = txn.get(desc.table_id, pk)
                if fields is not None:
                    rows.append((pk, fields))
            for a in range(0, max(len(rows), 1), capacity):
                part = rows[a:a + capacity]
                if not part:
                    return
                masks = np.asarray(
                    [f[nv] if len(f) > nv else 0 for _, f in part],
                    dtype=np.int64)
                out: Dict[str, np.ndarray] = {}
                off = 0
                for i, (n, t) in enumerate(value_cols):
                    s = _slots_of(t)
                    if s == 1:
                        out[n] = np.asarray(
                            [f[off] if off < len(f) else 0
                             for _, f in part], dtype=np.int64)
                    else:
                        out[n] = _slots_to_f32(np.asarray(
                            [[f[off + j] if off + j < len(f) else 0
                              for j in range(s)]
                             for _, f in part], dtype=np.int64))
                    off += s
                    if desc.nullable(n):
                        out[n + "__valid"] = ((masks >> i) & 1) == 0
                if desc.pk is not None:
                    out[desc.pk] = np.asarray([p for p, _ in part],
                                              dtype=np.int64)
                chunk = {n: out[n] for n in wanted}
                for n in wanted:
                    if n + "__valid" in out:
                        chunk[n + "__valid"] = out[n + "__valid"]
                yield chunk

        return chunks


class _Prepared:
    """One cached SELECT: the built operator tree (re-collectable; its
    cached FusedRunner makes repeats a single dispatch), the output
    schema, the per-table scan-cache keys the plan was built against
    (MVCC-write-versioned — the invalidation check), the capacity those
    keys were computed at (entries are shared across sessions, which may
    differ in capacity; the plan's own chunking governs, not the
    reader's), the batchable-statement spec when the statement is in
    the serving queue's coalescible class (sql/serving.py), and whether
    a distributing session made it (`distsql`; the cache is shared by a
    catalog's sessions, and an entry serves only its own kind). A
    statement with `$n` placeholders is ONE entry under its parameterised
    text: `slots` (sql/params.ParamSlot, one a program argument) turn a
    Bind's values into the arguments of the tree's one program, and what
    a flow restart widened stays on the tree for every later binding."""

    __slots__ = ("op", "schema", "vkeys", "capacity", "bspec", "dist",
                 "slots")

    def __init__(self, op, schema, vkeys: Dict[str, tuple],
                 capacity: int, bspec=None, dist: bool = False,
                 slots=()):
        self.op = op
        self.schema = schema
        self.vkeys = vkeys
        self.capacity = capacity
        self.bspec = bspec
        self.dist = dist
        self.slots = tuple(slots)


_session_ids = itertools.count(1)


class Session:
    """One SQL session: statement dispatch + session vars."""

    # session var -> cluster-setting key (None = session-local only)
    _VARS = {
        "exact_arithmetic": "sql.tpu.exact_arithmetic",
        "pallas": "sql.tpu.pallas",
        "admission_slots": "sql.tpu.admission_slots",
        "workmem": "sql.distsql.temp_storage.workmem",
        "vectorize": None,
        # distsql: off | on | always (_distsql)
        "distsql": None,
        # per-statement deadline in seconds: session-local, defaulting
        # to the sql.defaults.statement_timeout cluster setting
        "statement_timeout": None,
        # admission priority for this session's statements: low|normal|high
        "admission_priority": None,
    }

    def __init__(self, catalog: Catalog, capacity: int = 1 << 14,
                 db: Optional[DB] = None, registry=None):
        self.catalog = catalog
        self.capacity = capacity
        self.session_id = next(_session_ids)
        # SHOW SESSIONS / cluster_sessions visibility; the registry holds
        # this session by weakref, so registration never extends its life.
        # Pluggable so a multi-node test can bind sessions to DIFFERENT
        # nodes' registries (cross-node CANCEL QUERY routes between them)
        from cockroach_tpu.server.registry import default_query_registry

        self._qreg = registry or default_query_registry()
        self._qreg.register_session(self)
        # execution-insights sampling state (_observe_insight): tick
        # counter for the 1-in-8 sub-floor baseline feed and the cached
        # latency floor (0.0 -> the first statement refreshes it)
        self._ins_tick = 0
        self._ins_floor = 0.0
        # vectorize: tpu | cpu force a backend; any other value (auto)
        # leaves the route to the coster (sql/cost.py)
        self.vars: Dict[str, object] = {"vectorize": "auto",
                                        "distsql": "off",
                                        "admission_priority": "normal"}
        if db is None and isinstance(catalog, SessionCatalog):
            db = DB(catalog.store)
        self.db = db
        self._txn = None  # open interactive transaction (BEGIN..COMMIT)
        self._txn_aborted = False
        self._txn_row_deltas: Dict[str, int] = {}  # stats, applied at COMMIT
        # prepared-statement cache: EXACT SQL text -> _Prepared. Keyed on
        # the text, NOT sqlstats.fingerprint — the fingerprint strips
        # literals, and two statements differing only in literals need
        # different plans (a statement sent with `$n` placeholders is
        # keyed on its parameterised text: one entry for all bindings,
        # see bind_params). Validity is checked per hit against the
        # catalog's current scan-cache keys (which embed each table's
        # MVCC write version), so one write to any scanned table rotates
        # the key and forces a rebuild. Guarded by _prepared_mu: the
        # check_race harness drives one session from many threads, and a
        # torn OrderedDict move corrupts the whole dict. A SessionCatalog
        # shares ONE (dict, lock) pair across all of its sessions — the
        # cross-connection warmth the serving queue batches over; other
        # catalogs fall back to a private pair.
        shared = getattr(catalog, "shared_prepared", None)
        if shared is not None:
            self._prepared, self._prepared_mu = shared
        else:
            self._prepared = OrderedDict()
            self._prepared_mu = threading.Lock()
        # the in-flight statement's cancel context, set for the duration
        # of execute(): pgwire's cancel path (and drain) reach it via
        # cancel_query() from OTHER threads
        self._cancel_mu = threading.Lock()
        self._active_cancel = None
        # parameterised texts that bind_params found outside the typed
        # scope (DML, LIMIT $1, IN ($1, ...)): bound as text without a
        # second look
        self._textual_only: set = set()

    PREPARED_CACHE_ENTRIES = 32
    TEXTUAL_MEMO_ENTRIES = 256

    # ------------------------------------------------------ cancellation

    def _statement_timeout(self) -> float:
        """Effective statement deadline: session var if SET, else the
        sql.defaults.statement_timeout cluster setting; <= 0 = none."""
        v = self.vars.get("statement_timeout")
        if v is None:
            v = Settings().get(STATEMENT_TIMEOUT)
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    def _admission_priority(self) -> int:
        from cockroach_tpu.util.admission import HIGH, LOW, NORMAL

        return {"low": LOW, "high": HIGH}.get(
            str(self.vars.get("admission_priority", "normal")).lower(),
            NORMAL)

    def cancel_query(self, reason: str = "query cancelled") -> bool:
        """Cancel the in-flight statement (if any) from another thread —
        the CancelRequest / drain entry point. Returns whether a
        statement was actually in flight to cancel."""
        with self._cancel_mu:
            ctx = self._active_cancel
        if ctx is None:
            return False
        ctx.cancel(reason)
        return True

    # ---------------------------------------------------------- execute --

    # statements exempt from admission gating AND from error-aborts-txn:
    # txn control must always run (a COMMIT queued behind the very work
    # holding the slots would wedge), SET/SHOW are free, and CANCEL must
    # reach an overloaded server — a CANCEL QUERY queued behind the very
    # statements it is trying to kill would wedge the operator's only
    # remedy
    _CONTROL_HEADS = ("begin", "commit", "rollback", "abort", "start",
                      "set", "show", "cancel")

    def execute(self, sql: str, params=None) -> Tuple[str, object, object]:
        """-> (kind, payload, schema) like explain.execute_with_plan,
        plus kinds: 'ok' (DDL/DML, payload = tag string). `params`
        (sql/params.BoundParams, from bind_params) are the values of the
        statement's `$n` placeholders, bound as data. Every
        statement records into sqlstats (the statements-page feed); a
        root span covers the statement when `sql.trace.enabled` is on.

        Statement lifecycle seams added around _execute: a CancelContext
        (armed with the effective statement_timeout) is registered so
        pgwire CancelRequest / drain can abort from other threads; the
        statement registers in the process-wide query registry BEFORE
        admission (so a queued statement is visible to SHOW QUERIES and
        cancellable by CANCEL QUERY while it waits); work statements
        pass session admission first (shed -> 53300); a cancel/deadline
        anywhere surfaces as 57014 with the session left reusable; a
        per-query stats overlay attributes device time / bytes scanned
        to the fingerprint and feeds the execution-insights baseline."""
        import time as _time

        from cockroach_tpu.exec import stats as _stats
        from cockroach_tpu.server import registry as _registry
        from cockroach_tpu.sql.insights import default_insights
        from cockroach_tpu.sql.sqlstats import default_sqlstats
        from cockroach_tpu.util import cancel as _cancel
        from cockroach_tpu.util import tracing

        from cockroach_tpu.sql import serving as _serving

        head = sql.strip().split(None, 1)[0].lower() if sql.strip() else ""
        t0 = _time.perf_counter()
        qreg = self._qreg
        queue = qentry = None
        qid = 0
        serving_path = False
        try:
            tags = {} if params is None else {"params": len(params)}
            with tracing.query_span("session.execute", sql=sql[:60],
                                    **tags), \
                    _stats.query_stats() as qcol:
                try:
                    with _stats.timed("session.admit"):
                        timeout = self._statement_timeout()
                        # a statement headed for the serving queue skips
                        # per-statement admission — the batch LEADER
                        # acquires one slot for the whole coalesced batch
                        # (sql/serving.py), so the coalescing depth is
                        # not capped at the slot count. The probe (a dict
                        # get, no side effects) runs first so the
                        # statement registers directly in its final
                        # phase — the warm path pays ONE registry write.
                        serving_path = (head == "select"
                                        and _serving.probe(self, sql))
                        # the registry entry doubles as the statement's
                        # CancelContext
                        ctx = qentry = qreg.register(
                            self, sql, timeout if timeout > 0 else None,
                            phase=(_registry.PHASE_SERVING if serving_path
                                   else _registry.PHASE_QUEUED),
                            track=not serving_path, start_pc=t0)
                        qid = qentry.query_id
                        with self._cancel_mu:
                            self._active_cancel = ctx
                        if not serving_path:
                            queue = self._admit(head, ctx)
                            qentry.phase = _registry.PHASE_EXECUTING
                    with _cancel.active(ctx):
                        kind, payload, schema = self._execute(sql, params)
                except Exception as e:
                    elapsed = _time.perf_counter() - t0
                    default_sqlstats().record(
                        sql, elapsed, error=True,
                        session_id=self.session_id,
                        device_s=_stats.device_seconds(qcol),
                        bytes_scanned=_stats.bytes_scanned(qcol))
                    self._maybe_log_slow(sql, elapsed, error=True)
                    default_insights().observe(
                        sql, elapsed, session_id=self.session_id,
                        query_id=qid,
                        shed=(isinstance(e, SQLError)
                              and e.pgcode == "53300"),
                        degraded=_stats.degradations_seen(qcol),
                        error=True)
                    if self._txn is not None:
                        # Postgres semantics: a statement error aborts
                        # the open transaction — but txn-control/var
                        # statements failing (e.g. a redundant BEGIN)
                        # are warnings there, not aborts, so they do not
                        # poison the transaction
                        if head not in self._CONTROL_HEADS:
                            self._txn_aborted = True
                    mapped = map_execution_error(e)
                    if mapped is not None:
                        raise mapped from e
                    raise
                with _stats.timed("session.account"):
                    rows = 0
                    if kind == "rows" and payload:
                        first = next(iter(payload.values()), None)
                        rows = len(first) if first is not None else 0
                    elapsed = _time.perf_counter() - t0
                    default_sqlstats().record(
                        sql, elapsed, rows=rows,
                        session_id=self.session_id,
                        device_s=_stats.device_seconds(qcol),
                        bytes_scanned=_stats.bytes_scanned(qcol),
                        op_device=_stats.operator_device(qcol))
                    self._maybe_log_slow(sql, elapsed, rows=rows)
                    self._observe_insight(
                        sql, elapsed, qid, _stats.degradations_seen(qcol),
                        _stats.stage_seconds(qcol, "fused.wait")
                        + _stats.stage_seconds(qcol, "dist.wait"))
            return kind, payload, schema
        finally:
            if qentry is not None:
                qreg.deregister(self, qentry, not serving_path)
            if queue is not None:
                queue.release()
            with self._cancel_mu:
                self._active_cancel = None

    def execute_spec(self, spec, sql: str):
        """The EXECUTE fast path (pgwire Bind matched the bound text to
        a batch class): serve the statement straight through the
        ServingQueue with the same lifecycle seams as execute() —
        cancel context + statement_timeout, sqlstats, slow-query log,
        error mapping — but no parse, no plan, and no per-statement
        admission (the batch leader admits for the whole batch).
        Returns (kind, payload, schema), or None when the statement
        should run the normal path instead (batch declined/fell back,
        open transaction, serving disabled)."""
        import time as _time

        from cockroach_tpu.server import registry as _registry
        from cockroach_tpu.sql import serving as _serving
        from cockroach_tpu.sql.insights import default_insights
        from cockroach_tpu.sql.sqlstats import default_sqlstats
        from cockroach_tpu.util import cancel as _cancel
        from cockroach_tpu.util import tracing

        if (not _serving.enabled() or self._txn is not None
                or self._txn_aborted):
            return None
        t0 = _time.perf_counter()
        timeout = self._statement_timeout()
        qreg = self._qreg
        # the registry entry doubles as the statement's CancelContext
        ctx = qentry = qreg.register(self, sql,
                                     timeout if timeout > 0 else None,
                                     phase=_registry.PHASE_SERVING,
                                     start_pc=t0)
        qid = qentry.query_id
        with self._cancel_mu:
            self._active_cancel = ctx
        try:
            with tracing.query_span("session.execute_spec",
                                    sql=sql[:60]), \
                    _cancel.active(ctx):
                try:
                    vkey = _serving._class_vkey(self.catalog,
                                                self.capacity, spec)
                    if vkey is None:
                        return None
                    payload = _serving.serving_queue().submit(
                        self, spec, vkey, via="execute")
                except Exception as e:
                    elapsed = _time.perf_counter() - t0
                    default_sqlstats().record(
                        sql, elapsed, error=True,
                        session_id=self.session_id)
                    self._maybe_log_slow(sql, elapsed, error=True)
                    default_insights().observe(
                        sql, elapsed, session_id=self.session_id,
                        query_id=qid,
                        shed=(isinstance(e, SQLError)
                              and e.pgcode == "53300"),
                        error=True)
                    mapped = map_execution_error(e)
                    if mapped is not None:
                        raise mapped from e
                    raise
                if payload is None:
                    # the batch declined or fell apart mid-flight: the
                    # caller re-runs the statement serially — an insight
                    # the operator should see when it becomes a pattern
                    default_insights().observe(
                        sql, _time.perf_counter() - t0,
                        session_id=self.session_id, query_id=qid,
                        batch_fallback=True, error=True)
                    return None
                first = next(iter(payload.values()), None)
                rows = len(first) if first is not None else 0
                elapsed = _time.perf_counter() - t0
                default_sqlstats().record(sql, elapsed, rows=rows,
                                          session_id=self.session_id)
                self._maybe_log_slow(sql, elapsed, rows=rows)
                self._observe_insight(sql, elapsed, qid, False)
                return "rows", payload, _serving.spec_schema(spec)
        finally:
            qreg.deregister(self, qentry)
            with self._cancel_mu:
                self._active_cancel = None

    def _admit(self, head: str, ctx):
        """Session-layer admission: gate work statements through the
        shared WorkQueue (reference: sql admission queues above the KV
        work queues). Returns the queue holding ONE slot — released in
        execute()'s finally, so a shed, cancel, or execution error can
        never leak a slot — or None when admission is off / the
        statement is exempt. `ctx` is the statement's CancelContext: a
        queued statement polls it while it waits."""
        from cockroach_tpu.util import cancel as _cancel
        from cockroach_tpu.util.admission import (
            SESSION_QUEUE_TIMEOUT, session_queue,
        )

        queue = session_queue()
        if queue is None or head in self._CONTROL_HEADS:
            return None
        try:
            with _cancel.active(ctx):
                queue.acquire(
                    priority=self._admission_priority(),
                    timeout=float(Settings().get(SESSION_QUEUE_TIMEOUT)))
        except TimeoutError as e:
            # 53300 too_many_connections: the canonical "server is at
            # capacity, back off" class — overload degrades into shed
            # statements instead of a collapsing convoy
            raise SQLError(
                "53300",
                "statement shed: admission queue timed out under "
                "overload") from e
        return queue

    def _observe_insight(self, sql: str, elapsed: float, qid: int,
                         degraded: bool, wait_s: float = 0.0) -> None:
        """Healthy-statement insights seam. Full observe() runs for
        degraded or at/above-floor executions (those can flag) and for
        a 1-in-8 baseline sample of sub-floor ones; the other 7/8 of
        warm sub-floor statements — which can never flag and whose
        EWMA contribution a sample preserves — pay only this guard.
        The floor is re-read from settings on each sampled tick.

        First the fingerprint's usual time, as it stood BEFORE this
        execution, goes to the statement's trace (tracing.note_usual):
        the tracer keeps the tree of a statement that took twice that.
        `wait_s` is this execution's `fused.wait` (`dist.wait` on the
        distributed tier)."""
        from cockroach_tpu.sql.insights import default_insights
        from cockroach_tpu.util import tracing

        ins = default_insights()
        base = ins.baseline(sql)
        if base is not None and base.count >= _USUAL_MIN_SAMPLES:
            tracing.note_usual(base.mean, base.wait)
        tick = self._ins_tick = (self._ins_tick + 1) & 7
        if degraded or tick == 0 or elapsed >= self._ins_floor:
            self._ins_floor = ins.min_latency_floor()
            ins.observe(sql, elapsed, session_id=self.session_id,
                        query_id=qid, degraded=degraded, wait_s=wait_s)

    def _maybe_log_slow(self, sql: str, elapsed: float, rows: int = 0,
                        error: bool = False) -> None:
        """Slow-query log (reference: sql.log.slow_query.latency_threshold
        feeding the SQL_EXEC channel). Disabled at the default 0."""
        threshold = float(Settings().get(SLOW_QUERY_LATENCY))
        if threshold <= 0 or elapsed < threshold:
            return
        interval = float(Settings().get(SLOW_QUERY_INTERVAL))
        if interval > 0:
            import time as _time

            from cockroach_tpu.sql.sqlstats import fingerprint

            fp = fingerprint(sql)
            now = _time.monotonic()
            with _slow_log_mu:
                last = _slow_log_last.get(fp)
                if last is not None and now - last < interval:
                    return
                _slow_log_last[fp] = now
        from cockroach_tpu.util.log import (Channel, Redactable,
                                            get_logger)

        get_logger().structured(
            Channel.SQL_EXEC, "WARNING", "slow_query",
            sql=Redactable(sql), latency_s=round(elapsed, 4), rows=rows,
            error=error, session=self.session_id)

    # ------------------------------------------------- bound parameters

    def bind_params(self, sql: str, values) -> Tuple[object, str]:
        """pgwire's Bind for a statement with `$n` placeholders ->
        (BoundParams, the text to execute them with), or (None, the
        statement with the values written in) where the statement is
        outside the typed scope: not a SELECT whose parameters all stand
        beside a typed operand in a comparison, BETWEEN or + - *
        arithmetic, or as LIKE's pattern over a dictionary-coded column
        (sql/bind.py). Stage `sql.bind_params`: typing (a parse and a
        bind of the parameterised text, once a statement), the dictionary
        lookup of a string, the host folding of `$1 + interval '1' year`,
        a LIKE pattern matched against its dictionary (stage
        `sql.bind_like` inside this one); `rows` counts the values."""
        from cockroach_tpu.exec import stats
        from cockroach_tpu.util.metric import default_registry

        values = tuple(values)
        with stats.timed("sql.bind_params", rows=len(values)):
            bound = None
            if sql not in self._textual_only:
                try:
                    bound = self._type_params(sql, values)
                except _params.ValueOutOfScope:
                    pass    # this binding only: the next may fit
                except Exception:  # noqa: BLE001 — whatever the typed
                    # path cannot take runs as it always did, and a real
                    # error is the textual path's to report, at Execute
                    self._note_textual(sql)
            if bound is None:
                return None, self._bind_textual(sql, values)
            default_registry().counter(
                "sql_bind_params_total",
                "parameter values bound as data: arguments of the "
                "statement's one program").inc(len(values))
            return bound, sql

    def _type_params(self, sql: str, values: tuple):
        """The statement's slots -> this binding's program arguments.
        Warm: the prepared entry of the parameterised text holds the
        slots. Cold: parse and bind once to learn whether the statement
        is in scope (the plan itself is made at Execute, at this
        binding)."""

        if self._txn is None and not self._txn_aborted:
            prep = self._prepared_lookup(sql)
            if prep is not None and prep.slots:
                return _params.BoundParams(
                    values, _params.evaluate(prep.slots, values))
        ast = P.parse(sql)
        stmt = ast.stmt if isinstance(ast, P.ExplainStmt) else ast
        if not isinstance(stmt, P.SelectStmt):
            raise _params.ParamOutOfScope(type(stmt).__name__)
        binder = Binder(self.catalog, params=values)
        binder.bind(stmt)
        _params.evaluate(binder.param_slots, values)
        return _params.BoundParams(values)

    def _note_textual(self, sql: str) -> None:
        if len(self._textual_only) >= self.TEXTUAL_MEMO_ENTRIES:
            self._textual_only.clear()
        self._textual_only.add(sql)

    def _bind_textual(self, sql: str, values) -> str:
        """The statement with this binding's values written into its
        text: planned, compiled and cached per binding, as every
        parameterised statement was before bind_params."""
        from cockroach_tpu.util.metric import default_registry

        default_registry().counter(
            "sql_bind_textual_total",
            "statements whose parameter values were written into the "
            "text and planned as literals").inc()
        return _params.substitute(sql, values)

    # ------------------------------------------------ prepared statements

    def _prepared_lookup(self, sql: str) -> Optional[_Prepared]:
        """The prepared entry for this exact SQL text, IF every scanned
        table's current scan-cache key still equals the one the plan was
        built against (the key embeds the table's MVCC write version, so
        any write — this session's or another's — rotates it)."""
        with self._prepared_mu:
            prep = self._prepared.get(sql)
        if prep is None:
            return None
        # the validity probe runs OUTSIDE the lock (it reads the MVCC
        # engine); only the dict mutations re-enter it. Keys recompute
        # at the capacity the entry was BUILT at: the shared cache serves
        # sessions of any capacity, and the plan's chunking — not the
        # reader's preference — is what the stored keys describe.
        for tname, vkey in prep.vkeys.items():
            try:
                cur = self.catalog.scan_cache_key(tname, None,
                                                  prep.capacity)
            except Exception:  # noqa: BLE001 — e.g. table dropped
                cur = None
            if cur != vkey:
                if (prep.bspec is not None and cur is not None
                        and len(prep.vkeys) == 1
                        and tname == prep.bspec.table
                        and self._serving_still_warm(tname,
                                                     prep.capacity)):
                    # the plan's stacked image is stale, but the
                    # statement is batchable over a device-resident
                    # table whose serving image refreshes per dispatch:
                    # hand back a serving-only entry (op=None) so the
                    # warm path still skips the parse — _execute falls
                    # through to the cold path only if the serving
                    # submit itself declines
                    return _Prepared(None, prep.schema, prep.vkeys,
                                     prep.capacity, prep.bspec)
                with self._prepared_mu:
                    self._prepared.pop(sql, None)
                return None
        with self._prepared_mu:
            if sql in self._prepared:
                self._prepared.move_to_end(sql)
        return prep

    def _serving_still_warm(self, tname: str, capacity: int) -> bool:
        """Is `tname` device-resident, i.e. does its serving image
        survive writes? (The stable-across-writes serving_image_key
        tags resident tables "resident-serving".)"""
        sik = getattr(self.catalog, "serving_image_key", None)
        if sik is None:
            return False
        try:
            k = sik(tname, capacity)
        except Exception:  # noqa: BLE001
            return False
        return k is not None and "resident-serving" in k

    def _prepared_store(self, sql: str, sunk, ast=None,
                        dist: bool = False) -> None:
        """Cache the built operator tree when it is safely re-runnable:
        every scan carries a versioned cache key (rules out IndexScan
        ops and non-MVCC catalogs, whose inputs we cannot re-validate).
        Statements in the serving queue's batchable class additionally
        carry a BatchSpec, the ticket into cross-session coalescing —
        unless a distributing session (`dist`) made the entry: the
        serving queue is a single-chip path."""
        from cockroach_tpu.exec.operators import ScanOp, walk_operators
        from cockroach_tpu.sql.plan import (
            MVCCCatalog, Scan as _Scan, _walk_plan,
        )

        op = sunk.get("op") if isinstance(sunk, dict) else None
        if op is None or not isinstance(self.catalog,
                                        (SessionCatalog, MVCCCatalog)):
            return
        for s in walk_operators(op):
            if isinstance(s, ScanOp) and s.cache_key is None:
                return
        vkeys: Dict[str, tuple] = {}
        for t in {n.table for n in _walk_plan(sunk["plan"])
                  if isinstance(n, _Scan)}:
            try:
                k = self.catalog.scan_cache_key(t, None, self.capacity)
            except Exception:  # noqa: BLE001
                return
            if k is None:
                return
            vkeys[t] = k
        bspec = None
        if ast is not None and not dist:
            from cockroach_tpu.sql import serving as _serving

            try:
                bspec = _serving.match_batchable(ast, self.catalog,
                                                 self.capacity)
            except Exception:  # noqa: BLE001 — matcher must never
                bspec = None   # block the prepared path
        slots = sunk.get("slots", ())
        with self._prepared_mu:
            self._prepared[sql] = _Prepared(op, op.schema, vkeys,
                                            self.capacity, bspec, dist,
                                            slots)
            self._prepared.move_to_end(sql)
            while len(self._prepared) > self.PREPARED_CACHE_ENTRIES:
                self._prepared.popitem(last=False)
        if slots:
            # the ladder pre-warm compiles off the query path, where no
            # binding exists to give the program its arguments
            return
        # compile-at-prepare: hand the statement's pow2 bucket ladder to
        # the background pre-warm job (no-op unless sql.prewarm.enabled)
        # — the remaining rungs and the vault artifacts materialize off
        # the query path
        from cockroach_tpu.server import prewarm as _prewarm

        _prewarm.note_prepared(self.catalog, sql, self.capacity)

    def _invalidate_vault(self, ast) -> None:
        """DDL/ANALYZE hygiene for the persistent plan vault: content-
        hash keying already guarantees a stale artifact can't be LOADED
        (the changed schema lowers to a different program, hence a
        different key) — this eagerly deletes the now-unreachable
        artifacts tagged with the statement's table and resets the
        pre-warm dedupe so changed plans re-enqueue."""
        from cockroach_tpu.util.plan_vault import plan_vault

        table = getattr(ast, "table", None) or getattr(ast, "name", None)
        vault = plan_vault()
        if vault is not None and table and not isinstance(ast, P.SetVar):
            try:
                vault.invalidate_tables([table])
            except Exception:  # noqa: BLE001 — hygiene must not fail DDL
                pass
        svc = getattr(self.catalog, "_prewarm_service", None)
        if svc is not None:
            svc.forget()

    def _execute(self, sql: str, params=None) -> Tuple[str, object, object]:
        from cockroach_tpu.ops.expr import ParamOutsideProgram

        if params is not None:
            # the typed path reaches from here to the fused program; a
            # tree that leaves it (the streaming operators' own jits, a
            # binding the slots cannot hold) is answered as bound text
            try:
                return self._execute_bound(sql, params)
            except _params.ValueOutOfScope:
                sql = self._bind_textual(sql, params.values)
            except (ParamOutsideProgram, _params.ParamOutOfScope):
                self._note_textual(sql)
                sql = self._bind_textual(sql, params.values)
        return self._execute_bound(sql, None)

    def _execute_bound(self, sql: str, params) -> Tuple[str, object, object]:
        from cockroach_tpu.exec import collect, stats
        from cockroach_tpu.ops.expr import bound_args

        # warm-path short-circuit BEFORE the parse: a prepared hit needs
        # no ast at all (only SELECTs are ever stored, and the entry
        # already validated against the tables' MVCC versions), so the
        # serving path's per-statement cost is a dict probe + dispatch
        # instead of a full tokenize/parse
        if self._txn is None and not self._txn_aborted:
            with stats.timed("sql.lookup"):
                prep = self._prepared_lookup(sql)
            if prep is not None:
                mesh, strict = self._distsql()
                if prep.dist != (mesh is not None):
                    # made under another value of `distsql` (by a session
                    # of the same catalog; this one's SET cleared its
                    # own): never served here; the cold path stores this
                    # kind
                    prep = None
            if prep is not None:
                stats.add("sql.prepared_hit")
                if prep.bspec is not None:
                    from cockroach_tpu.sql import serving as _serving

                    payload = _serving.maybe_submit(self, prep, sql=sql)
                    if payload is not None:
                        return "rows", payload, prep.schema
                args = None
                if params is not None:
                    args = params.args
                    if args is None:  # bound before the entry existed
                        args = _params.evaluate(prep.slots, params.values)
                if prep.op is not None and mesh is not None:
                    from cockroach_tpu.parallel.dist_flow import (
                        collect_distributed,
                    )

                    with bound_args(args):
                        return "rows", collect_distributed(
                            prep.op, mesh, strict=strict), prep.schema
                if prep.op is not None:
                    with bound_args(args):
                        return "rows", collect(
                            prep.op, backend=self.vars["vectorize"]), \
                            prep.schema
                # serving-only entry (stale plan over a resident table)
                # whose batch submit declined: fall through to the cold
                # parse path, which also re-stores a full entry
        with stats.timed("sql.parse"):
            ast = P.parse(sql)
        if isinstance(ast, (P.CreateTable, P.DropTable, P.CreateIndex,
                            P.AlterTable, P.SetVar, P.AnalyzeStmt)):
            # schema, settings, or stats changes can change plans
            # wholesale — version checks can't see them, so drop all
            # prepared entries (DML is covered by the per-hit version
            # check instead)
            with self._prepared_mu:
                self._prepared.clear()
            self._invalidate_vault(ast)
        if self._txn_aborted and not isinstance(ast, P.TxnControl):
            raise BindError("current transaction is aborted — "
                            "ROLLBACK to continue")
        if self._txn is not None and isinstance(
                ast, (P.CreateTable, P.DropTable, P.CreateIndex,
                      P.AlterTable)):
            raise BindError("DDL inside a transaction is not supported "
                            "(descriptors are not transactional yet)")
        if isinstance(ast, P.SelectStmt) and len(ast.tables) == 1 \
                and ast.tables[0].subquery is None \
                and isinstance(self.catalog, SessionCatalog) \
                and self._matviews().get(ast.tables[0].name) is not None:
            return self._select_matview(ast)
        if isinstance(ast, (P.SelectStmt, P.ExplainStmt)):
            from cockroach_tpu.sql.explain import execute_with_plan

            catalog = self.catalog
            mesh, strict = self._distsql()
            if self._txn is not None and isinstance(catalog,
                                                    SessionCatalog):
                # read-your-writes: SELECTs inside an open transaction
                # must see its buffered mutations (conn_executor routes
                # statement execution through the txn's kv.Txn), which
                # are the gateway's: such a read is never distributed
                if mesh is not None and strict:
                    raise SQLError(
                        "0A000", "distsql = always: a SELECT inside an "
                        "open transaction reads the transaction's "
                        "buffered writes on the gateway and cannot be "
                        "distributed")
                mesh = None
                catalog = _TxnReadCatalog(catalog, self._txn)
            if isinstance(ast, P.SelectStmt) and self._txn is None:
                # cold path only: warm prepared hits short-circuited
                # before the parse above
                sink: List[object] = []
                out = execute_with_plan(sql, catalog, self.capacity,
                                        mesh=mesh, ast=ast, op_sink=sink,
                                        setting=self.vars["vectorize"],
                                        strict=strict, params=params)
                if sink:
                    self._prepared_store(sql, sink[0], ast,
                                         dist=mesh is not None)
                return out
            return execute_with_plan(sql, catalog, self.capacity,
                                     mesh=mesh, ast=ast,
                                     setting=self.vars["vectorize"],
                                     strict=strict, params=params)
        if params is not None:
            # DDL, DML, SET: their values are written into the text
            raise _params.ParamOutOfScope(type(ast).__name__)
        if isinstance(ast, P.TxnControl):
            return self._txn_control(ast)
        if isinstance(ast, P.SetVar):
            return self._set_var(ast)
        if isinstance(ast, P.ShowVar):
            name = ast.name
            if name not in self._VARS:
                raise BindError(f"unknown session variable {name!r}")
            return "rows", {name: np.asarray([str(self._get_var(name))],
                                             dtype=object)}, None
        if isinstance(ast, P.ShowStmt):
            return self._show_stmt(ast)
        if isinstance(ast, P.CancelQuery):
            from cockroach_tpu.server.nodestatus import route_cancel

            reason = f"CANCEL QUERY {ast.query_id}"
            # local registry first; a miss routes by the id's node
            # prefix through the status plane's node directory (the
            # reference forwards CANCEL QUERY over node RPC)
            if not (self._qreg.cancel(ast.query_id, reason=reason)
                    or route_cancel(ast.query_id, reason=reason,
                                    frm=self._qreg.node_id)):
                # 42704 undefined_object: the id names nothing live —
                # a clean, retry-safe error, not a stack trace
                raise SQLError(
                    "42704", f"unknown query id {ast.query_id}")
            return "ok", "CANCEL QUERY", None
        if not isinstance(self.catalog, SessionCatalog):
            raise BindError("this catalog is read-only (DDL/DML need a "
                            "storage-backed session)")
        if isinstance(ast, P.CreateTable):
            return self._create(ast)
        if isinstance(ast, P.CreateIndex):
            return self._create_index(ast)
        if isinstance(ast, P.AlterTable):
            return self._alter(ast)
        if isinstance(ast, P.AnalyzeStmt):
            cat: SessionCatalog = self.catalog
            st = cat.analyze(ast.table)
            return "ok", f"ANALYZE {st.row_count} rows", None
        if isinstance(ast, P.DropTable):
            return self._drop(ast)
        if isinstance(ast, P.Insert):
            return self._insert(ast)
        if isinstance(ast, P.Update):
            return self._update(ast)
        if isinstance(ast, P.Delete):
            return self._delete(ast)
        if isinstance(ast, P.CreateChangefeed):
            return self._create_changefeed(ast)
        if isinstance(ast, P.StreamChangefeed):
            return self._stream_changefeed(ast)
        if isinstance(ast, P.CreateMatView):
            return self._create_matview(ast)
        if isinstance(ast, P.DropMatView):
            return self._drop_matview(ast)
        if isinstance(ast, P.RefreshMatView):
            return self._refresh_matview(ast)
        if isinstance(ast, P.JobControl):
            return self._job_control(ast)
        raise BindError(f"unsupported statement {type(ast).__name__}")

    def _show_stmt(self, ast: "P.ShowStmt"):
        """SHOW QUERIES | SESSIONS | JOBS: sugar over the crdb_internal
        virtual-table providers, rendered in the ShowVar wire shape
        (object-dtype columns, no schema) — psql-friendly without a
        plan."""
        from cockroach_tpu.sql.vtable import TABLES, provider_rows

        table = {"queries": "cluster_queries",
                 "sessions": "cluster_sessions",
                 "jobs": "jobs"}[ast.kind]
        if ast.kind == "jobs" and isinstance(self.catalog,
                                             SessionCatalog):
            # attach the store's jobs registry so the provider sees it
            self._jobs_registry()
        rows = provider_rows(table, self.catalog)
        cols = [c for c, _, _ in TABLES[table][0]]
        payload = {c: np.asarray([r.get(c) for r in rows], dtype=object)
                   for c in cols}
        return "rows", payload, None

    # --------------------------------------- changefeeds / matviews / jobs

    def _matviews(self):
        """Catalog-attached MatViewManager (lazy; definitions load from
        the 0xFFC0 system keyspace once per catalog)."""
        from cockroach_tpu.sql.matview import MatViewManager

        cat = self.catalog
        mgr = getattr(cat, "_matview_mgr", None)
        if mgr is None:
            mgr = MatViewManager(cat)
            cat._matview_mgr = mgr
        return mgr

    def _jobs_registry(self):
        """Catalog-attached jobs Registry with the changefeed resumer
        registered (shared across sessions so CANCEL JOB fences feeds
        started by any session on this store)."""
        from cockroach_tpu.server.jobs import Registry
        from cockroach_tpu.sql import changefeed as _cf

        cat: SessionCatalog = self.catalog
        reg = getattr(cat, "_jobs_registry", None)
        if reg is None:
            reg = Registry(cat.store)
            _cf.register(reg, cat)
            cat._jobs_registry = reg
        return reg

    def _create_changefeed(self, ast: P.CreateChangefeed):
        from cockroach_tpu.sql.bind import bind_changefeed
        from cockroach_tpu.server.jobs import States

        cat: SessionCatalog = self.catalog
        desc, options = bind_changefeed(ast, cat)
        payload: dict = {"table": desc.name,
                         "options": {"resolved":
                                     bool(options.pop("resolved", False))}}
        sink_opt = options.pop("sink", None)
        if sink_opt:
            s = str(sink_opt)
            payload["sink"] = ({"kind": "file", "path": s[5:]}
                               if s.startswith("file:")
                               else {"kind": "memory", "token": s})
        if "target_wall" in options:
            payload["target"] = [int(options.pop("target_wall")), 0]
        for k in ("max_polls", "poll_interval_ms", "once"):
            if k in options:
                payload[k] = options.pop(k)
        finite = any(k in payload for k in ("target", "max_polls",
                                            "once"))
        run_opt = options.pop("run", None)
        if run_opt is not None and bool(run_opt) and not finite:
            # adopt_and_run would never return: a continuous feed has
            # no stop condition, so inline execution hangs the session
            raise BindError(
                "WITH run needs a stop condition (once / max_polls / "
                "target_wall); run continuous feeds on a background "
                "adopter and stop them with CANCEL JOB")
        run_inline = finite if run_opt is None else bool(run_opt)
        reg = self._jobs_registry()
        job_id = reg.create("changefeed", payload)
        if run_inline:
            reg.adopt_and_run()
            rec = reg.get(job_id)
            if rec.state == States.FAILED:
                raise SQLError("XX000", f"changefeed failed: {rec.error}")
        return "rows", {"job_id": np.asarray([job_id], np.int64)}, None

    def _stream_changefeed(self, ast: P.StreamChangefeed):
        from cockroach_tpu.sql.bind import bind_changefeed
        from cockroach_tpu.sql import changefeed as _cf

        cat: SessionCatalog = self.catalog
        desc, options = bind_changefeed(ast, cat)
        return "stream", _cf.stream_rows(cat, desc, options), None

    def _create_matview(self, ast: P.CreateMatView):
        self._matviews().create(ast.name, ast.sql, ast.if_not_exists)
        return "ok", "CREATE MATERIALIZED VIEW", None

    def _drop_matview(self, ast: P.DropMatView):
        self._matviews().drop(ast.name, ast.if_exists)
        return "ok", "DROP MATERIALIZED VIEW", None

    def _refresh_matview(self, ast: P.RefreshMatView):
        mv = self._matviews().get(ast.name)
        if mv is None:
            raise BindError(f"no materialized view {ast.name!r}")
        mv.refresh()
        return "ok", "REFRESH MATERIALIZED VIEW", None

    def _select_matview(self, ast: P.SelectStmt):
        """SELECT * FROM <view>: serve from the device-resident group
        state (refreshed to now), rows sorted by group key."""
        if (len(ast.items) != 1
                or not isinstance(ast.items[0][0], P.ColRef)
                or ast.items[0][0].name != "*"
                or ast.where is not None or ast.group_by
                or ast.order_by or ast.limit is not None):
            raise BindError("materialized views support only "
                            "SELECT * FROM <view> reads")
        payload, schema = self._matviews().read(ast.tables[0].name)
        return "rows", payload, schema

    def _job_control(self, ast: P.JobControl):
        reg = self._jobs_registry()
        if ast.op == "cancel":
            reg.cancel(ast.job_id)
        elif ast.op == "pause":
            reg.pause(ast.job_id)
        else:
            reg.resume(ast.job_id)
        return "ok", f"{ast.op.upper()} JOB", None

    # ------------------------------------------------------ transactions

    def _txn_control(self, ast: P.TxnControl):
        """BEGIN / COMMIT / ROLLBACK (conn_executor txn state machine).

        Mutations inside an open transaction buffer in one kv.Txn and
        apply atomically at COMMIT with serializable validation (a
        conflict surfaces at COMMIT as a retryable error, the
        Postgres-style 'restart transaction'). SELECTs inside the
        transaction run the columnar scan path over COMMITTED data —
        read-your-writes within an open txn applies to UPDATE/DELETE
        predicate evaluation (which reads through the txn), not yet to
        SELECT (tracked gap)."""
        if self.db is None:
            raise BindError("transactions need a storage-backed session")
        if ast.op == "begin":
            if self._txn is not None:
                raise BindError("there is already a transaction open")
            self._txn = self.db.txn()
            self._txn_aborted = False
            self._txn_row_deltas = {}
            return "ok", "BEGIN", None
        if self._txn is None:
            raise BindError("no transaction is open")
        txn, self._txn = self._txn, None
        deltas, self._txn_row_deltas = self._txn_row_deltas, {}
        aborted, self._txn_aborted = self._txn_aborted, False
        if ast.op == "rollback" or aborted:
            # COMMIT of an aborted transaction rolls back (Postgres)
            txn.rollback()
            return "ok", "ROLLBACK", None
        try:
            txn.commit()
        except TxnRetryError as e:
            raise BindError(f"restart transaction: {e}") from e
        # stats deltas apply only once the writes are durable
        if isinstance(self.catalog, SessionCatalog):
            for tname, d in deltas.items():
                try:
                    desc = self.catalog.desc(tname)
                except BindError:
                    continue  # table dropped meanwhile
                desc.row_count = max(0, desc.row_count + d)
                self.catalog.save(desc)
        return "ok", "COMMIT", None

    def _create_index(self, ast: P.CreateIndex):
        """CREATE INDEX: allocate the index keyspace, BACKFILL it as a
        checkpointed job (the reference's index backfiller runs as a
        resumable job over DistSQL flows, sql/backfill + jobs), then
        publish the index in the descriptor. Maintenance of later DML is
        synchronous (see _index_ops)."""
        from cockroach_tpu.server.jobs import Registry, States

        cat: SessionCatalog = self.catalog
        desc = cat.desc(ast.table)
        types = dict(desc.columns)
        if ast.column not in types:
            raise BindError(f"unknown column {ast.column!r}")
        if types[ast.column] != "int":
            raise BindError("only INT columns are indexable (composite "
                            "byte index keys arrive with the key codec)")
        if ast.column == desc.pk:
            raise BindError("the primary key already orders the table")
        if ast.column in desc.indexes:
            raise BindError(f"index on {ast.column!r} already exists")
        idx_id = cat._next_id()
        value_names = [c for c, _ in desc.value_columns()]
        ci = value_names.index(ast.column)
        store = cat.store

        def backfill(registry: Registry, rec):
            start_pk = int(rec.progress.get("start_pk", 0))
            ts = store.clock.now()
            chunk = 512
            while True:
                keys = store.engine.scan_keys(
                    struct.pack(">HQ", desc.table_id, start_pk),
                    struct.pack(">HQ", desc.table_id + 1, 0), ts,
                    max_rows=chunk)
                if not keys:
                    break
                for k in keys:
                    rid = struct.unpack(">HQ", k)[1]
                    hit = store.get(desc.table_id, rid, ts)
                    if hit is None:
                        continue
                    v = hit[0][ci]
                    store.put(idx_id, _index_pk(v, rid), [rid, v])
                start_pk = struct.unpack(">HQ", keys[-1])[1] + 1
                registry.checkpoint(rec.id, rec.lease_epoch,
                                    {"start_pk": start_pk})
                if len(keys) < chunk:
                    break

        reg = Registry(store)
        reg.register_resumer("index_backfill", backfill)
        job_id = reg.create("index_backfill", {
            "table": ast.table, "column": ast.column,
            "index_id": idx_id, "name": ast.name})
        reg.adopt_and_run()
        rec = reg.get(job_id)
        if rec.state != States.SUCCEEDED:
            raise BindError(f"index backfill failed: {rec.error}")
        desc.indexes[ast.column] = idx_id
        cat.save(desc)
        return "ok", "CREATE INDEX", None

    def _column_backfill(self, desc: TableDescriptor, kind: str,
                         phys_i: int, job_name: str):
        """Checkpointed row-rewrite job shared by ALTER TABLE ADD/DROP
        (reference: sql/rowexec/backfiller.go via the jobs registry,
        same machinery as the CREATE INDEX backfill). ADD normalizes
        every row to the new physical layout (value slot + NULL bit);
        DROP scrubs the dead slot to NULL. Progress checkpoints by
        primary key; a crash mid-backfill resumes from the watermark."""
        from cockroach_tpu.server.jobs import Registry, States

        cat: SessionCatalog = self.catalog
        store = cat.store
        n_phys = sum(1 for _ in desc.value_columns())

        def backfill(registry: Registry, rec):
            start_pk = int(rec.progress.get("start_pk", 0))
            ts = store.clock.now()
            chunk = 256
            while True:
                keys = store.engine.scan_keys(
                    struct.pack(">HQ", desc.table_id, start_pk),
                    struct.pack(">HQ", desc.table_id + 1, 0), ts,
                    max_rows=chunk)
                if not keys:
                    break
                from cockroach_tpu.util.fault import maybe_fail

                maybe_fail("alter.backfill_chunk")
                for kk in keys:
                    rid = struct.unpack(">HQ", kk)[1]
                    hit = store.get(desc.table_id, rid)
                    if hit is None:
                        continue
                    fields = list(hit[0])
                    # split off the mask (absent on legacy rows)
                    if kind == "add":
                        old_n = n_phys - 1
                        vals = fields[:old_n]
                        mask = fields[old_n] if len(fields) > old_n \
                            else 0
                        vals += [0] * (old_n - len(vals))
                        vals.append(0)                 # the new slot
                        mask |= 1 << phys_i            # starts NULL
                    else:
                        vals = fields[:n_phys]
                        mask = fields[n_phys] if len(fields) > n_phys \
                            else 0
                        vals += [0] * (n_phys - len(vals))
                        vals[phys_i] = 0               # scrub
                        mask |= 1 << phys_i
                    store.put(desc.table_id, rid, vals + [mask])
                start_pk = struct.unpack(">HQ", keys[-1])[1] + 1
                registry.checkpoint(rec.id, rec.lease_epoch,
                                    {"start_pk": start_pk})
                if len(keys) < chunk:
                    break

        reg = Registry(store)
        reg.register_resumer(job_name, backfill)
        job_id = reg.create(job_name, {
            "table": desc.name, "kind": kind, "phys_i": phys_i})
        reg.adopt_and_run()
        rec = reg.get(job_id)
        if rec.state != States.SUCCEEDED:
            raise BindError(f"column backfill failed: {rec.error}")

    def _alter(self, ast: P.AlterTable):
        """ALTER TABLE ADD/DROP COLUMN (schemachanger in miniature):
        the column's PHYSICAL slot is allocated/retired in the
        descriptor, a checkpointed backfill rewrites rows, and only
        then does ADD become public (reads during the backfill see the
        old schema; writers already produce the new layout)."""
        cat: SessionCatalog = self.catalog
        desc = cat.desc(ast.table)
        if any(t.startswith("vector(") for _, t in desc.columns):
            # multi-slot columns break the backfiller's 1-slot-per-column
            # row rewrite; lift when the backfill goes slot-aware
            raise BindError("ALTER TABLE is not supported on tables "
                            "with VECTOR columns")
        if ast.op == "add" and ast.type_name.startswith("vector("):
            raise BindError("ALTER TABLE ADD of a VECTOR column is not "
                            "supported — declare it at CREATE TABLE")
        if ast.op == "add":
            if desc.backfilling == ast.column:
                # resume after a crashed backfill: rerun the job (row
                # rewrites are idempotent; checkpoints bound the redo)
                phys_i = [c for c, _ in desc.value_columns()].index(
                    ast.column)
                self._column_backfill(desc, "add", phys_i, "add_column")
                desc.backfilling = None
                cat.save(desc)
                return "ok", "ALTER TABLE", None
            if any(c == ast.column for c, _ in desc.columns):
                raise BindError(f"column {ast.column!r} already exists "
                                "(dropped slots keep their name)")
            if ast.type_name == "float":
                raise BindError("FLOAT storage columns are not "
                                "supported — use DECIMAL")
            desc.columns.append((ast.column, ast.type_name))
            if ast.type_name == "string":
                desc.dicts.setdefault(ast.column, [])
            desc.backfilling = ast.column
            cat.save(desc)
            phys_i = [c for c, _ in desc.value_columns()].index(
                ast.column)
            self._column_backfill(desc, "add", phys_i, "add_column")
            desc.backfilling = None
            cat.save(desc)
            return "ok", "ALTER TABLE", None
        # drop
        if not any(c == ast.column and desc.visible(c)
                   for c, _ in desc.columns):
            raise BindError(f"no column {ast.column!r}")
        if ast.column == desc.pk:
            raise BindError("cannot drop the PRIMARY KEY")
        if ast.column in desc.indexes:
            raise BindError(f"drop index on {ast.column!r} first")
        desc.dropped.append(ast.column)  # invisible immediately
        cat.save(desc)
        phys_i = [c for c, _ in desc.value_columns()].index(ast.column)
        self._column_backfill(desc, "drop", phys_i, "drop_column")
        return "ok", "ALTER TABLE", None

    def _index_ops(self, desc: TableDescriptor, txn, rowid: int,
                   old_fields, new_fields) -> None:
        """Synchronous secondary-index maintenance for one row mutation
        (old_fields/new_fields = value-field lists or None)."""
        if not desc.indexes:
            return
        value_names = [c for c, _ in desc.value_columns()]
        for col, idx_id in desc.indexes.items():
            i = value_names.index(col)
            # NULL values have no index entry (field_value -> None)
            old_v = (desc.field_value(old_fields, i)
                     if old_fields is not None else None)
            new_v = (desc.field_value(new_fields, i)
                     if new_fields is not None else None)
            if old_v == new_v:
                continue
            if old_v is not None:
                txn.delete(idx_id, _index_pk(int(old_v), rowid))
            if new_v is not None:
                txn.put(idx_id, _index_pk(int(new_v), rowid),
                        [rowid, int(new_v)])

    def _run_dml(self, op) -> None:
        """Run a mutation closure: inside the open transaction when one
        exists (deferred commit), else auto-commit with retries.

        Mutations from concurrent sessions serialize under the shared
        catalog's lock: the closures mutate descriptor state in place
        (string dictionaries grow in _encode_value, next_rowid bumps)
        which no MVCC version check protects."""
        import contextlib

        mu = getattr(self.catalog, "_mu", None)
        with (mu if mu is not None else contextlib.nullcontext()):
            if self._txn is not None:
                if self._txn_aborted:
                    raise BindError("current transaction is aborted — "
                                    "ROLLBACK to continue")
                op(self._txn)
            else:
                self.db.run(op)

    def _bump_rows(self, cat: "SessionCatalog", desc: "TableDescriptor",
                   delta: int) -> None:
        """Row-count stats: immediate in auto-commit; deferred to COMMIT
        inside an open transaction (a rollback must not drift stats)."""
        if self._txn is not None:
            self._txn_row_deltas[desc.name] = (
                self._txn_row_deltas.get(desc.name, 0) + delta)
        else:
            desc.row_count = max(0, desc.row_count + delta)
        cat.save(desc)  # dictionaries/rowid watermark persist either way

    # ------------------------------------------------------------- vars --

    def _get_var(self, name: str):
        if name == "statement_timeout":
            # SHOW reports the EFFECTIVE deadline (session override or
            # the sql.defaults.statement_timeout fallback)
            return self._statement_timeout()
        key = self._VARS[name]
        if key is None:
            return self.vars.get(name)
        from cockroach_tpu.util.settings import Settings

        return Settings().get(key)

    _DISTSQL_MODES = ("off", "on", "always")

    def _distsql(self):
        """-> (mesh, strict): the mesh this session's SELECTs are
        distributed over, or None for the single-chip ladder, and whether
        a plan the distributed runner declines is an error (`always`).
        `distsql`: `off` (the default) never
        distributes; `on` distributes whenever the node has a mesh
        (Catalog.mesh) and the distributed grammar takes the plan, and
        runs the single-chip ladder otherwise; `always` makes a
        statement that cannot be distributed (no mesh, a plan outside
        the grammar) an error, never a silent single-chip run.
        Upstream's default is `auto`; there is none here until a
        measurement says when several chips beat one (ROADMAP U1)."""
        mode = self.vars["distsql"]
        if mode == "off":
            return None, False
        mesh = self.catalog.mesh
        if mesh is None and mode == "always":
            raise SQLError(
                "0A000", "distsql = always: this node has no device mesh "
                "to distribute over (Catalog.mesh)")
        return mesh, mode == "always"

    def _set_var(self, ast: P.SetVar):
        if ast.name not in self._VARS:
            raise BindError(f"unknown session variable {ast.name!r}")
        value = ast.value
        if ast.name == "distsql":
            if value not in self._DISTSQL_MODES:
                raise SQLError(
                    "22023", f"distsql takes one of "
                    f"{', '.join(self._DISTSQL_MODES)}, not {value!r}")
        elif ast.name not in ("pallas", "vectorize"):  # string-valued vars
            if value in ("on", "true"):
                value = True
            elif value in ("off", "false"):
                value = False
        key = self._VARS[ast.name]
        if key is None:
            self.vars[ast.name] = value
        else:
            from cockroach_tpu.util.settings import Settings

            Settings().set(key, value)
        return "ok", f"SET {ast.name}", None

    # -------------------------------------------------------------- DDL --

    def _create(self, ast: P.CreateTable):
        cat: SessionCatalog = self.catalog
        if ast.if_not_exists and ast.name in cat._descs:
            return "ok", "CREATE TABLE", None
        pk = None
        for c in ast.columns:
            if c.type_name == "float":
                raise BindError(
                    "FLOAT storage columns are not supported yet — use "
                    "DECIMAL (the row codec is exact int64 lanes)")
            if c.primary_key:
                if c.type_name != "int":
                    raise BindError("PRIMARY KEY must be an INT column")
                if pk is not None:
                    raise BindError("multiple primary keys")
                pk = c.name
        cols = [(c.name, c.type_name) for c in ast.columns]
        cat.create(ast.name, cols, pk,
                   notnull=[c.name for c in ast.columns if c.not_null])
        return "ok", "CREATE TABLE", None

    def _drop(self, ast: P.DropTable):
        cat: SessionCatalog = self.catalog
        if ast.name not in cat._descs:
            if ast.if_exists:
                return "ok", "DROP TABLE", None
            raise BindError(f"no table {ast.name!r}")
        cat.drop(ast.name)
        return "ok", "DROP TABLE", None

    # -------------------------------------------------------------- DML --

    def _encode_value(self, desc: TableDescriptor, cname: str,
                      tname: str, v) -> int:
        ty = _type_of(tname)
        if v is None:
            if not desc.nullable(cname):
                raise BindError(
                    f"null value in column {cname!r} violates "
                    f"not-null constraint")
            if ty.kind is Kind.VECTOR:
                return [0] * ty.dim
            return 0  # caller sets the row's NULL-bitmap bit
        if ty.kind is Kind.VECTOR:
            from cockroach_tpu.ops.vector import parse_vector_literal

            if isinstance(v, str):
                try:
                    v = parse_vector_literal(v)
                except ValueError as err:
                    raise BindError(f"bad vector literal: {err}")
            arr = np.asarray(v, dtype=np.float32)
            if arr.shape != (ty.dim,):
                raise BindError(
                    f"column {cname!r} expects a {ty.dim}-dim vector, "
                    f"got shape {arr.shape}")
            return [int(x) for x in arr.view(np.uint32)]
        if ty.kind is Kind.DECIMAL:
            return int(Decimal(str(v)).scaleb(ty.scale)
                       .to_integral_value(ROUND_HALF_UP))
        if ty.kind is Kind.STRING:
            d = desc.dicts[cname]
            s = str(v)
            if s in d:
                return d.index(s)
            d.append(s)  # grow the dictionary (persisted with the desc)
            return len(d) - 1
        if ty.kind is Kind.DATE and isinstance(v, str):
            dt = datetime.date.fromisoformat(v)
            return (dt - datetime.date(1970, 1, 1)).days
        return int(v)

    def _literal(self, node: P.Node):
        if isinstance(node, P.Num):
            return node.value
        if isinstance(node, P.Str):
            return node.value
        if isinstance(node, P.DateLit):
            return node.days
        if isinstance(node, P.NullLit):
            return None
        if isinstance(node, P.BoolLit):
            return node.value
        if isinstance(node, P.Unary) and node.op == "-":
            inner = self._literal(node.arg)
            return -inner
        raise BindError("INSERT VALUES must be literals")

    def _insert(self, ast: P.Insert):
        cat: SessionCatalog = self.catalog
        desc = cat.desc(ast.table)
        col_names = [c for c, _ in desc.visible_columns()]
        target = ast.columns or col_names
        unknown = set(target) - set(col_names)
        if unknown:
            raise BindError(f"unknown columns {sorted(unknown)}")
        missing = set(c for c, _ in desc.visible_columns()
                      if c != desc.pk) - set(target)
        if desc.pk is not None and desc.pk not in target:
            raise BindError(f"missing PRIMARY KEY {desc.pk!r}")
        not_nullable = [c for c in missing if not desc.nullable(c)]
        if not_nullable:
            raise BindError(f"INSERT missing NOT NULL columns "
                            f"{sorted(not_nullable)}")
        n = 0
        new_rows = 0

        def op(txn):
            nonlocal n, new_rows
            n = new_rows = 0
            for row in ast.rows:
                if len(row) != len(target):
                    raise BindError("VALUES arity mismatch")
                vals = {c: self._literal(v) for c, v in zip(target, row)}
                for c, _t in desc.value_columns():
                    # unnamed nullable + dropped/backfilling slots: NULL
                    vals.setdefault(c, None)
                old = None
                if desc.pk is not None:
                    rowid = int(vals[desc.pk])
                    old = txn.get(desc.table_id, rowid)
                    new_row = old is None
                    if not new_row and not ast.upsert:
                        # Postgres duplicate-key error (the reference
                        # raises pgcode 23505); overwrite semantics are
                        # reserved for an explicit UPSERT
                        raise BindError(
                            f"duplicate key value violates unique "
                            f"constraint ({desc.pk}={rowid})")
                else:
                    rowid = desc.next_rowid
                    desc.next_rowid += 1
                    new_row = True
                fields = []
                for c, t in desc.value_columns():
                    ev = self._encode_value(desc, c, t, vals[c])
                    # VECTOR columns encode to d slots
                    fields.extend(ev if isinstance(ev, list) else [ev])
                mask = 0
                for i, (c, _t) in enumerate(desc.value_columns()):
                    if vals[c] is None:
                        mask |= 1 << i
                fields.append(mask)  # hidden NULL bitmap (value_columns)
                txn.put(desc.table_id, rowid, fields)
                self._index_ops(desc, txn, rowid, old, fields)
                n += 1
                new_rows += int(new_row)

        self._run_dml(op)
        self._bump_rows(cat, desc, new_rows)
        return "ok", f"INSERT {n}", None

    def _scan_rows(self, desc: TableDescriptor, txn):
        """-> [(rowid, {col: datum})] decoded for predicate evaluation."""
        from cockroach_tpu.exec.rowexec import _decode

        schema = desc.schema()
        out = []
        # read-your-writes: rows inserted by THIS txn are not in the
        # store yet — merge the txn's buffered pks into the scan
        pks = sorted(set(txn.scan_pks(desc.table_id))
                     | set(txn.buffered_pks(desc.table_id)))
        n_slots = desc.value_slots()
        for rowid in pks:
            fields = txn.get(desc.table_id, rowid)
            if fields is None:
                continue
            mask = fields[n_slots] if len(fields) > n_slots else 0
            row: Dict[str, object] = {}
            vi = 0   # value-column index (NULL bitmap bit)
            off = 0  # physical slot offset
            for cname, tname in desc.columns:
                ty = _type_of(tname)
                if cname == desc.pk:
                    row[cname] = rowid
                    continue
                s = _slots_of(tname)
                null = ((mask >> vi) & 1) == 1 or off >= len(fields)
                raw = None if null else fields[off:off + s]
                vi += 1
                off += s
                if not desc.visible(cname):
                    continue
                if raw is None:
                    row[cname] = None
                    continue
                if ty.kind is Kind.VECTOR:
                    row[cname] = _slots_to_f32(
                        np.asarray([raw], dtype=np.int64))[0]
                    continue
                row[cname] = _decode(
                    np.asarray([raw[0]]), None, ty,
                    schema.dictionary(cname))[0]
            out.append((rowid, row))
        return out

    def _update(self, ast: P.Update):
        from cockroach_tpu.exec.rowexec import eval_datum
        from cockroach_tpu.sql.bind import Binder

        cat: SessionCatalog = self.catalog
        desc = cat.desc(ast.table)
        types = dict(desc.visible_columns())
        for col, _ in ast.sets:
            if col not in types:
                raise BindError(f"unknown column {col!r}")
            if col == desc.pk:
                raise BindError("cannot UPDATE the primary key")
        binder = Binder(cat)
        schema = desc.schema()
        binder.scope_one_table(ast.table, schema)
        where = (binder._bind_scalar(ast.where)[0]
                 if ast.where is not None else None)
        sets = [(c, binder._bind_scalar(e)[0]) for c, e in ast.sets]
        n = 0

        def op(txn):
            nonlocal n
            n = 0
            for rowid, row in self._scan_rows(desc, txn):
                if where is not None and \
                        eval_datum(where, row, schema) is not True:
                    continue
                new = dict(row)
                for c, e in sets:
                    new[c] = eval_datum(e, row, schema)
                for c, _t in desc.value_columns():
                    new.setdefault(c, None)  # dropped/backfilling slots
                old_fields = txn.get(desc.table_id, rowid)
                fields = []
                for c, t in desc.value_columns():
                    ev = self._encode_value(desc, c, t, new[c])
                    fields.extend(ev if isinstance(ev, list) else [ev])
                mask = 0
                for i, (c, _t) in enumerate(desc.value_columns()):
                    if new[c] is None:
                        mask |= 1 << i
                fields.append(mask)
                txn.put(desc.table_id, rowid, fields)
                self._index_ops(desc, txn, rowid, old_fields, fields)
                n += 1

        self._run_dml(op)
        cat.save(desc)
        return "ok", f"UPDATE {n}", None

    def _delete(self, ast: P.Delete):
        from cockroach_tpu.exec.rowexec import eval_datum
        from cockroach_tpu.sql.bind import Binder

        cat: SessionCatalog = self.catalog
        desc = cat.desc(ast.table)
        binder = Binder(cat)
        schema = desc.schema()
        binder.scope_one_table(ast.table, schema)
        where = (binder._bind_scalar(ast.where)[0]
                 if ast.where is not None else None)
        n = 0

        def op(txn):
            nonlocal n
            n = 0
            for rowid, row in self._scan_rows(desc, txn):
                if where is not None and \
                        eval_datum(where, row, schema) is not True:
                    continue
                old_fields = txn.get(desc.table_id, rowid)
                txn.delete(desc.table_id, rowid)
                self._index_ops(desc, txn, rowid, old_fields, None)
                n += 1

        self._run_dml(op)
        self._bump_rows(cat, desc, -n)
        return "ok", f"DELETE {n}", None
