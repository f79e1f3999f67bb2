"""Logical query plans + the plan -> operator-tree builder.

Reference seams (SURVEY.md §2.4, §7.2 M5):
- the declarative plan nodes are the memo-expression analog
  (pkg/sql/opt/memo/memo.go:116) in miniature;
- `normalize()` is the normalization-rules pass (opt/norm/rules/*.opt):
  predicate pushdown through projections/joins down to scans, OrderBy+
  Limit -> top-K, ordered-aggregate detection;
- `build()` is the NewColOperator porting seam
  (pkg/sql/colexec/colbuilder/execplan.go:785): pattern-match each node,
  assemble exec/ operators — adding a new query requires ONLY a plan
  definition, never operator-wiring code;
- `run()` makes the single-vs-distributed decision
  (distsql_physical_planner.go DistSQL on/off): with a mesh, the plan
  executes through parallel/dist_flow's shard_map runner.

Tables come from a `Catalog`: anything resolving a name to (schema,
chunk stream) — the TPC-H generator and the MVCC storage layer both
implement it, so the same plans run over synthetic data or the C++ LSM.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cockroach_tpu.coldata.batch import Schema
from cockroach_tpu.exec.operators import (
    DistinctOp, HashAggOp, JoinOp, LimitOp, MapOp, Operator, OrderedAggOp,
    ScanOp, ShrinkOp, SortOp, TopKOp,
)
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.expr import BoolOp, Cmp, Col, Expr, Lit
from cockroach_tpu.ops.sort import SortKey


# ---------------------------------------------------------------- catalog --

class Catalog:
    """Resolve a table name to (Schema, chunks_thunk)."""

    # the device mesh of the node that serves this catalog
    # (parallel/mesh.make_mesh), or None: a node with one device. It
    # belongs to the node, not to a call: a session whose `distsql` is
    # not `off` distributes its SELECTs over it (sql/session.py)
    mesh = None

    def with_mesh(self, mesh) -> "Catalog":
        self.mesh = mesh
        return self

    def table_schema(self, name: str) -> Schema:
        raise NotImplementedError

    def table_chunks(self, name: str, capacity: int):
        """-> a zero-arg callable yielding column-dict chunks."""
        raise NotImplementedError

    def table_rows(self, name: str) -> int:
        """Row-count estimate for join ordering (stats histogram analog,
        pkg/sql/stats)."""
        return 1 << 20

    def table_pk(self, name: str) -> Optional[Tuple[str, ...]]:
        """Primary-key columns (uniqueness info for semi-join rewrites)."""
        return None

    def table_indexes(self, name: str) -> Dict[str, object]:
        """column name -> index metadata for secondary indexes."""
        return {}

    def table_stats(self, name: str):
        """Optional sql/stats.TableStats (ANALYZE output) for costing."""
        return None

    def index_chunks(self, name: str, column: str, lo: int, hi: int,
                     capacity: int, columns=None):
        """Chunk thunk for an IndexScan (index entries in [lo, hi] ->
        primary-row lookups)."""
        raise NotImplementedError

    def scan_cache_key(self, name: str, columns, capacity: int
                       ) -> Optional[tuple]:
        """Content-identity tuple for the cross-query scan-image cache
        (exec/scan_cache.py), or None to disable sharing. Must derive
        from the underlying DATA identity, never from this catalog
        object — catalogs are rebuilt per statement while the data
        persists."""
        return None

    def scan_source(self, name: str, columns=None):
        """(store, table_id, read ts, column indices) of the MVCC store
        backing this table, or None when the table has no reachable
        store (generated data, index feeds). The indices map each
        projected output column to its row in the resident value lanes.
        Distributed ingest (parallel/ingest.py) uses the handle to make
        the device-resident MVCC image the shard unit — write deltas
        then refresh only the owning pk-range shard."""
        return None


_TPCH_PKS = {
    "part": ("p_partkey",), "supplier": ("s_suppkey",),
    "customer": ("c_custkey",), "orders": ("o_orderkey",),
    "nation": ("n_nationkey",), "region": ("r_regionkey",),
    "partsupp": ("ps_partkey", "ps_suppkey"),
    "lineitem": ("l_orderkey", "l_linenumber"),
}


class TPCHCatalog(Catalog):
    def __init__(self, gen):
        self.gen = gen
        self._stats_cache: Dict[str, object] = {}

    def table_stats(self, name: str):
        if name not in self._stats_cache:
            import itertools

            from cockroach_tpu.sql.stats import sample_stats

            # bounded sample: the FIRST 4 x 16K chunks only (draining the
            # generator would materialize the whole table at plan time);
            # the exact row count comes from the generator. Bounds are
            # therefore prefix-biased — fine for selectivities, and said
            # so, for what needs them exact (key_domains)
            st = sample_stats(
                itertools.islice(self.gen.chunks(name, 1 << 14), 4),
                self.gen.schema(name))
            st.row_count = self.gen.num_rows(name)
            st.exact_bounds = False
            self._stats_cache[name] = st
        return self._stats_cache[name]

    def table_schema(self, name: str) -> Schema:
        return self.gen.schema(name)

    def table_rows(self, name: str) -> int:
        return self.gen.num_rows(name)

    def table_pk(self, name: str) -> Optional[Tuple[str, ...]]:
        return _TPCH_PKS.get(name)

    def table_chunks(self, name: str, capacity: int, columns=None):
        gen = self.gen

        def chunks():
            for c in gen.chunks(name, capacity):
                yield ({k: c[k] for k in columns} if columns else c)

        return chunks

    def scan_cache_key(self, name: str, columns, capacity: int
                       ) -> Optional[tuple]:
        # generated data is a pure function of (sf, seed): images are
        # shareable across generator AND catalog instances
        return ("tpch", float(self.gen.sf),
                int(getattr(self.gen, "seed", 0)), name, int(capacity),
                tuple(columns or ()))


class MVCCCatalog(Catalog):
    """Tables served by the MVCC storage layer (storage/mvcc.py): name ->
    (table_id, Schema); scans stream the newest-visible rows through the
    native columnar scanner."""

    def __init__(self, store, tables: Dict[str, Tuple[int, Schema]],
                 rows: Optional[Dict[str, int]] = None,
                 pks: Optional[Dict[str, Tuple[str, ...]]] = None,
                 stats: Optional[Dict[str, object]] = None, mesh=None):
        self.store = store
        self.mesh = mesh
        self.tables = dict(tables)
        self.rows = dict(rows or {})
        self.pks = dict(pks or {})
        self.stats = dict(stats or {})
        self._scan_ts: Dict[str, object] = {}  # name -> pinned read ts
        # one prepared-statement cache for every session of this catalog,
        # as SessionCatalog has (sql/session.py adopts the pair): a
        # statement planned on one connection is warm on all, and what a
        # flow restart widened on its tree stays widened for the process
        self.shared_prepared = (OrderedDict(), threading.Lock())

    def table_stats(self, name: str):
        return self.stats.get(name)

    def table_schema(self, name: str) -> Schema:
        return self.tables[name][1]

    def table_rows(self, name: str) -> int:
        return self.rows.get(name, super().table_rows(name))

    def table_pk(self, name: str) -> Optional[Tuple[str, ...]]:
        return self.pks.get(name)

    def table_chunks(self, name: str, capacity: int, columns=None):
        table_id, schema = self.tables[name]
        all_names = [f.name for f in schema]
        store = self.store
        # the row codec is positional: the scanner always decodes the
        # full field tuple; a pruned (non-prefix) column subset is
        # projected host-side after decode (native-scanner column
        # pushdown is a later optimization)
        wanted = list(columns) if columns else all_names
        # snapshot semantics: pin the read timestamp at plan time, the
        # same instant scan_cache_key samples the table's write version —
        # the cached image and the stream it came from can never diverge
        # (a later write is invisible at this ts AND rotates the key)
        ts = store.clock.now()
        self._scan_ts[name] = ts  # scan_source shares the same snapshot

        def chunks():
            for c in store.scan_chunks(table_id, len(all_names), capacity,
                                       ts=ts, col_names=all_names):
                yield {n: c[n] for n in wanted}

        return chunks

    def scan_cache_key(self, name: str, columns, capacity: int
                       ) -> Optional[tuple]:
        table_id, schema = self.tables[name]
        cols = tuple(columns) if columns else tuple(f.name for f in schema)
        return self.store.scan_cache_prefix(table_id) + (
            self.store.table_version(table_id), int(capacity), cols)

    def scan_source(self, name: str, columns=None):
        table_id, schema = self.tables[name]
        all_names = [f.name for f in schema]
        wanted = list(columns) if columns else all_names
        ts = self._scan_ts.get(name) or self.store.clock.now()
        return (self.store, table_id, ts,
                tuple(all_names.index(n) for n in wanted))


# ------------------------------------------------------------- plan nodes --

@dataclass(frozen=True)
class Plan:
    def inputs(self) -> tuple:
        return ()


@dataclass(frozen=True)
class Scan(Plan):
    table: str
    columns: Optional[Tuple[str, ...]] = None


@dataclass(frozen=True)
class IndexScan(Plan):
    """Constrained scan through a secondary index: read index entries in
    [lo, hi] on `column`, then fetch the matching primary rows — the
    index-join/joinReader shape (pkg/sql/rowexec/joinreader.go:74,
    colfetcher/index_join.go). Residual predicates stay in a Filter
    above (the index bound is a superset guarantee, not the filter)."""

    table: str
    column: str
    lo: int
    hi: int          # inclusive
    columns: Optional[Tuple[str, ...]] = None


@dataclass(frozen=True)
class Filter(Plan):
    input: Plan
    predicate: Expr

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Shrink(Plan):
    """Adaptive capacity compaction (exec ShrinkOp): placed after
    operators whose live output is expected to be a tiny fraction of
    its static capacity (HAVING filters; joins against shrunk builds)."""

    input: Plan
    start_capacity: int = 1 << 12

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Project(Plan):
    input: Plan
    outputs: Tuple[Tuple[str, Expr], ...]  # complete output column list

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Join(Plan):
    left: Plan
    right: Plan
    left_on: Tuple[str, ...]
    right_on: Tuple[str, ...]
    how: str = "inner"
    # a semi or anti join's predicate beside the key equality, over the
    # left's columns and the right's: a left row has a match iff a right
    # row of its key makes it TRUE (NULL is no match). Only where the
    # right is UNIQUE on `right_on` (decorrelate() puts an Aggregate on
    # the key there): build() lowers the join as an inner (semi) or left
    # (anti) join, a filter and a projection back to the left's columns,
    # which answers a duplicate key with a duplicate row.
    residual: Optional[Expr] = None

    def inputs(self):
        return (self.left, self.right)


@dataclass(frozen=True)
class Aggregate(Plan):
    input: Plan
    group_by: Tuple[str, ...]
    aggs: Tuple[AggSpec, ...]

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class OrderBy(Plan):
    input: Plan
    keys: Tuple[SortKey, ...]

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Limit(Plan):
    input: Plan
    n: int
    offset: int = 0

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Distinct(Plan):
    input: Plan
    keys: Optional[Tuple[str, ...]] = None

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Window(Plan):
    input: Plan
    partition_by: Tuple[str, ...]
    order_by: Tuple[SortKey, ...]
    specs: Tuple  # ops.window.WindowSpec

    def inputs(self):
        return (self.input,)


@dataclass(frozen=True)
class Apply(Plan):
    """Correlated subquery (the lateral-apply shape, opt/norm's
    TryDecorrelate* rules): for each `input` row, the subquery `sub`
    restricted to the rows whose `correlation` columns match. Never
    executed directly — `decorrelate()` rewrites every Apply into the
    join+aggregate form before the builder runs (arXiv:2203.01877 §4's
    plan-level decorrelation, which is what lets correlated shapes reach
    the tensor path at all):

    - kind="exists"     -> semi  Join(input, sub) on the correlation
    - kind="not_exists" -> anti  Join(input, sub) on the correlation
    - either with a `residual`, ONE comparison `Cmp(op, Col(inner x),
      Col(outer y))` beside the equalities (`b.x <> a.y`: TPC-H Q21) ->
      Aggregate(sub, group_by=inner correlation cols, min x and/or max x)
      and the semi / anti join against that unique build with the
      comparison rewritten over the extremes: some `b.x <> a.y` holds iff
      `min(b.x) <> a.y OR max(b.x) <> a.y`; `<` and `<=` ask the minimum
      alone, `>` and `>=` the maximum. min and max skip NULLs and a NULL
      `a.y` compares to NULL, so three-valued logic is kept
    - kind="scalar"     -> Aggregate(sub, group_by=inner correlation
      cols, (scalar,)) + LEFT Join — empty groups surface as NULL
      (SQL's empty-scalar-subquery semantics) through the left join's
      validity. An EMPTY correlation (an uncorrelated scalar subquery,
      Q15/Q22 shape) joins on an injected constant key: the single
      aggregate row broadcasts to every input row.
    """

    input: Plan
    sub: Plan
    correlation: Tuple[Tuple[str, str], ...]  # (outer col, inner col)
    kind: str = "exists"        # "exists" | "not_exists" | "scalar"
    scalar: Optional[AggSpec] = None   # kind="scalar": the aggregate
    residual: Optional[Cmp] = None     # exists / not_exists: see above

    def inputs(self):
        return (self.input, self.sub)


@dataclass(frozen=True)
class VectorTopK(Plan):
    """ORDER BY <vector distance> LIMIT k — the vector-search node
    (arXiv:2605.15957's in-engine placement). `ann=False` lowers to the
    fused filter -> distance projection -> TopK composition over existing
    operators (so prepared/exec caches apply unchanged); `ann=True` (bare
    scans only — filtered queries stay exact) lowers to VectorANNOp, a
    clustered-index probe with the recall/latency `nprobe` dial."""

    input: Plan
    column: str                 # VECTOR column being ranked
    query: Tuple[float, ...]    # bind-time constant query vector
    metric: str                 # "l2" (<->) | "cos" (<=>)
    k: int
    ann: bool = False
    nprobe: int = 4

    def inputs(self):
        return (self.input,)


# ------------------------------------------------------------ normalization

def _expr_columns(e: Expr, out: set) -> set:
    if isinstance(e, Col):
        out.add(e.name)
    for child in getattr(e, "__dict__", {}).values():
        if isinstance(child, Expr):
            _expr_columns(child, out)
        elif isinstance(child, (tuple, list)):
            for c in child:
                if isinstance(c, Expr):
                    _expr_columns(c, out)
    return out


def _plan_columns(p: Plan, catalog: Catalog) -> List[str]:
    """Output column names of a plan node."""
    if isinstance(p, (Scan, IndexScan)):
        schema = catalog.table_schema(p.table)
        return list(p.columns) if p.columns else schema.names()
    if isinstance(p, Project):
        return [n for n, _ in p.outputs]
    if isinstance(p, Filter):
        return _plan_columns(p.input, catalog)
    if isinstance(p, Join):
        if p.how in ("semi", "anti"):
            return _plan_columns(p.left, catalog)
        return (_plan_columns(p.left, catalog)
                + _plan_columns(p.right, catalog))
    if isinstance(p, Aggregate):
        cols = list(p.group_by)
        for a in p.aggs:
            if a.func == "sum" and a.wide:
                cols += [f"{a.out}__hi", f"{a.out}__lo"]
            else:
                cols.append(a.out)
        return cols
    if isinstance(p, (OrderBy, Limit, Shrink)):
        return _plan_columns(p.input, catalog)
    if isinstance(p, Distinct):
        return (list(p.keys) if p.keys
                else _plan_columns(p.input, catalog))
    if isinstance(p, Window):
        return (_plan_columns(p.input, catalog)
                + [s.out for s in p.specs])
    if isinstance(p, VectorTopK):
        return _plan_columns(p.input, catalog)
    if isinstance(p, Apply):
        cols = _plan_columns(p.input, catalog)
        if p.kind == "scalar" and p.scalar is not None:
            # the decorrelated form strips its helper join keys: output
            # is the input plus the one scalar column
            cols = cols + [p.scalar.out]
        return cols
    raise TypeError(type(p))


def _split_conjuncts(e: Expr) -> List[Expr]:
    if isinstance(e, BoolOp) and e.op == "and":
        out: List[Expr] = []
        for part in e.args:
            out.extend(_split_conjuncts(part))
        return out
    return [e]


def _conjoin(parts: Sequence[Expr]) -> Expr:
    return parts[0] if len(parts) == 1 else BoolOp("and", tuple(parts))


def push_filters(p: Plan, catalog: Catalog) -> Plan:
    """Predicate pushdown (norm-rules analog): split conjunctions and sink
    each conjunct as deep as its column references allow — through
    pass-through projections and to the matching side of a join."""
    if isinstance(p, Filter):
        child = push_filters(p.input, catalog)
        remaining: List[Expr] = []
        for conj in _split_conjuncts(p.predicate):
            pushed, child = _try_push(conj, child, catalog)
            if not pushed:
                remaining.append(conj)
        if not remaining:
            return child
        return Filter(child, _conjoin(remaining))
    kids = tuple(push_filters(k, catalog) for k in p.inputs())
    if not kids:
        return p
    if isinstance(p, Project):
        return Project(kids[0], p.outputs)
    if isinstance(p, Join):
        return Join(kids[0], kids[1], p.left_on, p.right_on, p.how,
                    p.residual)
    if isinstance(p, Aggregate):
        return Aggregate(kids[0], p.group_by, p.aggs)
    if isinstance(p, OrderBy):
        return OrderBy(kids[0], p.keys)
    if isinstance(p, Limit):
        return Limit(kids[0], p.n, p.offset)
    if isinstance(p, Distinct):
        return Distinct(kids[0], p.keys)
    if isinstance(p, Window):
        # filters never push THROUGH a window (they'd change frames),
        # but pushdown inside its input subtree is preserved
        return Window(kids[0], p.partition_by, p.order_by, p.specs)
    if isinstance(p, VectorTopK):
        # filters above a top-K must not sink below it (they would
        # change WHICH k rows win); inside the subtree is fine
        return VectorTopK(kids[0], p.column, p.query, p.metric, p.k,
                          p.ann, p.nprobe)
    return p


def _try_push(conj: Expr, node: Plan, catalog: Catalog) -> Tuple[bool, Plan]:
    refs = _expr_columns(conj, set())
    if isinstance(node, Filter):
        ok, pushed = _try_push(conj, node.input, catalog)
        if ok:
            return True, Filter(pushed, node.predicate)
        return False, node
    if isinstance(node, Project):
        # only through pass-through (renaming-free) output columns
        passthrough = {n for n, e in node.outputs
                       if isinstance(e, Col) and e.name == n}
        if refs <= passthrough:
            ok, pushed = _try_push(conj, node.input, catalog)
            if ok:
                return True, Project(pushed, node.outputs)
        return False, node
    if isinstance(node, Join):
        left_cols = set(_plan_columns(node.left, catalog))
        right_cols = set(_plan_columns(node.right, catalog))
        # NULL-extended sides must not receive pushed filters: the left
        # side of right/full joins and the right side of left/full joins
        # produce NULL rows the filter would wrongly suppress pre-join
        if refs <= left_cols and node.how in ("inner", "left", "semi",
                                              "anti"):
            ok, pushed = _try_push(conj, node.left, catalog)
            child = pushed if ok else Filter(node.left, conj)
            return True, Join(child, node.right, node.left_on,
                              node.right_on, node.how, node.residual)
        if refs <= right_cols and node.how in ("inner", "right"):
            ok, pushed = _try_push(conj, node.right, catalog)
            child = pushed if ok else Filter(node.right, conj)
            return True, Join(node.left, child, node.left_on,
                              node.right_on, node.how, node.residual)
        return False, node
    if isinstance(node, Scan):
        # land just above the scan (MapOp fuses it into the scan program)
        return True, Filter(node, conj)
    return False, node


def _ordering_of(p: Plan) -> Tuple[str, ...]:
    """Column ordering the node's output is known to satisfy (prefix).

    Deliberately does NOT pass through Filter: the ordered-aggregate
    kernel requires live rows to form a contiguous prefix (SortOp output
    is compacted; a filter's selection mask punches holes that would split
    runs), so only a DIRECT OrderBy input qualifies."""
    if isinstance(p, OrderBy):
        return tuple(k.col for k in p.keys)
    return ()


_INT_MIN = -(1 << 31)
_INT_MAX = (1 << 31) - 1


def _index_bounds(conjuncts, indexed: Dict[str, object]):
    """-> (column, lo, hi) from the conjuncts' literal constraints on an
    indexed column, or None. The bound is a SUPERSET of the predicate
    (residual filter stays), so combining multiple comparisons is just
    interval intersection."""
    best = None
    for col in indexed:
        lo, hi = _INT_MIN, _INT_MAX
        constrained = False
        for c in conjuncts:
            if not isinstance(c, Cmp):
                continue
            if isinstance(c.left, Col) and c.left.name == col \
                    and isinstance(c.right, Lit) \
                    and isinstance(c.right.value, (int, np.integer)):
                op, v = c.op, int(c.right.value)
            elif isinstance(c.right, Col) and c.right.name == col \
                    and isinstance(c.left, Lit) \
                    and isinstance(c.left.value, (int, np.integer)):
                # literal OP col: mirror the comparison
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                      "=": "=", "==": "="}.get(c.op, c.op)
                v = int(c.left.value)
            else:
                continue
            if op in ("=", "=="):
                lo, hi = max(lo, v), min(hi, v)
            elif op == "<":
                hi = min(hi, v - 1)
            elif op == "<=":
                hi = min(hi, v)
            elif op == ">":
                lo = max(lo, v + 1)
            elif op == ">=":
                lo = max(lo, v)
            else:
                continue
            constrained = True
        if constrained and (best is None or (hi - lo) < (best[2] - best[1])):
            best = (col, lo, hi)
    return best


def use_indexes(p: Plan, catalog: Catalog) -> Plan:
    """Index selection (xform's GenerateConstrainedScans analog, heuristic
    form): a filtered scan whose predicate constrains an indexed column
    with literals becomes IndexScan + residual Filter."""
    if isinstance(p, Filter) and isinstance(p.input, Scan):
        indexed = catalog.table_indexes(p.input.table)
        if indexed:
            found = _index_bounds(_split_conjuncts(p.predicate), indexed)
            if found is not None:
                col, lo, hi = found
                return Filter(IndexScan(p.input.table, col, lo, hi,
                                        p.input.columns), p.predicate)
        return p
    kids = tuple(use_indexes(k, catalog) for k in p.inputs())
    if not kids:
        return p
    return _rebuild(p, kids)


def _rebuild(p: Plan, kids) -> Plan:
    if isinstance(p, Filter):
        return Filter(kids[0], p.predicate)
    if isinstance(p, Project):
        return Project(kids[0], p.outputs)
    if isinstance(p, Join):
        return Join(kids[0], kids[1], p.left_on, p.right_on, p.how,
                    p.residual)
    if isinstance(p, Aggregate):
        return Aggregate(kids[0], p.group_by, p.aggs)
    if isinstance(p, OrderBy):
        return OrderBy(kids[0], p.keys)
    if isinstance(p, Limit):
        return Limit(kids[0], p.n, p.offset)
    if isinstance(p, Distinct):
        return Distinct(kids[0], p.keys)
    if isinstance(p, Window):
        return Window(kids[0], p.partition_by, p.order_by, p.specs)
    if isinstance(p, VectorTopK):
        return VectorTopK(kids[0], p.column, p.query, p.metric, p.k,
                          p.ann, p.nprobe)
    if isinstance(p, Apply):
        return Apply(kids[0], kids[1], p.correlation, p.kind, p.scalar,
                     p.residual)
    return p


def _subtree_stats(p: Plan, catalog: Catalog, cols: set):
    """TableStats of the first scanned table covering `cols` (the
    independence-assumption shortcut: conjuncts reference one table)."""
    for sub in _walk_plan(p):
        if isinstance(sub, (Scan, IndexScan)):
            try:
                schema = catalog.table_schema(sub.table)
            except Exception:
                continue
            if cols <= set(schema.names()):
                return catalog.table_stats(sub.table)
    return None


def _base_rows(p: Plan, catalog: Catalog) -> float:
    """Unfiltered cardinality of the largest scan under `p` (the PK-side
    denominator for FK->PK join fractions)."""
    best = 1.0
    for sub in _walk_plan(p):
        if isinstance(sub, (Scan, IndexScan)):
            st = catalog.table_stats(sub.table)
            best = max(best, float(st.row_count) if st is not None
                       else float(catalog.table_rows(sub.table)))
    return best


def keep_share(est_rows: float, base_rows: float) -> float:
    """The share of a probe's rows that a join to a unique build keeps, by
    estimate (FK->PK: each probe row matches at most one build row, and
    the build's filters have left `est_rows` of its `base_rows`). The ONE
    definition: `estimate_cardinality` sizes a join's output, and through
    it a Shrink, by it; the binder's join orderer (sql/bind.py) ranks the
    relations it may attach by it, so the relation that goes first is the
    one `insert_shrinks` compacts above."""
    return min(est_rows / max(base_rows, 1.0), 1.0)


def join_keeps(p: Join, catalog: Catalog) -> Optional[float]:
    """The estimated share of its probe's rows that join `p` keeps (what
    EXPLAIN prints beside it), or None for a join that keeps both sides
    whole."""
    frac = keep_share(estimate_cardinality(p.right, catalog),
                      _base_rows(p.right, catalog))
    if p.residual is not None:
        # a key's rows mostly differ somewhere (<>); an ordering holds of
        # half the pairs. No statistics reach a correlated comparison.
        frac *= 0.5 if isinstance(p.residual, Cmp) else 0.9
    if p.how in ("inner", "semi"):
        return frac
    if p.how == "anti":
        return 1.0 - frac
    if p.how == "left":
        return 1.0
    return None


def estimate_cardinality(p: Plan, catalog: Catalog) -> float:
    """Stats-based output-row estimate (the coster's cardinality model:
    histogram/selectivity per conjunct, FK->PK fraction per join —
    pkg/sql/opt/memo/statistics_builder.go in miniature)."""
    from cockroach_tpu.sql.stats import conjunct_selectivity

    if isinstance(p, (Scan, IndexScan)):
        st = catalog.table_stats(p.table)
        return (float(st.row_count) if st is not None
                else float(catalog.table_rows(p.table)))
    if isinstance(p, Filter):
        base = estimate_cardinality(p.input, catalog)
        sel = 1.0
        for c in _split_conjuncts(p.predicate):
            st = _subtree_stats(p.input, catalog,
                                _expr_columns(c, set()))
            sel *= conjunct_selectivity(c, st)
        return max(base * sel, 1.0)
    if isinstance(p, Join):
        le = estimate_cardinality(p.left, catalog)
        keeps = join_keeps(p, catalog)
        if keeps is None:
            return max(le + estimate_cardinality(p.right, catalog), 1.0)
        return max(le * keeps, 1.0)
    if isinstance(p, Aggregate):
        ce = estimate_cardinality(p.input, catalog)
        return max(ce / 2.0, 1.0) if p.group_by else 1.0
    if isinstance(p, Limit):
        return float(min(estimate_cardinality(p.input, catalog), p.n))
    if isinstance(p, VectorTopK):
        return float(min(estimate_cardinality(p.input, catalog), p.k))
    if isinstance(p, Distinct):
        return max(estimate_cardinality(p.input, catalog) / 2.0, 1.0)
    if p.inputs():
        return estimate_cardinality(p.inputs()[0], catalog)
    return 1.0


def insert_shrinks(p: Plan, catalog: Optional[Catalog] = None) -> Plan:
    """Capacity compaction placement: (1) above every HAVING-shaped
    filter (group counts << input capacity, a selective HAVING leaves a
    sliver); (2) above inner/semi joins whose BUILD side is already
    shrunk — matching a multi-M-lane probe against a tiny build leaves
    ~build-count x fanout live rows, so downstream aggregations and
    sorts should not pay full-capacity lanes; (3) round 5, STATS-driven:
    above any selective join whose estimated output is a small fraction
    of its probe input (Q9: the 5% green-parts semi join collapses the
    remaining 4 joins + aggregation from 8M lanes to a 524,288-lane
    compaction: all four, because the binder's join orderer ranks by the
    same `keep_share` and so attaches that semi join first).
    Smallness propagates through row-preserving nodes; the deferred
    overflow flag + 16x capacity growth keep the optimism safe (a stale
    estimate costs one recompile, never a wrong answer)."""
    node, _small = _shrink_rec(p, catalog)
    return node


def _pow2_at_least(n: int) -> int:
    c = 1
    while c < n:
        c *= 2
    return c


def _shrink_rec(p: Plan, catalog: Optional[Catalog]):
    if isinstance(p, Filter) and isinstance(p.input, Aggregate):
        inner, _ = _shrink_rec(p.input, catalog)
        return Shrink(Filter(inner, p.predicate)), True
    if not p.inputs():
        return p, False
    pairs = [_shrink_rec(k, catalog) for k in p.inputs()]
    kids = tuple(n for n, _ in pairs)
    smalls = [sm for _, sm in pairs]
    out = _rebuild(p, kids)
    if isinstance(p, Shrink):
        return out, True
    if isinstance(p, Join):
        if p.how in ("inner", "semi") and smalls[1] and not smalls[0]:
            return Shrink(out, start_capacity=1 << 14), True
        # stats-driven: a selective join's output should not ride its
        # probe's multi-M lane capacity into the rest of the query.
        if (catalog is not None and p.how in ("inner", "semi", "anti")
                and not smalls[0]):
            est = estimate_cardinality(out, catalog)
            probe_est = estimate_cardinality(p.left, catalog)
            if est * 3.0 <= probe_est and est >= 1.0:
                cap = max(_pow2_at_least(int(est * 1.5) + 1), 1 << 12)
                return Shrink(out, start_capacity=cap), True
        return out, (smalls[0] and p.how in ("inner", "left", "semi",
                                             "anti"))
    if isinstance(p, (Filter, Project, Limit, OrderBy, Distinct,
                      Aggregate, Shrink, VectorTopK)):
        # row-preserving (or row-reducing) single-child nodes keep
        # their child's smallness
        return out, smalls[0]
    return out, False


# comparison -> the extremes of the inner column that decide whether SOME
# inner value satisfies it against an outer value
_RESIDUAL_EXTREMES = {"!=": ("min", "max"), "<": ("min",), "<=": ("min",),
                      ">": ("max",), ">=": ("max",)}


def _column_distinct(p: Plan, name: str, catalog: Catalog) -> Optional[int]:
    """The distinct values of output column `name` of `p`, at most, by the
    statistics of the table it is scanned from, or None: followed through
    what keeps a column's values a subset of the table's (as
    _column_range) and through projections that rename it."""
    if isinstance(p, (Scan, IndexScan)):
        st = catalog.table_stats(p.table)
        cs = st.columns.get(name) if st is not None else None
        if name not in (p.columns or catalog.table_schema(p.table).names()):
            return None
        return int(cs.distinct) if cs is not None else None
    if isinstance(p, Project):
        e = dict(p.outputs).get(name)
        return (_column_distinct(p.input, e.name, catalog)
                if isinstance(e, Col) else None)
    if isinstance(p, (Filter, Shrink, OrderBy, Limit, Distinct)):
        return _column_distinct(p.input, name, catalog)
    if isinstance(p, Aggregate):
        return (_column_distinct(p.input, name, catalog)
                if name in p.group_by else None)
    if isinstance(p, Join):
        sides = (p.left,) if p.how in ("semi", "anti") else (p.left, p.right)
        for side in sides:
            if name in _plan_columns(side, catalog):
                return _column_distinct(side, name, catalog)
    return None


def _residual_join(outer: Plan, sub: Plan, p: "Apply", n: int,
                   catalog: Catalog) -> Join:
    """Apply `p` (exists / not_exists with a residual, the `n`th of its
    plan) over its decorrelated inputs -> the semi / anti join against
    the per-key extremes (see Apply's docstring). The build's columns are
    renamed `__apply<n>_*`: hand-built plans scan the same table on both
    sides under the same names. Where the statistics count the keys'
    distinct values, and they are fewer than the rows the subquery scans,
    the build is SHRUNK to them (a fact table's aggregate comes out at the
    table's lanes, one live lane a group: lineitem's 8.4M lanes for 1.5M
    orders, and the join's sorts would run at those lanes); a count that
    is too low takes the Shrink's restart."""
    r = p.residual
    if not (isinstance(r, Cmp) and r.op in _RESIDUAL_EXTREMES
            and isinstance(r.left, Col) and isinstance(r.right, Col)
            and p.correlation):
        raise TypeError(
            "an Apply's residual is ONE comparison (!=, <, <=, >, >=) of "
            "an inner column with an outer one, beside an equality "
            f"correlation: {r!r}")
    inner_on = tuple(b for _, b in p.correlation)
    ext = {f: f"__apply{n}_{f}" for f in _RESIDUAL_EXTREMES[r.op]}
    agg = Aggregate(sub, inner_on, tuple(AggSpec(f, r.left.name, out)
                                         for f, out in ext.items()))
    keys = tuple(f"__apply{n}_k{i}" for i in range(len(inner_on)))
    build = Project(agg, tuple(zip(keys, map(Col, inner_on)))
                    + tuple((out, Col(out)) for out in ext.values()))
    distinct = [_column_distinct(sub, k, catalog) for k in inner_on]
    if None not in distinct:
        import math

        cap = max(_pow2_at_least(int(math.prod(distinct) * 1.25) + 1),
                  1 << 12)
        # against the subquery's LANES, which its filters do not cut
        if cap < _base_rows(sub, catalog):
            build = Shrink(build, start_capacity=cap)
    parts = [Cmp(r.op, Col(out), r.right) for out in ext.values()]
    return Join(outer, build, tuple(a for a, _ in p.correlation), keys,
                "semi" if p.kind == "exists" else "anti",
                parts[0] if len(parts) == 1 else BoolOp("or", tuple(parts)))


def decorrelate(p: Plan, catalog: Catalog) -> Plan:
    """Rewrite every Apply (correlated subquery) into join+aggregate form
    (see Apply's docstring). Runs FIRST in normalize(): the later passes
    (pushdown, index selection, shrink placement) and the builder only
    ever see ordinary relational nodes — compiled and host walks execute
    the same decorrelated plan, so the rewrite can never diverge the two
    paths. A plan that held an Apply counts stage `sql.decorrelate` (one
    event, `rows` = the Applies rewritten) and
    `sql_apply_decorrelated_total`."""
    rewritten: List[Apply] = []
    out = _decorrelate(p, catalog, rewritten)
    if rewritten:
        from cockroach_tpu.exec import stats
        from cockroach_tpu.util.metric import default_registry

        stats.add("sql.decorrelate", rows=len(rewritten))
        default_registry().counter(
            "sql_apply_decorrelated_total",
            "correlated subqueries (Apply nodes) rewritten into joins "
            "and aggregates").inc(len(rewritten))
    return out


def _decorrelate(p: Plan, catalog: Catalog, rewritten: List["Apply"]) -> Plan:
    kids = tuple(_decorrelate(k, catalog, rewritten) for k in p.inputs())
    if not isinstance(p, Apply):
        return _rebuild(p, kids) if kids else p
    outer, sub = kids
    rewritten.append(p)
    outer_on = tuple(a for a, _ in p.correlation)
    inner_on = tuple(b for _, b in p.correlation)
    if p.kind in ("exists", "not_exists"):
        if p.residual is not None:
            return _residual_join(outer, sub, p, len(rewritten) - 1,
                                  catalog)
        how = "semi" if p.kind == "exists" else "anti"
        return Join(outer, sub, outer_on, inner_on, how)
    if p.kind != "scalar" or p.scalar is None:
        raise TypeError(f"Apply kind {p.kind!r} needs a scalar AggSpec")
    from cockroach_tpu.coldata.batch import INT as _INT

    out_cols = _plan_columns(outer, catalog)
    if not p.correlation:
        # uncorrelated scalar subquery: broadcast the single aggregate
        # row to every input row through a constant join key
        outer = Project(outer, tuple((n, Col(n)) for n in out_cols)
                        + (("__apply_c0", Lit(0, _INT)),))
        outer_on = ("__apply_c0",)
        inner_on = ("__apply_c0_",)
        agg = Aggregate(sub, (), (p.scalar,))
        inner = Project(agg, (("__apply_c0_", Lit(0, _INT)),
                              (p.scalar.out, Col(p.scalar.out))))
    else:
        # one aggregate row per distinct correlation key; the keys are
        # renamed so the join never collides with same-named outer
        # columns (Q17: l_partkey exists on both sides)
        agg = Aggregate(sub, inner_on, (p.scalar,))
        renames = tuple((f"__apply_k{i}", Col(c))
                        for i, c in enumerate(inner_on))
        inner = Project(agg, renames
                        + ((p.scalar.out, Col(p.scalar.out)),))
        inner_on = tuple(f"__apply_k{i}" for i in range(len(inner_on)))
    joined = Join(outer, inner, outer_on, inner_on, "left")
    # strip the helper keys: Apply's contract is input cols + the scalar
    # (NULL where the group was empty, via the left join's validity)
    return Project(joined, tuple((n, Col(n)) for n in out_cols)
                   + ((p.scalar.out, Col(p.scalar.out)),))


def normalize(p: Plan, catalog: Catalog) -> Plan:
    return insert_shrinks(use_indexes(push_filters(
        decorrelate(p, catalog), catalog), catalog), catalog)


# ------------------------------------------------------------------ build --

def build(p: Plan, catalog: Catalog, capacity: int = 1 << 17,
          _normalized: bool = False, node_map=None) -> Operator:
    """Logical plan -> exec/ operator tree (the NewColOperator seam).

    `node_map` (a dict) receives id(plan node) -> wired operator (the
    object a parent actually references, CheckedOp-wrapped in test
    builds) — the placement pass (sql/plan_compile.py) uses it to pair
    plan nodes with their operators for tier assignment."""
    if not _normalized:
        p = normalize(p, catalog)

    from cockroach_tpu.exec.invariants import CheckedOp, enabled as _inv

    checking = _inv()
    # common-subplan elimination: VALUE-equal plan nodes build ONE
    # operator (plan nodes are frozen dataclasses; Q18 scans lineitem
    # twice with identical Scan nodes — deduping halves its resident
    # image and, with the fused tracer's _mat memo, its scan concats).
    # Nodes whose predicates hash by identity (Expr eq=False) simply
    # never hit the memo.
    memo: Dict[Plan, Operator] = {}

    def rec(node: Plan) -> Operator:
        try:
            hit = memo.get(node)
        except TypeError:
            hit = None
        if hit is not None:
            if node_map is not None:
                node_map[id(node)] = hit
            return hit
        op = _rec(node)
        # test builds insert an invariants checker above every operator
        # (colexec/invariants_checker.go)
        if checking:
            op = CheckedOp(op)
        try:
            memo[node] = op
        except TypeError:
            pass
        if node_map is not None:
            node_map[id(node)] = op
        return op

    def residual_join(node: Join) -> Operator:
        """A semi / anti join with a residual over a unique build: the
        inner (left) join that brings the build's columns to the probe's
        lanes, the residual as a filter (for the anti join: whatever it
        does NOT make TRUE, an unmatched lane's NULLs among that), and
        the probe's columns alone again. Every lowering of a join and of
        a filter serves it as it is, on one chip and on a mesh; the
        JoinOp keeps `residual_of` for EXPLAIN and the lanes' count."""
        if node.how not in ("semi", "anti"):
            raise TypeError(f"a residual on a {node.how} join")
        from cockroach_tpu.ops.expr import Not, ScalarFunc

        join = JoinOp(rec(node.left), rec(node.right),
                      list(node.left_on), list(node.right_on),
                      how="inner" if node.how == "semi" else "left")
        join.residual_of = node.how
        keep = (node.residual if node.how == "semi"
                else Not(ScalarFunc("coalesce",
                                    (node.residual, Lit(False)))))
        cols = _plan_columns(node.left, catalog)
        return MapOp(join, [("filter", keep),
                            ("project", [(n, Col(n)) for n in cols])])

    def _rec(node: Plan) -> Operator:
        if isinstance(node, Scan):
            schema = catalog.table_schema(node.table)
            cols = list(node.columns) if node.columns else None
            if cols:
                schema = schema.project(cols)
            chunks = catalog.table_chunks(node.table, capacity, cols)
            op = ScanOp(schema, chunks, capacity,
                        cache_key=catalog.scan_cache_key(
                            node.table, cols, capacity),
                        table=node.table)
            # stats stamp for TPU-vs-host engine routing (sql/cost.py)
            op.est_rows = catalog.table_rows(node.table)
            src = catalog.scan_source(node.table, cols)
            if src is not None:
                # distributed ingest shards the resident MVCC image per
                # pk range when the scan's store is reachable
                op._mvcc_src = src
            return op
        if isinstance(node, IndexScan):
            schema = catalog.table_schema(node.table)
            cols = list(node.columns) if node.columns else None
            if cols:
                schema = schema.project(cols)
            chunks = catalog.index_chunks(node.table, node.column,
                                          node.lo, node.hi, capacity,
                                          cols)
            op = ScanOp(schema, chunks, capacity, table=node.table)
            op.est_rows = max(catalog.table_rows(node.table) // 4, 1)
            return op
        if isinstance(node, Filter):
            return MapOp(rec(node.input), [("filter", node.predicate)])
        if isinstance(node, Shrink):
            return ShrinkOp(rec(node.input),
                            capacity=node.start_capacity)
        if isinstance(node, Project):
            # exact-semantics seam (§2.3): decimal division degrades to
            # float32 on the device path; with exact arithmetic on, such
            # projections run through the row-at-a-time datum engine
            from cockroach_tpu.exec.rowexec import (
                EXACT_ARITHMETIC, RowMapOp, has_decimal_division,
                has_string_compute,
            )

            from cockroach_tpu.util.settings import Settings

            child_op = rec(node.input)
            # computed strings ALWAYS take the row engine (dictionary
            # minting is host-side by nature); exact decimal division
            # does so under the setting
            def _computes_string(e):
                if has_string_compute(e):
                    return True
                from cockroach_tpu.coldata.batch import Kind as _K
                from cockroach_tpu.ops.expr import Col as _Col

                if isinstance(e, _Col):
                    return False
                try:  # e.g. CASE with string branches
                    return e.type(child_op.schema).kind is _K.STRING
                except Exception:
                    return False

            if any(_computes_string(e) for _, e in node.outputs) or (
                    Settings().get(EXACT_ARITHMETIC) and any(
                        has_decimal_division(e, child_op.schema)
                        for _, e in node.outputs)):
                return RowMapOp(child_op, list(node.outputs))
            return MapOp(child_op, [("project", list(node.outputs))])
        if isinstance(node, Join):
            if node.residual is not None:
                return residual_join(node)
            return JoinOp(rec(node.left), rec(node.right),
                          list(node.left_on), list(node.right_on),
                          how=node.how)
        if isinstance(node, Aggregate):
            child = rec(node.input)
            ordering = _ordering_of(node.input)
            agg_cls = (OrderedAggOp
                       if node.group_by
                       and tuple(node.group_by)
                       == ordering[:len(node.group_by)]
                       else HashAggOp)
            if agg_cls is HashAggOp:
                return HashAggOp(child, list(node.group_by),
                                 list(node.aggs),
                                 key_domains=key_domains(
                                     node, catalog, child.schema))
            return agg_cls(child, list(node.group_by), list(node.aggs))
        if isinstance(node, OrderBy):
            return SortOp(rec(node.input), list(node.keys))
        if isinstance(node, Limit):
            # OrderBy + Limit (no offset) -> top-K (sorttopk.go analog)
            if isinstance(node.input, OrderBy) and node.offset == 0:
                return TopKOp(rec(node.input.input),
                              list(node.input.keys), node.n)
            return LimitOp(rec(node.input), node.n, node.offset)
        if isinstance(node, Distinct):
            return DistinctOp(rec(node.input),
                              list(node.keys) if node.keys else None)
        if isinstance(node, Window):
            from cockroach_tpu.exec.operators import WindowOp

            return WindowOp(rec(node.input), list(node.partition_by),
                            list(node.order_by), list(node.specs))
        if isinstance(node, VectorTopK):
            from cockroach_tpu.ops.expr import VecDistance, VecLit

            if node.ann and isinstance(node.input, Scan):
                from cockroach_tpu.exec.operators import VectorANNOp

                return VectorANNOp(rec(node.input), node.column,
                                   node.query, node.metric, node.k,
                                   node.nprobe)
            # exact path: distance projection -> sort-and-slice top-K
            # -> strip the helper column. Composed entirely from MapOp /
            # TopKOp so the fused tracer and prepared/exec caches treat
            # a vector query like any other fused scan program.
            child = rec(node.input)
            cols = _plan_columns(node.input, catalog)
            dist = VecDistance(node.metric, Col(node.column),
                               VecLit(node.query))
            proj = [(n, Col(n)) for n in cols] + [("__vdist", dist)]
            inner = MapOp(child, [("project", proj)])
            # NULL embeddings rank LAST (a NULL distance must not beat a
            # real neighbor), overriding the engine's ASC-nulls-first
            topk = TopKOp(inner,
                          [SortKey("__vdist", nulls_first=False)],
                          node.k)
            return MapOp(topk, [("project",
                                 [(n, Col(n)) for n in cols])])
        raise TypeError(f"unknown plan node {type(node).__name__}")

    return rec(p)


def _year_of(days: int) -> int:
    """The civil year of a DATE (days since the unix epoch): what
    ops/expr evaluates Extract('year', ...) to, on the host."""
    return int(np.datetime64(int(days), "D").astype("datetime64[Y]")
               .astype(np.int64)) + 1970


def _column_range(p: Plan, name: str, catalog: Catalog):
    """-> (lo, hi, Kind) that every non-NULL value of output column `name`
    of `p` lies in, by the statistics of the table it is scanned from, or
    None. Followed only through what keeps a column's values a SUBSET of
    the table's: filters, shrinks, sorts, limits, DISTINCT, joins (an
    outer join adds NULLs only), an inner GROUP BY's keys, and projections
    that rename it. The one computed form is Extract('year', <such a DATE>),
    monotone in the day: year(lo)..year(hi)."""
    from cockroach_tpu.coldata.batch import Kind
    from cockroach_tpu.ops.expr import Extract

    if isinstance(p, (Scan, IndexScan)):
        schema = catalog.table_schema(p.table)
        if name not in (p.columns or schema.names()):
            return None
        st = catalog.table_stats(p.table)
        cs = st.columns.get(name) if st is not None else None
        kind = schema.field(name).type.kind
        if (cs is None or cs.lo is None or cs.hi is None
                or not st.exact_bounds
                or kind not in (Kind.INT, Kind.DATE)):
            return None
        return int(cs.lo), int(cs.hi), kind
    if isinstance(p, Project):
        e = dict(p.outputs).get(name)
        if isinstance(e, Col):
            return _column_range(p.input, e.name, catalog)
        if (isinstance(e, Extract) and e.part == "year"
                and isinstance(e.arg, Col)):
            r = _column_range(p.input, e.arg.name, catalog)
            if r is not None and r[2] is Kind.DATE:
                return _year_of(r[0]), _year_of(r[1]), Kind.INT
        return None
    if isinstance(p, (Filter, Shrink, OrderBy, Limit, Distinct)):
        return _column_range(p.input, name, catalog)
    if isinstance(p, Aggregate):
        return (_column_range(p.input, name, catalog)
                if name in p.group_by else None)
    if isinstance(p, Join):
        sides = (p.left,) if p.how in ("semi", "anti") else (p.left, p.right)
        for side in sides:
            if name in _plan_columns(side, catalog):
                return _column_range(side, name, catalog)
    return None


def key_domains(node: "Aggregate", catalog: Catalog, schema: Schema):
    """Stats-derived {key: (lo, hi)} of `node`'s integer and date GROUP BY
    keys (HashAggOp's `key_domains`), or None: given only where, WITH
    them, every key has a small static domain (ops/agg.dense_key_sizes
    over `schema`, the aggregate's input's: dictionaries and bools count
    as they are, the product is held to its DENSE_MAX_GROUPS), so that
    the aggregate lowers by slot: no hash, no sort, D lanes out. A key
    whose range cannot be proved (_column_range) gives none, and the
    aggregate lowers as without statistics. Statistics go stale: the
    lowering flags a live key outside its range and the flow restarts
    without (HashAggOp.widen)."""
    from cockroach_tpu.ops.agg import dense_key_sizes

    doms = {}
    for key in node.group_by:
        r = _column_range(node.input, key, catalog)
        if r is not None:
            doms[key] = r[:2]
    if not doms or dense_key_sizes(schema, node.group_by, doms) is None:
        return None
    return doms


def _walk_plan(p: Plan):
    yield p
    for k in p.inputs():
        yield from _walk_plan(k)


def run(p: Plan, catalog: Catalog, capacity: int = 1 << 17, mesh=None,
        axis: str = "x", with_schema: bool = False, op_sink=None,
        sql: Optional[str] = None, setting: str = "auto",
        strict: bool = False):
    """Execute a logical plan; `mesh` switches to distributed execution
    (the DistSQL on/off decision: a session's `distsql` variable and its
    catalog's mesh, or a caller's own), and `strict` makes a plan the
    distributed runner declines an error instead of a single-chip run
    (`distsql = always`). `with_schema=True` also returns the
    operator tree's output Schema (result decoding needs the exact
    output types, and the tree was built anyway). `op_sink` (a list)
    receives the built operator tree — Session's prepared-statement
    cache re-collects it on warm re-execution. `sql` keys the placement
    pass's per-fingerprint cache (measured-cost tier routing); `setting`
    is the session's `vectorize` (auto lets the coster route)."""
    from cockroach_tpu.exec import collect, stats
    from cockroach_tpu.sql.plan_compile import compile_plan

    with stats.timed("sql.plan"):
        compiled = compile_plan(p, catalog, capacity, sql=sql,
                                setting=setting)
    op = compiled.op
    if op_sink is not None:
        op_sink.append(op)
    if mesh is None:
        result = collect(op, backend=compiled.backend)
    else:
        from cockroach_tpu.parallel.dist_flow import collect_distributed

        result = collect_distributed(op, mesh, axis,
                                     placement=compiled.placement,
                                     strict=strict)
    return (result, op.schema) if with_schema else result
