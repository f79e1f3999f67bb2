"""Table statistics: sampled per-column distinct counts, bounds and
equi-depth histograms, persisted in a system keyspace; selectivity
estimation for the binder's cost-ranked join ordering.

Reference: pkg/sql/stats (sampler-based histograms, histogram.go;
automatic stats jobs, automatic_stats.go; the stats cache) feeding
opt/memo logical props and xform/coster.go costing. Here ANALYZE <table>
samples through the catalog's chunk stream, and the binder multiplies
row counts by per-conjunct selectivities instead of a flat filter
discount — the SURVEY Appendix A costing hook (coster.go:70,526).
"""

from __future__ import annotations

import json
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from cockroach_tpu.ops.expr import (
    BoolOp, Cmp, Col, InList, Like, Lit, Param,
)
from cockroach_tpu.util.hlc import Timestamp

STATS_TABLE = 0xFFE1  # system.table_statistics keyspace
HIST_BUCKETS = 16
SAMPLE_ROWS = 1 << 16


@dataclass
class ColumnStats:
    distinct: int
    null_frac: float
    lo: Optional[int] = None          # int-typed columns only
    hi: Optional[int] = None
    histogram: List[int] = field(default_factory=list)  # bucket uppers


@dataclass
class TableStats:
    row_count: int
    columns: Dict[str, ColumnStats] = field(default_factory=dict)
    # lo/hi cover EVERY row the statistics were taken over (sample_stats'
    # contract); a catalog that samples a prefix of the table says False,
    # and nothing then reads a bound as a domain (sql/plan.key_domains)
    exact_bounds: bool = True

    def encode(self) -> bytes:
        return json.dumps({
            "row_count": self.row_count,
            "columns": {n: vars(c) for n, c in self.columns.items()},
        }, sort_keys=True).encode()

    @staticmethod
    def decode(b: bytes) -> "TableStats":
        d = json.loads(b.decode())
        return TableStats(d["row_count"], {
            n: ColumnStats(**c) for n, c in d["columns"].items()})


def stats_key(table_id: int) -> bytes:
    return struct.pack(">HQ", STATS_TABLE, table_id)


def sample_stats(chunks, schema, sample_rows: int = SAMPLE_ROWS
                 ) -> TableStats:
    """Build TableStats from a chunk stream. Histograms and distinct
    counts come from a strided per-chunk SAMPLE (the reference samples
    via a DistSQL sampler processor), but integer BOUNDS are exact over
    every row — lo/hi feed planner decisions (direct-address aggregation
    ranges, index spans) where a prefix-biased bound would be wrong, not
    just imprecise — and so is the count of runs that caps an integer
    column's distinct count (it sizes the Shrink of a decorrelated
    subquery's build, sql/plan._residual_join)."""
    cols: Dict[str, List[np.ndarray]] = {}
    bounds: Dict[str, Tuple[int, int]] = {}
    runs: Dict[str, int] = {}   # runs of one value, over every row
    sampled = 0
    total = 0
    for c in chunks:
        n = len(next(iter(c.values())))
        total += n
        take = min(n, max(sample_rows // 16,
                          sample_rows - sampled)) if sampled \
            < sample_rows else 0
        for name, arr in c.items():
            a = np.asarray(arr)
            if np.issubdtype(a.dtype, np.integer) and len(a):
                lo, hi = int(a.min()), int(a.max())
                if name in bounds:
                    plo, phi = bounds[name]
                    bounds[name] = (min(plo, lo), max(phi, hi))
                else:
                    bounds[name] = (lo, hi)
                runs[name] = (runs.get(name, 0) + 1
                              + int(np.count_nonzero(a[1:] != a[:-1])))
            if take:
                stride = max(1, n // take)
                cols.setdefault(name, []).append(a[::stride][:take])
        if take:
            sampled += min(take, n)
    out = TableStats(total)
    scale = total / max(sampled, 1)
    for name, parts in cols.items():
        arr = np.concatenate(parts)
        distinct_sample = len(np.unique(arr))
        # scale distinct estimates for columns that look key-like in the
        # sample (every sampled value unique -> assume it grows with the
        # table); saturated small domains stay as measured
        if distinct_sample >= 0.95 * len(arr):
            distinct = int(distinct_sample * scale)
        else:
            distinct = distinct_sample
        # a value's rows form at least one run, so the runs counted over
        # every row bound the distinct values from above: a CLUSTERED
        # foreign key (lineitem's order key: 4 rows an order, side by
        # side) looks unique to a strided sample and was scaled to the
        # row count
        distinct = min(distinct, runs.get(name, distinct))
        cs = ColumnStats(max(distinct, 1), 0.0)
        if name in bounds:
            cs.lo, cs.hi = bounds[name]
            if len(arr):
                qs = np.quantile(
                    arr, np.linspace(0, 1, HIST_BUCKETS + 1)[1:])
                cs.histogram = [int(q) for q in qs]
        out.columns[name] = cs
    return out


def save_stats(store, table_id: int, st: TableStats) -> None:
    store.engine.put(stats_key(table_id), store.clock.now(), st.encode())


def load_stats(store, table_id: int) -> Optional[TableStats]:
    hit = store.engine.get(stats_key(table_id), Timestamp.MAX)
    if hit is None or not hit[0]:
        return None
    return TableStats.decode(hit[0])


# ------------------------------------------------------------ selectivity --

_DEFAULT_SEL = 0.2    # the pre-stats flat discount, kept as the fallback
_MIN_SEL = 1e-4


def _range_frac(cs: ColumnStats, lo: float, hi: float) -> float:
    """Fraction of rows in [lo, hi] from the equi-depth histogram."""
    if cs.lo is None or cs.hi is None or cs.hi < cs.lo:
        return _DEFAULT_SEL
    if hi < cs.lo or lo > cs.hi:
        return 0.0
    if not cs.histogram:
        span = max(cs.hi - cs.lo, 1)
        return max(0.0, min(1.0, (min(hi, cs.hi) - max(lo, cs.lo) + 1)
                            / span))
    uppers = cs.histogram
    prev = cs.lo
    frac = 0.0
    per_bucket = 1.0 / len(uppers)
    for up in uppers:
        blo, bhi = prev, up
        if bhi >= lo and blo <= hi and bhi >= blo:
            width = max(bhi - blo, 1)
            overlap = min(hi, bhi) - max(lo, blo) + 1
            frac += per_bucket * max(0.0, min(1.0, overlap / width))
        prev = up
    return max(0.0, min(1.0, frac))


def _constant(e):
    """A literal's value; for a bound parameter the value at the binding
    the plan is made at (the plan then serves every binding: an estimate
    that a later one overflows costs one flow restart)."""
    return e.sample if isinstance(e, Param) else e.value


def conjunct_selectivity(e, stats: Optional[TableStats]) -> float:
    """Estimated fraction of rows satisfying one bound conjunct."""
    if isinstance(e, BoolOp):
        if e.op == "and":
            out = 1.0
            for part in e.args:
                out *= conjunct_selectivity(part, stats)
            return out
        if e.op == "or":
            out = 0.0
            for part in e.args:
                out = out + conjunct_selectivity(part, stats) * (1 - out)
            return out
    if stats is None:
        return _DEFAULT_SEL
    if isinstance(e, Cmp):
        col, lit = None, None
        if isinstance(e.left, Col) and isinstance(e.right, (Lit, Param)):
            col, lit, op = e.left.name, _constant(e.right), e.op
        elif isinstance(e.right, Col) and isinstance(e.left, (Lit, Param)):
            col, lit = e.right.name, _constant(e.left)
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(
                e.op, e.op)
        else:
            return _DEFAULT_SEL
        cs = stats.columns.get(col)
        if cs is None or not isinstance(lit, (int, float, np.integer)):
            return _DEFAULT_SEL
        v = float(lit)
        if op in ("=", "=="):
            return max(1.0 / cs.distinct, _MIN_SEL)
        if op in ("!=", "<>"):
            return 1.0 - max(1.0 / cs.distinct, _MIN_SEL)
        if op == "<":
            return _range_frac(cs, -float("inf"), v - 1)
        if op == "<=":
            return _range_frac(cs, -float("inf"), v)
        if op == ">":
            return _range_frac(cs, v + 1, float("inf"))
        if op == ">=":
            return _range_frac(cs, v, float("inf"))
        return _DEFAULT_SEL
    if isinstance(e, InList):
        cs = (stats.columns.get(e.arg.name)
              if isinstance(e.arg, Col) else None)
        if cs is None:
            return _DEFAULT_SEL
        return min(1.0, len(e.values) / cs.distinct)
    if isinstance(e, Like):
        sample = getattr(e.pattern, "sample", None)
        if sample:
            # a bound pattern: the share of the column's dictionary that
            # the binding the plan is made at matches
            hits, size = sample
            sel = max(hits / max(size, 1), _MIN_SEL)
            return 1.0 - sel if e.negate else sel
        return 0.1
    return _DEFAULT_SEL


def estimate_rows(stats: Optional[TableStats], base_rows: int,
                  filters) -> float:
    """Cost-model cardinality: base rows x product of conjunct
    selectivities (independence assumption, as the reference's coster
    without multi-column stats)."""
    est = float(stats.row_count if stats is not None else base_rows)
    for e in filters:
        est *= conjunct_selectivity(e, stats)
    return max(est, 1.0)
