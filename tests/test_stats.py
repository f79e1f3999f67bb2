"""Table statistics + cost-ranked join ordering (optimizer-lite).

Reference: pkg/sql/stats/histogram.go (sampled histograms),
opt/xform/coster.go:70,526 (stats-driven costing). The acceptance test
from VERDICT r3 #7: stats FLIP a join order decision, visible in the
plan."""

import numpy as np
import pytest

from cockroach_tpu.coldata.batch import Field, Schema
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import Binder
from cockroach_tpu.sql.plan import (
    Catalog, Filter, IndexScan, Join, Scan, Shrink, normalize,
)
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.sql.stats import (
    ColumnStats, TableStats, conjunct_selectivity, sample_stats,
)
from cockroach_tpu.ops.expr import Cmp, Col, Lit
from cockroach_tpu.coldata.batch import INT
from cockroach_tpu.storage.engine import PyEngine
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.hlc import HLC, ManualClock


@pytest.fixture
def sess():
    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    return Session(SessionCatalog(store), capacity=1024)


def test_sample_stats_histogram_and_distinct():
    rng = np.random.default_rng(0)
    chunks = [{"a": rng.integers(0, 100, 500).astype(np.int64),
               "b": np.arange(i * 500, (i + 1) * 500, dtype=np.int64)}
              for i in range(4)]
    st = sample_stats(iter(chunks), None)
    assert st.row_count == 2000
    assert 80 <= st.columns["a"].distinct <= 100
    assert st.columns["b"].distinct >= 1900  # key-like: scaled estimate
    assert st.columns["a"].lo == 0 and st.columns["a"].hi <= 99
    assert len(st.columns["a"].histogram) == 16


def test_a_clustered_keys_distinct_count_is_capped_by_its_runs():
    """A foreign key stored side by side (lineitem's order key: 1 to 7
    rows an order) looks unique to the strided sample, whose count was
    scaled to the rows; the runs of one value, counted over EVERY row,
    bound the distinct values from above (PR 50: the bound sizes the
    Shrink of a decorrelated subquery's build)."""
    rng = np.random.default_rng(5)
    orders = np.arange(1, 200_001, dtype=np.int64) * 4     # sparse keys
    key = np.repeat(orders, rng.integers(1, 8, len(orders)))
    # one chunk a table, as the bulk loaders hand it over: the sample is
    # every twelfth row, so no order key comes twice
    chunk = {"k": key, "u": np.arange(len(key), dtype=np.int64) % 97}
    st = sample_stats([chunk], Schema([Field("k", INT), Field("u", INT)]))
    assert st.row_count == len(key) > 4 * 150_000
    assert st.columns["k"].distinct == len(orders)   # exact; was the rows
    # a column without clustering keeps its sampled count
    assert st.columns["u"].distinct == 97


def test_selectivity_eq_and_range():
    cs = ColumnStats(distinct=100, null_frac=0.0, lo=0, hi=999,
                     histogram=list(range(62, 1000, 62))[:16])
    st = TableStats(10000, {"a": cs})
    eq = conjunct_selectivity(Cmp("==", Col("a"), Lit(5, INT)), st)
    assert abs(eq - 0.01) < 1e-9
    half = conjunct_selectivity(Cmp("<", Col("a"), Lit(500, INT)), st)
    assert 0.3 < half < 0.7


def _plan_of(sess, sql):
    from cockroach_tpu.sql.bind import Binder
    from cockroach_tpu.sql import parser as P

    ast = P.Parser(sql).parse_select()
    return Binder(sess.catalog).bind(ast)


def _probe_table(plan):
    """The probe (left) spine's base table of the top join."""
    node = plan
    while not isinstance(node, Join):
        node = node.inputs()[0]
    left = node.left
    while not isinstance(left, (Scan, IndexScan)):
        left = left.inputs()[0]
    return left.table


def test_stats_flip_join_order(sess):
    """big has 3000 rows but the filter keeps ~3; without stats the
    binder treats filtered-big as the fact table (3000*0.2=600 > 100);
    with ANALYZE stats the estimate drops to ~3 and `small` becomes the
    probe spine."""
    sess.execute("create table big (id int primary key, fk int, v int)")
    sess.execute("create table small (sid int primary key, w int)")
    rows = ", ".join(f"({i}, {i % 100}, {i % 7})" for i in range(3000))
    sess.execute(f"insert into big values {rows}")
    rows = ", ".join(f"({i}, {i})" for i in range(100))
    sess.execute(f"insert into small values {rows}")

    q = ("select big.id, small.w from big, small "
         "where big.fk = small.sid and big.v = 1 and big.id < 8")
    before = _probe_table(_plan_of(sess, q))
    assert before == "big"

    sess.execute("analyze big")
    sess.execute("analyze small")
    after = _probe_table(_plan_of(sess, q))
    assert after == "small"

    # and the answer is right regardless of order: id<8 with id%7==1
    kind, got, _ = sess.execute(q)
    assert kind == "rows"
    assert sorted(got["id"].tolist()) == [1]
    assert got["w"].tolist() == [1]  # small.sid == big.fk == 1


# ----- the join orderer's rank: the share a join keeps, then the size -----
#
# ISSUE 39: `Binder._join_tree` attaches first the relation whose join
# KEEPS the smallest share of the tree's rows, by the estimate that sizes a
# Shrink (`sql/plan.keep_share`), and only among equal shares the smallest.

class _Counts(Catalog):
    """Row counts, primary keys and column statistics, and no rows."""

    def __init__(self, tables):
        # name -> (rows, columns, {column: distinct values})
        self.tables = tables

    def table_schema(self, name):
        return Schema([Field(c, INT) for c in self.tables[name][1]])

    def table_rows(self, name):
        return self.tables[name][0]

    def table_pk(self, name):
        return (self.tables[name][1][0],)

    def table_stats(self, name):
        rows, _cols, distinct = self.tables[name]
        return TableStats(rows, {
            c: ColumnStats(distinct=d, null_frac=0.0, lo=0, hi=d - 1)
            for c, d in distinct.items()})


def _star():
    return _Counts({
        "fact": (1_000_000, ["f_id", "f_big", "f_mid", "f_small", "f_v"],
                 {}),
        "big": (50_000, ["b_id", "b_flag"], {"b_flag": 20}),
        "mid": (5_000, ["m_id", "m_sat", "m_flag"], {"m_flag": 2}),
        "small": (1_000, ["s_id", "s_w"], {}),
        "sat": (100, ["t_id", "t_flag"], {"t_flag": 10}),
    })


def _shape(p):
    """`((fact semi big) inner small)`: the join tree with everything but
    joins, scans and Shrinks taken off; `[...]` is a Shrink."""
    if isinstance(p, (Scan, IndexScan)):
        return p.table
    if isinstance(p, Join):
        return f"({_shape(p.left)} {p.how} {_shape(p.right)})"
    inner = _shape(p.inputs()[0])
    return f"[{inner}]" if isinstance(p, Shrink) else inner


def _bound(cat, sql, params=None):
    """-> (the normalized plan, the binder's `join_ranks`, the events and
    rows of stage `sql.join_rank`)."""
    from cockroach_tpu.exec import stats

    col = stats.enable()
    try:
        binder = Binder(cat, params=params)
        plan = normalize(binder.bind(parser.parse(sql)), cat)
    finally:
        stats.disable()
    stage = col.stages.get("sql.join_rank")
    return plan, binder.join_ranks, (
        (stage.events, stage.rows) if stage else (0, 0))


@pytest.mark.parametrize("tables,where,shape,ranks", [
    # (i) a small unfiltered and a larger filtered dimension: the filtered
    # one (2,500 of 50,000: keeps 5%) goes first though `small` (1,000) is
    # smaller, and its Shrink stands directly above it
    ("fact, big, small", "f_big = b_id and f_small = s_id and b_flag = 1",
     "([(fact semi big)] semi small)", [1]),
    # the same statement written the other way round
    ("small, big, fact", "f_small = s_id and b_flag = 1 and f_big = b_id",
     "([(fact semi big)] semi small)", [1]),
    # (ii) equal shares fall back to size: nothing filtered ...
    ("fact, big, mid, small",
     "f_big = b_id and f_small = s_id and f_mid = m_id",
     "(((fact semi small) semi mid) semi big)", [0]),
    # ... and a dimension that keeps half (mid, 2,500 of 5,000) goes under
    # the two that keep everything, which follow by size
    ("fact, big, mid, small",
     "f_big = b_id and f_mid = m_id and m_flag = 1 and f_small = s_id",
     "(((fact semi mid) semi small) semi big)", [1]),
    # (iii) a filter on a satellite counts for the relation that carries
    # it: sat keeps a tenth, so mid x sat goes under small
    ("fact, mid, small, sat",
     "f_mid = m_id and m_sat = t_id and t_flag = 3 and f_small = s_id",
     "([(fact inner [(mid semi sat)])] semi small)", [1]),
    # a satellite with no filter leaves its parent at 1.0: size decides
    ("fact, mid, small, sat",
     "f_mid = m_id and m_sat = t_id and f_small = s_id",
     "((fact semi small) inner (mid semi sat))", [0]),
    # one candidate a step: no choice, no event
    ("fact, mid, sat", "f_mid = m_id and m_sat = t_id and t_flag = 3",
     "[(fact inner [(mid semi sat)])]", []),
])
def test_the_orderer_attaches_what_removes_most_first(tables, where, shape,
                                                      ranks):
    plan, join_ranks, stage = _bound(
        _star(), f"select f_v from {tables} where {where}")
    assert _shape(plan) == shape
    assert join_ranks == ranks
    assert stage == (len(ranks), sum(ranks))


# SF1's row counts (TPC-H clause 4.2.5; lineitem's as PERF.md has it)
_SF1_ROWS = {"lineitem": 5_999_863, "orders": 1_500_000, "customer": 150_000,
             "part": 200_000, "partsupp": 800_000, "supplier": 10_000,
             "nation": 25}


@pytest.fixture(scope="module")
def tpch():
    """TPC-H at SF 0.01 as the Q9 cell loads it (every cell's tables), and
    beside it a catalog with SF1's row counts and no rows of its own: the
    orderer reads counts and statistics, never a row."""
    import dataclasses

    from benchmark.loaders import tpch_cname, tpch_pname
    from benchmark.reference import tpch_q9
    from cockroach_tpu.sql.plan import MVCCCatalog

    loaded = tpch_cname.load_from(tpch_pname.TPCHPName(sf=0.01, seed=7),
                                  MVCCStore(), list(_SF1_ROWS))
    small = loaded["catalog"]
    loaded["sf1"] = MVCCCatalog(
        small.store, small.tables, rows=_SF1_ROWS, pks=small.pks,
        stats={t: dataclasses.replace(small.stats[t], row_count=n)
               for t, n in _SF1_ROWS.items()})
    loaded["ref"] = tpch_q9.Reference(loaded["data"], loaded["dicts"], {})
    return loaded


def _cell_sql(cell):
    from benchmark import manifest

    return manifest.cell(cell)["statements"][0]["sql"]


_Q9_SHAPE = ("((([(lineitem semi part)] inner (supplier inner nation)) "
             "inner partsupp) inner orders)")
_Q3_SHAPE = "[(lineitem inner [(orders semi customer)])]"


@pytest.mark.parametrize("cell,params,catalog,shape,ranks", [
    # (iv) Q3 has one candidate a step, whatever the scale: no event
    ("tpch-sf1.q3-1stream", None, "catalog", _Q3_SHAPE, []),
    ("tpch-sf1.q3-1stream", None, "sf1", _Q3_SHAPE, []),
    ("tpch-sf1-qgen.q3-1stream", ("BUILDING", "1995-03-15", "1995-03-15"),
     "sf1", _Q3_SHAPE, []),
    # Q18 chooses under orders between customer and the subquery (a flat
    # 65,536), both at share 1.0: the size decides, as it always has
    ("tpch-sf1-q18.q18-1stream", ("312",), "catalog",
     "[(lineitem inner [((orders inner customer) semi [lineitem])])]", [0]),
    ("tpch-sf1-q18.q18-1stream", ("312",), "sf1",
     "[(lineitem inner ([(orders semi [lineitem])] inner customer))]", [0]),
    # Q9: the filtered part (5% of its rows) goes under supplier, which is
    # smaller and removes nothing; the three at 1.0 follow by size
    ("tpch-sf1-q9.q9-1stream", ("%green%",), "catalog", _Q9_SHAPE, [1]),
    ("tpch-sf1-q9.q9-1stream", ("%green%",), "sf1", _Q9_SHAPE, [1]),
    # this seed's 2,000 names hold `yellow` 97 times: 9,700 of 200,000, so
    # part is ALSO the smallest and the size would have chosen it too (at
    # SF1 every colour keeps 10,600-11,150 parts, over supplier's 10,000)
    ("tpch-sf1-q9.q9-1stream", ("%yellow%",), "sf1", _Q9_SHAPE, [0]),
])
def test_the_cells_join_orders(tpch, cell, params, catalog, shape, ranks):
    plan, join_ranks, stage = _bound(tpch[catalog], _cell_sql(cell), params)
    assert _shape(plan) == shape
    assert join_ranks == ranks
    assert stage == (len(ranks), sum(ranks))


def test_the_shrink_at_sf1_stands_on_the_join_the_orderer_put_first(tpch):
    """One definition read in both places: the share that ranked part
    first is the share that sizes the Shrink above its join, and EXPLAIN
    prints it beside the join."""
    from cockroach_tpu.sql.explain import render_plan
    from cockroach_tpu.sql.plan import (
        _walk_plan, estimate_cardinality, join_keeps,
    )

    cat = tpch["sf1"]
    plan, _ranks, _stage = _bound(cat, _cell_sql("tpch-sf1-q9.q9-1stream"),
                                  ("%green%",))
    (shrink,) = [n for n in _walk_plan(plan) if isinstance(n, Shrink)]
    semi = shrink.input
    assert isinstance(semi, Join) and semi.how == "semi"
    hits = sum("green" in n for n in tpch["ref"].names)
    share = hits / len(tpch["ref"].names)
    assert join_keeps(semi, cat) == pytest.approx(share)
    assert estimate_cardinality(semi, cat) == pytest.approx(
        _SF1_ROWS["lineitem"] * share)
    # 1.5 times the estimate, to a power of two: 524,288 lanes at SF1
    assert shrink.start_capacity == 1 << 19
    lines = render_plan(plan, cat)
    (line,) = [ln for ln in lines if "semi join on" in ln]
    assert line.strip().endswith(
        f"semi join on l_partkey=p_partkey (keeps ~{100 * share:.1f}%)")
    assert sum("(keeps ~100.0%)" in ln for ln in lines) == 4


@pytest.mark.parametrize("pattern", ["%green%", "%dark%", "%nothing%"])
def test_q9_is_exact_in_the_new_order(tpch, pattern):
    """(v) the reordered plan against the plain reference, one pattern
    matching no part."""
    from cockroach_tpu.exec.operators import JoinOp, ShrinkOp, walk_operators
    from tests.test_q9 import _as_wire, _run, _session

    sess = _session(tpch)
    sql = _cell_sql("tpch-sf1-q9.q9-1stream")
    answer = _as_wire(_run(sess, sql, (pattern,)), tpch["dicts"]["n_name"])
    oks, compared = tpch["ref"].check([((pattern,), answer)])
    assert oks == [True], compared
    assert (len(answer) == 0) == (pattern == "%nothing%")
    (shrink,) = [o for o in walk_operators(sess._prepared.get(sql).op)
                 if isinstance(o, ShrinkOp)]
    assert isinstance(shrink.child, JoinOp) and shrink.child.how == "semi"
