"""Planner seam tests (sql/plan.py): normalization (predicate pushdown,
top-K fusion, ordered-agg detection), the plan->operator builder, the
distribution decision, and catalogs (TPC-H generator + MVCC storage) —
the NewColOperator/norm-rules analog (SURVEY.md §2.4, execplan.go:785).
"""

import numpy as np
import pytest

from cockroach_tpu.coldata.batch import Field, INT, Schema
from cockroach_tpu.exec import collect
from cockroach_tpu.exec.operators import (
    HashAggOp, JoinOp, MapOp, OrderedAggOp, ScanOp, TopKOp,
)
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.ops.expr import BinOp, Cmp, Col, Lit
from cockroach_tpu.ops.sort import SortKey
from cockroach_tpu.sql import (
    Aggregate, Filter, Join, Limit, MVCCCatalog, OrderBy, Project, Scan,
    TPCHCatalog, build, normalize, run,
)
from cockroach_tpu.workload.tpch import TPCH
from cockroach_tpu.workload import tpch_queries as Q


def test_pushdown_splits_conjuncts_to_join_sides():
    gen = TPCH(sf=0.01)
    cat = TPCHCatalog(gen)
    plan = Filter(
        Join(Scan("orders", ("o_orderkey", "o_custkey", "o_orderdate")),
             Scan("customer", ("c_custkey", "c_name")),
             ("o_custkey",), ("c_custkey",)),
        # one conjunct per side: both must sink below the join
        Cmp("<", Col("o_orderdate"), Lit(9000, INT)))
    norm = normalize(plan, cat)
    assert isinstance(norm, Join)           # filter no longer on top
    assert isinstance(norm.left, Filter)    # ...it sank to the probe side
    assert isinstance(norm.left.input, Scan)


def test_orderby_limit_builds_topk_and_ordered_agg():
    gen = TPCH(sf=0.01)
    cat = TPCHCatalog(gen)
    topk = build(Limit(OrderBy(Scan("nation"), (SortKey("n_nationkey"),)),
                       5), cat, 64)
    assert isinstance(topk, TopKOp)
    # aggregate over input ordered by the group keys -> OrderedAggOp
    agg = build(Aggregate(OrderBy(Scan("nation"), (SortKey("n_regionkey"),)),
                          ("n_regionkey",),
                          (AggSpec("count_star", None, "n"),)), cat, 64)
    assert isinstance(agg, OrderedAggOp)
    # unordered input -> HashAggOp
    agg2 = build(Aggregate(Scan("nation"), ("n_regionkey",),
                           (AggSpec("count_star", None, "n"),)), cat, 64)
    assert isinstance(agg2, HashAggOp) and not isinstance(agg2, OrderedAggOp)


def test_sixth_query_needs_no_wiring():
    """VERDICT r3 item 4's done-bar: an unplanned-for query (TPC-H Q4
    shape: EXISTS semi-join + group-count + order) runs through the seam
    with nothing but a plan definition."""
    gen = TPCH(sf=0.01)
    o = gen.table("orders")
    l = gen.table("lineitem")
    lo, hi = 8582, 8582 + 92  # ~3 months of order dates
    from cockroach_tpu.ops.expr import BoolOp

    plan = OrderBy(
        Aggregate(
            Filter(
                Join(Scan("orders", ("o_orderkey", "o_orderdate",
                                     "o_orderpriority")),
                     # l_commitdate < l_receiptdate: late lineitems
                     Project(
                         Filter(Scan("lineitem",
                                     ("l_orderkey", "l_commitdate",
                                      "l_receiptdate")),
                                Cmp("<", Col("l_commitdate"),
                                    Col("l_receiptdate"))),
                         (("lk", Col("l_orderkey")),)),
                     ("o_orderkey",), ("lk",), how="semi"),
                BoolOp("and", (
                    Cmp(">=", Col("o_orderdate"), Lit(lo, INT)),
                    Cmp("<", Col("o_orderdate"), Lit(hi, INT))))),
            ("o_orderpriority",),
            (AggSpec("count_star", None, "order_count"),)),
        (SortKey("o_orderpriority"),))
    res = run(plan, TPCHCatalog(gen), capacity=1 << 12)
    late = set(l["l_orderkey"][l["l_commitdate"] < l["l_receiptdate"]]
               .tolist())
    keep = ((o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi) & np.isin(
        o["o_orderkey"], np.fromiter(late, dtype=np.int64)))
    exp: dict = {}
    for p in o["o_orderpriority"][keep].tolist():
        exp[p] = exp.get(p, 0) + 1
    got = dict(zip(res["o_orderpriority"].tolist(),
                   res["order_count"].tolist()))
    assert got == exp


@pytest.mark.parametrize("qn", [1, 3, 6, 9, 18])
def test_all_queries_build_through_planner(qn):
    gen = TPCH(sf=0.01)
    flow = Q.QUERIES[qn](gen, 1 << 12)
    # spot the structure: every leaf is a ScanOp reached through the seam
    from cockroach_tpu.exec.operators import walk_operators

    kinds = {type(op).__name__ for op in walk_operators(flow)}
    assert "ScanOp" in kinds


def test_distributed_decision(rng):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the CPU mesh")
    from cockroach_tpu.parallel import make_mesh

    gen = TPCH(sf=0.01)
    local = run(Q.q3_plan(), TPCHCatalog(gen), 1 << 12)
    dist = run(Q.q3_plan(), TPCHCatalog(gen), 1 << 12,
               mesh=make_mesh(8))
    for name in ("l_orderkey", "revenue"):
        np.testing.assert_array_equal(np.sort(local[name]),
                                      np.sort(dist[name]))


def test_mvcc_catalog_serves_plans():
    """The same planner runs over the C++ MVCC storage layer: scan ->
    filter -> aggregate over LSM-resident rows."""
    from cockroach_tpu.storage import MVCCStore, NativeEngine
    from cockroach_tpu.storage.engine import _load
    from cockroach_tpu.util.hlc import HLC, ManualClock

    if _load() is None:
        pytest.skip("no C++ toolchain")
    st = MVCCStore(engine=NativeEngine(), clock=HLC(ManualClock(5)))
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 100, 300)
    for pk, v in enumerate(vals):
        st.put(7, pk, [int(v), pk % 5])
    schema = Schema([Field("v", INT), Field("g", INT)])
    cat = MVCCCatalog(st, {"t": (7, schema)})
    plan = Aggregate(Filter(Scan("t"), Cmp(">=", Col("v"), Lit(50, INT))),
                     ("g",), (AggSpec("sum", "v", "s"),))
    res = run(plan, cat, capacity=128)
    keep = vals >= 50
    exp = {g: int(vals[keep & (np.arange(300) % 5 == g)].sum())
           for g in range(5)}
    got = dict(zip(res["g"].tolist(), res["s"].tolist()))
    assert got == {k: v for k, v in exp.items() if v}


# -- a selective join directly under an Aggregate is shrunk like any other --

def _having_shaped_build():
    """orders joined to the orders whose lineitems sum past a bound: the
    build is a HAVING-shaped filter, which rule (1) shrinks."""
    big = Project(
        Filter(Aggregate(Scan("lineitem", ("l_orderkey", "l_quantity")),
                         ("l_orderkey",),
                         (AggSpec("sum", "l_quantity", "qty"),)),
               Cmp(">", Col("qty"), Lit(300, INT))),
        (("big_okey", Col("l_orderkey")),))
    return Join(Scan("orders", ("o_orderkey", "o_custkey")), big,
                ("o_orderkey",), ("big_okey",), how="semi")


def _selective_by_the_statistics():
    """lineitem against one day's orders: the estimate keeps a sliver."""
    day = Filter(Scan("orders", ("o_orderkey", "o_orderdate")),
                 Cmp("==", Col("o_orderdate"), Lit(Q.Q3_DATE, INT)))
    return Join(Scan("lineitem", ("l_orderkey", "l_quantity")), day,
                ("l_orderkey",), ("o_orderkey",))


@pytest.mark.parametrize("join,with_catalog,key,capacity", [
    # rule (2): the build is already shrunk; no statistics asked
    (_having_shaped_build, False, "o_custkey", 1 << 14),
    # rule (3): the statistics' estimate is a sliver of the probe's
    (_selective_by_the_statistics, True, "l_orderkey", None),
], ids=["a_shrunk_build", "the_statistics_estimate"])
def test_insert_shrinks_reaches_a_join_directly_under_an_aggregate(
        join, with_catalog, key, capacity):
    """Until PR 44 both rules stepped over a join whose parent was an
    Aggregate (the group-join collapse wanted it raw): now it compacts
    like every other selective join."""
    from cockroach_tpu.sql.plan import Shrink, insert_shrinks

    cat = TPCHCatalog(TPCH(sf=0.01)) if with_catalog else None
    plan = Aggregate(join(), (key,), (AggSpec("count_star", None, "n"),))
    out = insert_shrinks(plan, cat)
    assert isinstance(out, Aggregate) and isinstance(out.input, Shrink)
    assert isinstance(out.input.input, Join)
    if capacity is not None:
        assert out.input.start_capacity == capacity
    else:       # from the estimate: a power of two under the probe's rows
        cap = out.input.start_capacity
        assert cap & (cap - 1) == 0 and 1 << 12 <= cap < 60175


@pytest.mark.parametrize("qn", [3, 18])
def test_hand_built_aggregate_over_join_is_shrunk_and_answers_its_oracle(qn):
    """Q3 and Q18 built by hand are Aggregate(Join): normalized, the join
    stands under a Shrink, compacts with it and leaves the aggregate its
    input grouped, the program their SQL text runs; the answer is the
    oracle's."""
    from cockroach_tpu.exec import stats
    from cockroach_tpu.sql.plan import Shrink

    gen = TPCH(sf=0.01)
    cat = TPCHCatalog(gen)
    node = normalize(Q.PLANS[qn](gen), cat)
    while not isinstance(node, Aggregate):
        (node,) = node.inputs()
    assert isinstance(node.input, Shrink)
    assert isinstance(node.input.input, Join)
    col = stats.enable()
    try:
        res = collect(Q.QUERIES[qn](gen, capacity=1 << 13), fuse=True)
    finally:
        stats.disable()
    traced = col.stages["fused.compile"].events
    assert col.stages["fused.agg_ordered"].events == traced
    assert col.stages["fused.join_compact"].events == 2 * traced
    if qn == 3:
        got = sorted(zip(res["l_orderkey"].tolist(), res["revenue"].tolist(),
                         res["o_orderdate"].tolist()))
        assert got == sorted(Q.q3_oracle(gen))
    else:
        got = [tuple(int(res[n][i]) for n in (
            "c_name", "c_custkey", "o_orderkey", "o_orderdate",
            "o_totalprice", "sum_qty")) for i in range(len(res["c_name"]))]
        assert got == Q.q18_oracle(gen)
