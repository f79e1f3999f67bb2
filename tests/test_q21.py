"""TPC-H Q21 in the specification's text as a served deployment (ISSUE 50):
three aliases of lineitem, a correlated EXISTS and a correlated NOT EXISTS
with a `<>` beside their equality, NATION bound as data.

- the binder: aliases of one table scoped apart, outer references,
  EXISTS / NOT EXISTS -> plan.Apply with its `correlation` and `residual`,
  and every shape still outside a BindError of its own words;
- plan.decorrelate with a residual against a nested-loop evaluation (and
  against the reference's `exists_other`) on seeded random tables with
  duplicate keys, empty groups and NULLs, for `<>, <, <=, >, >=`, semi
  and anti;
- the system (pgwire's extended protocol -> Session -> compile_plan ->
  FusedRunner) against the benchmark's plain reference
  (benchmark/reference/tpch_q21.py) at SF 0.01 on the CPU at all 25
  nations, from ONE prepared entry and ONE program;
- Q4's specification text binds to the plan q4_plan() builds by hand;
- EXPLAIN, the stages and the counter.
"""

import operator

import numpy as np
import pytest

from benchmark import manifest, wire
from benchmark.loaders import tpch_cname, tpch_dbgen, tpch_sname
from benchmark.paramgen import tpch_qgen_q21
from benchmark.reference import tpch_q21
from cockroach_tpu.coldata.batch import INT, Field, Schema
from cockroach_tpu.exec import collect, fused, stats
from cockroach_tpu.exec.operators import ScanOp, walk_operators
from cockroach_tpu.ops.expr import BoolOp, Cmp, Col
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import BindError, Binder
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.plan import (
    Aggregate, Apply, Catalog, Filter, Join, Project, Scan, Shrink,
    TPCHCatalog, _walk_plan, build, normalize,
)
from cockroach_tpu.sql.session import Session
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.workload import tpch_queries as Q
from cockroach_tpu.workload.tpch import TPCH

CELL = "tpch-sf1-q21.q21-1stream"
CAP = 1 << 17          # the configuration's: one chunk a table at SF 0.01
TABLES = ["supplier", "lineitem", "orders", "nation"]
Q21 = manifest.cell(CELL)["statements"][0]["sql"]


def _counter(name):
    return default_registry().counter(name).value()


@pytest.fixture(scope="module")
def tpch():
    gen = TPCH(sf=0.01)
    return gen, TPCHCatalog(gen)


def _bind(sql, cat, params=None):
    b = Binder(cat, params=params)
    return b.bind(parser.parse(sql)), b


# ------------------------------------------------------------ the binder ---

def test_three_aliases_of_one_table_scope_apart(tpch):
    _gen, cat = tpch
    plan, _b = _bind(Q21, cat, ("FRANCE",))
    applies = [n for n in _walk_plan(plan) if isinstance(n, Apply)]
    assert [a.kind for a in applies] == ["not_exists", "exists"]
    for a, alias in zip(applies, ("l3", "l2")):
        # `l1.l_orderkey` is the outer query's own name (its FROM names
        # lineitem once); the subquery's alias qualifies its columns
        assert a.correlation == (("l_orderkey", f"{alias}.l_orderkey"),)
        r = a.residual
        assert isinstance(r, Cmp) and r.op == "!="
        assert (r.left.name, r.right.name) == (f"{alias}.l_suppkey",
                                               "l_suppkey")
    # ONE value of Scan for the three aliases: one operator, one image
    scans = {n for n in _walk_plan(plan)
             if isinstance(n, Scan) and n.table == "lineitem"}
    assert scans == {Scan("lineitem", ("l_orderkey", "l_suppkey",
                                       "l_commitdate", "l_receiptdate"))}
    op = build(plan, cat, 1 << 14)
    assert len([s for s in walk_operators(op)
                if isinstance(s, ScanOp) and s.table == "lineitem"]) == 1


def test_a_self_join_in_one_from_list_names_columns_by_alias(tpch):
    gen, cat = tpch
    sql = ("select n1.n_name, count(*) as c from nation n1, nation n2 "
           "where n1.n_regionkey = n2.n_regionkey "
           "group by n1.n_name order by n1.n_name")
    plan, _b = _bind(sql, cat)
    joins = [n for n in _walk_plan(plan) if isinstance(n, Join)]
    assert [(j.left_on, j.right_on) for j in joins] in (
        [(("n1.n_regionkey",), ("n2.n_regionkey",))],
        [(("n2.n_regionkey",), ("n1.n_regionkey",))])
    from cockroach_tpu.sql.plan import run
    res = run(plan, cat, 1 << 12)
    n = gen.table("nation")
    per_region = np.bincount(n["n_regionkey"])
    want = {int(c): int(per_region[r])
            for c, r in zip(n["n_name"].tolist(), n["n_regionkey"].tolist())}
    # `n1.n_name` goes back under the name SQL gives it
    assert dict(zip(res["n_name"].tolist(), res["c"].tolist())) == want


def test_an_unaliased_statement_binds_to_the_names_it_always_had(tpch):
    _gen, cat = tpch
    for cell in ("tpch-sf1.q3-1stream", "tpch-sf1-q18.q18-1stream"):
        sql = manifest.cell(cell)["statements"][0]["sql"]
        params = ("312",) if "$1" in sql else None
        plan, b = _bind(sql, cat, params)
        assert not b._renamed_any
        assert not any("." in c for n in _walk_plan(plan)
                       if isinstance(n, Scan) for c in n.columns or ())
        assert not any(isinstance(n, Apply) for n in _walk_plan(plan))


_OUTSIDE = {
    "ambiguous_bare_name": (
        "select count(*) from nation n1, nation n2 "
        "where n_regionkey = n2.n_regionkey", "ambiguous column"),
    "duplicate_alias": (
        "select count(*) from nation n1, nation n1 "
        "where n1.n_regionkey = n1.n_regionkey", "duplicate table/alias"),
    "same_table_twice_without_an_alias": (
        "select count(*) from nation, nation", "duplicate table/alias"),
    "exists_under_or": (
        "select count(*) from orders where o_orderkey = 1 or exists "
        "(select * from lineitem where l_orderkey = o_orderkey)",
        "top-level WHERE conjuncts"),
    "exists_in_a_projection": (
        "select exists (select * from lineitem where l_orderkey = "
        "o_orderkey) from orders", "top-level WHERE conjuncts"),
    "uncorrelated_exists": (
        "select count(*) from orders where exists "
        "(select * from lineitem where l_quantity > 10)",
        "needs an equality"),
    "correlated_by_an_inequality_alone": (
        "select count(*) from orders where exists "
        "(select * from lineitem where l_orderkey < o_orderkey)",
        "needs an equality"),
    "two_residual_comparisons": (
        "select count(*) from lineitem l1 where exists (select * from "
        "lineitem l2 where l2.l_orderkey = l1.l_orderkey and "
        "l2.l_suppkey <> l1.l_suppkey and l2.l_partkey <> l1.l_partkey)",
        "more than one comparison"),
    "correlated_predicate_under_or": (
        "select count(*) from orders where exists (select * from lineitem "
        "where l_orderkey = o_orderkey and "
        "(l_suppkey = o_custkey or l_quantity > 10))",
        "must compare ONE column"),
    "correlated_arithmetic": (
        "select count(*) from orders where exists (select * from lineitem "
        "where l_orderkey = o_orderkey and l_suppkey <> o_custkey + 1)",
        "must compare ONE column"),
    "residual_over_strings": (
        "select count(*) from orders where exists (select * from lineitem "
        "where l_orderkey = o_orderkey and l_shipmode < o_orderpriority)",
        "only numbers and dates"),
    "grouped_exists_subquery": (
        "select count(*) from orders where exists (select l_orderkey from "
        "lineitem where l_orderkey = o_orderkey group by l_orderkey)",
        "GROUP BY, HAVING, aggregates"),
    "aggregate_in_an_exists_subquery": (
        "select count(*) from orders where exists (select count(*) from "
        "lineitem where l_orderkey = o_orderkey)",
        "GROUP BY, HAVING, aggregates"),
    "subquery_nested_in_an_exists": (
        "select count(*) from orders where exists (select * from lineitem "
        "where l_orderkey = o_orderkey and l_partkey in "
        "(select p_partkey from part))", "nested in an EXISTS"),
    "correlated_in_subquery": (
        "select count(*) from orders where o_orderkey in "
        "(select l_orderkey from lineitem where l_suppkey = o_custkey)",
        "unknown column"),
    "unknown_outer_alias": (
        "select count(*) from orders where exists (select * from lineitem "
        "where l_orderkey = x.o_orderkey)", "unknown table/alias"),
}


@pytest.mark.parametrize("shape", sorted(_OUTSIDE))
def test_a_shape_still_outside_raises_in_its_own_words(tpch, shape):
    _gen, cat = tpch
    sql, words = _OUTSIDE[shape]
    with pytest.raises(BindError, match=words):
        _bind(sql, cat)


def test_outer_references_resolve_unqualified_and_either_way_round(tpch):
    gen, cat = tpch
    # the outer column on the LEFT of both comparisons, no alias anywhere
    sql = ("select count(*) as c from orders where exists (select * from "
           "lineitem where o_orderkey = l_orderkey "
           "and o_custkey > l_suppkey)")
    plan, _b = _bind(sql, cat)
    (ap,) = [n for n in _walk_plan(plan) if isinstance(n, Apply)]
    assert ap.correlation == (("o_orderkey", "l_orderkey"),)
    # read with the subquery's column on the left: l_suppkey < o_custkey
    assert (ap.residual.op, ap.residual.left.name,
            ap.residual.right.name) == ("<", "l_suppkey", "o_custkey")
    from cockroach_tpu.sql.plan import run
    res = run(plan, cat, 1 << 13)
    o, l = gen.table("orders"), gen.table("lineitem")
    least = {}
    for k, s in zip(l["l_orderkey"].tolist(), l["l_suppkey"].tolist()):
        least[k] = min(s, least.get(k, s))
    want = sum(1 for k, c in zip(o["o_orderkey"].tolist(),
                                 o["o_custkey"].tolist())
               if k in least and least[k] < c)
    assert res["c"].tolist() == [want]


# -------------------------------------- decorrelate() with a residual ---

class _Tables(Catalog):
    def __init__(self, tables):
        self.t = tables

    def table_schema(self, name):
        return Schema([Field(n, INT, nullable=n + "__valid" in self.t[name])
                       for n in self.t[name] if not n.endswith("__valid")])

    def table_rows(self, name):
        return len(next(iter(self.t[name].values())))

    def table_chunks(self, name, capacity, columns=None):
        def chunks():
            for i in range(0, max(self.table_rows(name), 1), capacity):
                yield {k: v[i:i + capacity] for k, v in self.t[name].items()}
        return chunks


_OPS = {"!=": operator.ne, "<": operator.lt, "<=": operator.le,
        ">": operator.gt, ">=": operator.ge}


def _random_tables(seed):
    """Outer `a` (300 rows, keys 0..59) and inner `b` (500 rows, keys
    0..49: ten outer keys have an EMPTY group), keys many times over, the
    compared columns in 0..5 with NULLs on both sides."""
    rng = np.random.default_rng(seed)
    a = {"ak": rng.integers(0, 60, 300), "ax": rng.integers(0, 6, 300),
         "ax__valid": rng.random(300) > 0.1}
    b = {"bk": rng.integers(0, 50, 500), "bx": rng.integers(0, 6, 500),
         "bx__valid": rng.random(500) > 0.2}
    return a, b


@pytest.mark.parametrize("kind", ["exists", "not_exists"])
@pytest.mark.parametrize("op", sorted(_OPS))
def test_decorrelate_with_a_residual_answers_as_nested_loops(op, kind):
    a, b = _random_tables(11)
    cat = _Tables({"a": a, "b": b})
    plan = Apply(Scan("a"), Scan("b"), (("ak", "bk"),), kind, None,
                 Cmp(op, Col("bx"), Col("ax")))
    norm = normalize(plan, cat)
    (join,) = [n for n in _walk_plan(norm) if isinstance(n, Join)]
    assert join.how == ("semi" if kind == "exists" else "anti")
    assert isinstance(join.right.input, Aggregate)
    assert len(join.right.input.aggs) == (2 if op == "!=" else 1)
    for fuse in (True, False):
        res = collect(build(plan, cat, 128), fuse=fuse)
        got = sorted(zip(res["ak"].tolist(),
                         [x if v else None for x, v in
                          zip(res["ax"].tolist(),
                              res["ax__valid"].tolist())]), key=str)
        want = []
        for i in range(300):
            found = any(
                b["bk"][j] == a["ak"][i] and b["bx__valid"][j]
                and a["ax__valid"][i] and _OPS[op](b["bx"][j], a["ax"][i])
                for j in range(500))
            if found == (kind == "exists"):
                want.append((int(a["ak"][i]),
                             int(a["ax"][i]) if a["ax__valid"][i] else None))
        assert got == sorted(want, key=str), (op, kind, fuse)


@pytest.mark.parametrize("seed", [3, 2147483999])
def test_the_references_exists_other_is_the_operators_semantics(seed):
    """benchmark/reference/tpch_q21.exists_other (the set of x on a key)
    against the decorrelated `<>` on one table joined to itself: NULLs,
    duplicate (key, x) pairs and keys of one row."""
    a, _b = _random_tables(seed)
    a["id"] = np.arange(300)
    among = np.random.default_rng(seed + 1).random(300) > 0.5
    a["pick"] = among.astype(np.int64)
    cat = _Tables({"a": a})
    sub = Project(Filter(Scan("a"), Cmp("==", Col("pick"),
                                        Col("pick") * 0 + 1)),
                  (("bk", Col("ak")), ("bx", Col("ax"))))
    plan = Apply(Scan("a"), sub, (("ak", "bk"),), "exists", None,
                 Cmp("!=", Col("bx"), Col("ax")))
    res = collect(build(plan, cat, 128))
    want = tpch_q21.exists_other(a["ak"], a["ax"], a["ax__valid"], among)
    assert sorted(res["id"].tolist()) == np.flatnonzero(want).tolist()
    assert 20 < want.sum() < 280


def test_an_apply_with_any_other_residual_is_refused():
    cat = _Tables({"a": _random_tables(1)[0], "b": _random_tables(1)[1]})
    both = BoolOp("and", (Cmp("!=", Col("bx"), Col("ax")),
                          Cmp("<", Col("bx"), Col("ax"))))
    for residual, corr in ((both, (("ak", "bk"),)),
                           (Cmp("==", Col("bx"), Col("ax")), (("ak", "bk"),)),
                           (Cmp("!=", Col("bx"), Col("ax")), ())):
        with pytest.raises(TypeError, match="ONE comparison"):
            normalize(Apply(Scan("a"), Scan("b"), corr, "exists", None,
                            residual), cat)


# ------------------------------- the system against the plain reference ---

@pytest.fixture(scope="module")
def served():
    gen = tpch_sname.TPCHSName(sf=0.01, seed=2147483999)
    loaded = tpch_cname.load_from(gen, MVCCStore(), TABLES)
    loaded["pg"] = PgServer(loaded["catalog"], capacity=CAP).start()
    loaded["ref"] = tpch_q21.Reference(loaded["data"], loaded["dicts"], {})
    yield loaded
    loaded["pg"].close()


def test_the_parameter_stream_draws_the_loaders_nations():
    assert list(tpch_qgen_q21.NATIONS) == [n for n, _r in tpch_dbgen.NATIONS]
    state = tpch_qgen_q21.prepare({})
    drawn = tpch_qgen_q21.draw({}, np.random.default_rng([7, 0]), 400, state)
    assert {d[0] for d in drawn} == set(tpch_qgen_q21.NATIONS)
    assert len(tpch_qgen_q21.corners({})) == 25


def test_q21_is_exact_at_all_25_nations_from_one_entry_and_one_program(
        served, monkeypatch):
    texts = []
    lower = fused.lower_program

    def recording(fn, args):
        lowered = lower(fn, args)
        texts.append(lowered.as_text())
        return lowered

    monkeypatch.setattr(fused, "lower_program", recording)
    ref = served["ref"]
    client = wire.WireClient(served["pg"].addr, timeout=600.0)
    assert client.query("set vectorize = tpu") == ([], None)
    textual = _counter("sql_bind_textual_total")
    as_data = _counter("sql_bind_params_total")
    rewritten = _counter("sql_apply_decorrelated_total")
    col = stats.enable()
    try:
        sizes = {}
        for (nation,) in tpch_qgen_q21.corners({}):
            rows, code = client.query_extended(Q21, (nation,))
            assert code is None, (nation, code)
            got = [tuple(r) for r in rows]
            oks, compared = ref.check([((nation,), got)])
            assert oks == [True], (nation, compared, got[:3])
            sizes[nation] = len(got)
    finally:
        stats.disable()
        client.close()
    # every nation has suppliers that kept an order waiting at SF 0.01
    assert min(sizes.values()) >= 1 and max(sizes.values()) >= 4
    assert len(texts) == 1                     # ONE program for 25 bindings
    assert _counter("sql_bind_textual_total") == textual
    assert _counter("sql_bind_params_total") == as_data + 25
    assert col.stages["sql.prepared_hit"].events == 24
    assert "flow.restart" not in col.stages
    assert not [s for s in col.stages if s.startswith(("route.cpu",
                                                       "fused.fallback"))]
    # one bind rewrote two Applies
    assert _counter("sql_apply_decorrelated_total") == rewritten + 2
    assert (col.stages["sql.decorrelate"].events,
            col.stages["sql.decorrelate"].rows) == (1, 2)
    # the subquery joins probe the Shrink's lanes (16,384 at this scale,
    # the floor of a join under a shrunk build) against builds shrunk to
    # the order keys' distinct count (15,000 x 1.25 -> 32,768 lanes, not
    # the aggregates' 131,072): one event a dispatch
    lanes = col.stages["fused.join_residual_lanes"]
    assert (lanes.events, lanes.rows) == (25, 25 * 2 * (16384 + 32768))
    # both reduction aggregates took the int-key sort; the last hashes
    assert col.stages["fused.agg_int_key"].events == 2
    # ... and both joins under a Shrink lowered with it (nation into
    # supplier, supplier into lineitem)
    assert col.stages["fused.join_compact"].events == 2


def test_the_half_width_control_is_not_the_answer(served):
    ref = served["ref"]
    values = ("FRANCE",)
    exact = ref.control_rows(values, None)
    assert ref.check([(values, exact)])[0] == [True]
    rows = ref.control_rows(values, "half_width")
    oks, _compared = ref.check([(values, rows)])
    assert oks == [False]
    with pytest.raises(ValueError):
        ref.control_rows(values, "float32")


def test_the_served_plan_and_its_explain(served):
    sess = Session(served["catalog"], capacity=CAP)
    sess.execute("set vectorize = tpu")
    bound, text = sess.bind_params("explain " + Q21, ("CHINA",))
    _kind, lines, _schema = sess.execute(text, params=bound)
    text = "\n".join(lines)
    # the decorrelated joins with their residual, in the order the plan
    # runs them: above the Shrink of the supplier join
    semi = next(i for i, ln in enumerate(lines) if "semi join on "
                "l_orderkey=__apply0_k0 residual BoolOp(op='or'" in ln)
    anti = next(i for i, ln in enumerate(lines) if "anti join on "
                "l_orderkey=__apply1_k0 residual BoolOp(op='or'" in ln)
    shrink = next(i for i, ln in enumerate(lines)
                  if ln.lstrip(" ->").startswith("shrink"))
    assert anti < semi < shrink
    assert "__apply0_min" in lines[semi] and "__apply0_max" in lines[semi]
    # the reduction aggregates, operators of their own
    assert "aggregate min(l2.l_suppkey) as __apply0_min, " \
           "max(l2.l_suppkey) as __apply0_max group by l2.l_orderkey" in text
    assert "aggregate min(l3.l_suppkey) as __apply1_min, " \
           "max(l3.l_suppkey) as __apply1_max group by l3.l_orderkey" in text
    assert "parameters: $1 string(nation.n_name)" in text \
        or "parameters: $1" in text
    assert all("tier=fused" in ln for ln in lines[:shrink + 1])


def test_explain_analyze_device_names_the_new_operators(served):
    from cockroach_tpu.util import compile_cache

    sess = Session(served["catalog"], capacity=CAP)
    sess.execute("set vectorize = tpu")
    with compile_cache.persistent_cache_disabled():
        sess._prepared = type(sess._prepared)()
        bound, text = sess.bind_params(
            "explain analyze (device) " + Q21, ("PERU",))
        _kind, lines, _schema = sess.execute(text, params=bound)
    ops = [ln for ln in lines if ln.lstrip().startswith("op")]
    joins = [ln for ln in ops if " JoinOp " in ln]
    # named as the plan has them (each runs as the inner / left join that
    # feeds its residual's filter)
    assert len([ln for ln in joins if "JoinOp semi+residual "
                "l_orderkey = __apply0_k0" in ln]) == 1
    assert len([ln for ln in joins if "JoinOp anti+residual "
                "l_orderkey = __apply1_k0" in ln]) == 1
    aggs = [ln for ln in ops if " HashAggOp " in ln]
    assert len([ln for ln in aggs if "group by l2.l_orderkey" in ln]) == 1
    assert len([ln for ln in aggs if "group by l3.l_orderkey" in ln]) == 1
    assert any("fused.join_residual_lanes" in ln for ln in lines)


# --------------------------------------------------------------- Q4, Q21 ---

def test_q4s_specification_text_binds_to_the_hand_built_plan(tpch):
    gen, cat = tpch
    plan, _b = _bind(Q.Q4_SQL, cat)
    (ap,) = [n for n in _walk_plan(plan) if isinstance(n, Apply)]
    assert (ap.kind, ap.correlation, ap.residual) == (
        "exists", (("o_orderkey", "l_orderkey"),), None)
    assert repr(normalize(plan, cat)) == repr(normalize(Q.q4_plan(), cat))
    from cockroach_tpu.sql.plan import run
    res = run(plan, cat, 1 << 13)
    got = dict(zip(res["o_orderpriority"].tolist(),
                   res["order_count"].tolist()))
    assert got == Q.q4_oracle(gen)


def test_q21s_hand_built_plan_and_its_text_answer_as_the_oracle():
    gen = tpch_sname.TPCHSName(sf=0.01, seed=7)
    cat = TPCHCatalog(gen)
    want = Q.q21_oracle(gen)
    assert len(want) >= 3
    res = collect(Q.q21(gen, capacity=1 << 14), fuse=True)
    assert list(zip(res["s_name"].tolist(),
                    res["numwait"].tolist())) == want
    plan, b = _bind(Q.Q21_SQL, cat, (Q.Q21_NATION,))
    from cockroach_tpu.ops.expr import bound_args
    from cockroach_tpu.sql.plan import run
    with bound_args(P_.evaluate(b.param_slots, (Q.Q21_NATION,))):
        res = run(plan, cat, 1 << 14)
    assert list(zip(res["s_name"].tolist(),
                    res["numwait"].tolist())) == want
    assert 21 in Q.QUERIES and 21 in Q.PLANS and len(Q.QUERIES) == 16


def test_the_shrink_stands_below_the_subquery_joins(tpch):
    _gen, cat = tpch
    plan, _b = _bind(Q21, cat, ("FRANCE",))
    node = normalize(plan, cat)
    seen = []
    while not isinstance(node, Shrink):
        if isinstance(node, Join):
            seen.append((node.how, node.residual is not None))
            node = node.left
        else:
            (node,) = node.inputs()
    assert seen == [("anti", True), ("semi", True), ("semi", False)]
    assert isinstance(node.input, Join) and node.input.how == "inner"
    assert node.input.left_on == ("l_suppkey",)


# ----------------------------------------------- the cell's rehearsal ---
# benchmark/ holds its own tests, which tier-1 does not collect: the new
# cell's are collected here by name (as tests/test_benchmark_join_scan64.py
# does), the traced one under the lock every traced rehearsal of tests/
# holds.

from benchmark.test_q21_cell import (  # noqa: E402,F401
    test_the_manifest_holds_the_cell_and_its_metric,
)
from benchmark.test_q21_cell import (  # noqa: E402
    test_traced_rehearsal_is_correct_and_prints_the_new_metric as _rehearsal,
)


def test_the_q21_cell_rehearses_correct_and_its_control_does_not(
        one_traced_rehearsal):
    _rehearsal()
