"""GROUP BY by slot over a ranged integer key (ISSUE 42): a key whose value
range the table statistics hold (a bare integer or date column, or the year
of such a date) joins the dictionary and bool keys in the dense domain of
ops/agg.dense_aggregate, so Q9's aggregate on (nation, year of o_orderdate)
runs with no hash, no sort and no compaction of its input's lanes, and hands
every later operator a batch of D = 26 x 8 = 208 lanes.

Here: the planner's derivation case by case; Q9 at SF 0.01 through Session
and through the wire at the eight patterns of tests/test_q9.py; a nullable
year key's NULL group; statistics gone stale (a row written after ANALYZE
restarts the statement once on the hash aggregate, exact); on four virtual
devices the lane-wise merge of the shards' partials; and the lowerings of
the statements the other cells run, which must stay the parent's.
"""

import hashlib
from decimal import Decimal

import jax
import numpy as np
import pytest

from benchmark import manifest
from benchmark.loaders import tpch as tpch_loader
from benchmark.loaders import tpch_cname, tpch_pname
from benchmark.reference import tpch_q9
from cockroach_tpu.exec import fused, stats
from cockroach_tpu.exec.operators import HashAggOp, SortOp, walk_operators
from cockroach_tpu.ops import agg as agg_mod
from cockroach_tpu.parallel import dist_flow, make_mesh
from cockroach_tpu.sql import params as P_
from cockroach_tpu.sql import parser
from cockroach_tpu.sql.bind import Binder
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.plan import TPCHCatalog, build, normalize
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.settings import Settings
from tests.test_params import Client

CAP = 1 << 17
SEED = 7
Q9_STMT = manifest.cell("tpch-sf1-q9.q9-1stream")["statements"][0]
Q9 = Q9_STMT["sql"]
PATTERNS = ["%green%", "%almond%", "%yellow%", "%hot%", "%navajo%",
            "%nothing%", "%", None]
D = 26 * 8     # 25 nations and 1992..1998, a NULL slot each


def _cell_sql(cell):
    return manifest.cell(cell)["statements"][0]["sql"]


def _counter(name):
    return default_registry().counter(name).value()


def _events(col, name):
    s = col.stages.get(name)
    return s.events if s is not None else 0


def _aggs(op):
    return [o for o in walk_operators(op) if isinstance(o, HashAggOp)]


def _session(catalog, capacity=CAP, *setup):
    s = Session(catalog, capacity=capacity)
    for text in ("set vectorize = tpu",) + setup:
        assert s.execute(text)[0] == "ok"
    s._prepared = type(s._prepared)()   # the entry is made here
    return s


def _bound(sess, sql, values):
    bound, text = sess.bind_params(sql, values)
    assert isinstance(bound, P_.BoundParams) and text == sql
    kind, payload, _schema = sess.execute(text, params=bound)
    assert kind == "rows"
    return payload


def _q9_rows(payload, dicts):
    return [(dicts["n_name"][payload["nation"][i]],
             str(payload["o_year"][i]),
             str(Decimal(int(payload["sum_profit"][i])).scaleb(-4)))
            for i in range(len(payload["o_year"]))]


def _prepared_op(sess):
    (prep,) = [p for p in sess._prepared.values()
               if getattr(p, "op", None) is not None]
    return prep.op


# ----------------------------------------------- the planner's derivation ---

@pytest.fixture(scope="module")
def small():
    """t: 300 rows; k in 0..9, w in 0..299, d over 1992..1998 (NULL every
    50th row), s a string of three values; u is never analyzed."""
    sess = Session(SessionCatalog(MVCCStore()), capacity=1 << 10)
    assert sess.execute("set vectorize = tpu")[0] == "ok"
    sess.execute("create table t (a int primary key, k int, w int, "
                 "d date, m date, s string, v decimal(12,2))")
    sess.execute("create table u (ua int primary key, uk int)")
    rows = []
    for i in range(300):
        d = ("null" if i % 50 == 3 else
             f"date '{1992 + i % 7}-0{1 + i % 9}-1{i % 9}'")
        rows.append(f"({i}, {i % 10}, {i}, {d}, "
                    f"date '1995-03-{1 + i % 28:02d}', "
                    f"'{'xyz'[i % 3]}', {i}.25)")
    sess.execute("insert into t values " + ",".join(rows))
    sess.execute("insert into u values "
                 + ",".join(f"({i}, {i % 4})" for i in range(40)))
    sess.execute("analyze t")
    return sess


def _domains(sess, sql):
    cat = sess.catalog
    plan = normalize(Binder(cat).bind(parser.parse(sql)), cat)
    (agg,) = _aggs(build(plan, cat, 1 << 10))
    return agg.key_domains, agg._dense_sizes


DAY = np.datetime64("1970-01-01")


def _days(text):
    return int((np.datetime64(text) - DAY).astype(np.int64))


@pytest.mark.parametrize("sql,domains,sizes", [
    # a bare integer column: its bounds, and a NULL slot
    ("select k, count(*) from t group by k", {"k": (0, 9)}, [11]),
    # a bare date column
    ("select m, count(*) from t group by m",
     {"m": (_days("1995-03-01"), _days("1995-03-28"))}, [29]),
    # the year of a date: monotone, year(lo)..year(hi); the NULL dates'
    # zeros widen the statistics to 1970, a superset
    ("select extract(year from d) as y, sum(v) from t group by y",
     {"y": (1970, 1998)}, [30]),
    # beside a dictionary key, through a rename and a filter
    ("select s, k as kk, count(*) from t where w > 5 group by s, kk",
     {"kk": (0, 9)}, [4, 11]),
    # a product over DENSE_MAX_GROUPS: none, whatever each key's range
    ("select k, extract(year from d) as y, count(*) from t group by k, y",
     None, None),
    ("select w, count(*) from t group by w", None, None),
    # no statistics: none
    ("select uk, count(*) from u group by uk", None, None),
    # any other expression: none
    ("select k + 1 as k1, count(*) from t group by k1", None, None),
    ("select extract(month from d) as mo, count(*) from t group by mo",
     None, None),
    ("select extract(year from d) + 0 as y, count(*) from t group by y",
     None, None),
])
def test_a_key_gets_a_domain_only_where_the_statistics_prove_it(
        small, sql, domains, sizes):
    assert _domains(small, sql) == (domains, sizes)


def test_the_bound_stays_the_files_and_a_prefix_sample_gives_no_domain():
    assert agg_mod.DENSE_MAX_GROUPS == 256
    from cockroach_tpu.workload.tpch import TPCH

    # TPCHCatalog samples a prefix of each table: its bounds say so, and
    # l_linenumber (1..7 in every prefix) still gets no domain
    cat = TPCHCatalog(TPCH(sf=0.01))
    assert cat.table_stats("lineitem").exact_bounds is False
    sql = "select l_linenumber, count(*) from lineitem group by l_linenumber"
    plan = normalize(Binder(cat).bind(parser.parse(sql)), cat)
    (agg,) = _aggs(build(plan, cat, 1 << 14))
    assert agg.key_domains is None and agg._dense_sizes is None


def test_a_nullable_year_key_keeps_its_null_group(small):
    sql = ("select extract(year from d) as y, count(*) as n, sum(v) as sv "
           "from t group by y order by y")
    col = stats.enable()
    try:
        kind, got, _schema = small.execute(sql)
    finally:
        stats.disable()
    assert kind == "rows" and _events(col, "fused.agg_dense") >= 1
    want = {}
    for i in range(300):
        y = None if i % 50 == 3 else 1992 + i % 7
        n, sv = want.get(y, (0, 0))
        want[y] = (n + 1, sv + i * 100 + 25)
    rows = {(int(y) if ok else None): (int(n), int(sv))
            for y, ok, n, sv in zip(got["y"], got["y__valid"], got["n"],
                                    got["sv"])}
    assert rows == want and None in rows and len(got["y"]) == 8


# ------------------------------------------- statistics that went stale ---

def test_a_row_written_after_analyze_restarts_once_on_the_hash_aggregate():
    sess = Session(SessionCatalog(MVCCStore()), capacity=1 << 10)
    assert sess.execute("set vectorize = tpu")[0] == "ok"
    sess.execute("create table o (ok int primary key, od date, even bool)")
    sess.execute("create table l (lk int primary key, lok int, "
                 "v decimal(12,2))")
    sess.execute("insert into o values " + ",".join(
        f"({i}, date '{1992 + i % 7}-0{1 + i % 9}-1{i % 9}', "
        f"{'false' if i % 2 else 'true'})" for i in range(200)))
    sess.execute("insert into l values " + ",".join(
        f"({i}, {i % 200}, {i}.25)" for i in range(600)))
    sess.execute("analyze o")
    sess.execute("analyze l")
    sql = ("select extract(year from od) as y, even, count(*) as n, "
           "sum(v) as sv from l, o where lok = ok group by y, even "
           "order by y, even")

    def run():
        col = stats.enable()
        try:
            kind, got, _schema = sess.execute(sql)
        finally:
            stats.disable()
        assert kind == "rows"
        return col, {(int(y), bool(e)): (int(n), int(sv)) for y, e, n, sv
                     in zip(got["y"], got["even"], got["n"], got["sv"])}

    want = {}
    for i in range(600):
        key = (1992 + (i % 200) % 7, (i % 200) % 2 == 0)
        n, sv = want.get(key, (0, 0))
        want[key] = (n + 1, sv + i * 100 + 25)
    restarts = _counter("sql_flow_restarts_total")
    col, rows = run()
    assert rows == want and _events(col, "fused.agg_dense") >= 1
    assert _counter("sql_flow_restarts_total") == restarts
    (line,) = [ln for ln in sess.execute("explain " + sql)[1]
               if ln.startswith("aggregate by slot")]
    assert line == ("aggregate by slot: group by y, even in 24 slots "
                    "(y in [1992, 1998] by the statistics)")

    # an order dated 1999, after ANALYZE: outside [1992, 1998]
    sess.execute("insert into o values (1000, date '1999-02-03', true)")
    sess.execute("insert into l values (5000, 1000, 7.50)")
    want[(1999, True)] = (1, 750)
    col, rows = run()
    assert rows == want       # never dropped, never clamped
    assert _counter("sql_flow_restarts_total") == restarts + 1
    # the flag rose on the dense lowering; the restart hashed
    assert _events(col, "fused.agg_dense") >= 1
    assert _events(col, "fused.agg_materialized") >= 1
    # what the restart widened stays widened: no second restart
    col, rows = run()
    assert rows == want
    assert _counter("sql_flow_restarts_total") == restarts + 1
    assert _events(col, "fused.agg_dense") == 0


# ------------------------------------------------- Q9, one chip's program ---

@pytest.fixture(scope="module")
def q9():
    loaded = tpch_cname.load_from(
        tpch_pname.TPCHPName(sf=0.01, seed=SEED), MVCCStore(),
        Q9_STMT["tables"])
    loaded["pg"] = PgServer(loaded["catalog"], capacity=CAP).start()
    loaded["ref"] = tpch_q9.Reference(loaded["data"], loaded["dicts"], {})
    yield loaded
    loaded["pg"].close()


@pytest.mark.parametrize("pattern", PATTERNS)
def test_q9_aggregates_by_slot_and_is_exact(q9, pattern):
    ref, values = q9["ref"], (pattern,)
    sess = _session(q9["catalog"])
    restarts = _counter("sql_flow_restarts_total")
    col = stats.enable()
    try:
        payload = _bound(sess, Q9, values)
    finally:
        stats.disable()
    # the mechanism engaged, once a traced aggregate, and nothing hashed
    traced = _events(col, "fused.compile")
    assert traced >= 1
    assert _events(col, "fused.agg_dense") == traced
    assert _events(col, "fused.agg_materialized") == 0
    assert _counter("sql_flow_restarts_total") == restarts
    op = _prepared_op(sess)
    (agg,) = _aggs(op)
    assert agg._dense_sizes == [26, 8]
    assert agg.key_domains == {"o_year": (1992, 1998)}
    # SortOp is handed the D lanes, and so is the result's packing
    assert isinstance(op, SortOp)
    (prog,) = [p for p in op._fused_runner._progs.values()]
    assert prog[2] == D
    client = Client(q9["pg"].addr, timeout=300.0)
    try:
        assert client.query("set vectorize = tpu") == ([], None)
        rows, code = client.bound(Q9, values)
    finally:
        client.close()
    assert code is None
    for answer in (_q9_rows(payload, q9["dicts"]), [tuple(r) for r in rows]):
        oks, compared = ref.check([(values, answer)])
        assert oks == [True], (values, compared, answer[:3])


def test_sort_runs_at_the_domains_lanes(q9, monkeypatch):
    """The ORDER BY's sort sees a batch of D lanes, not the Shrink's."""
    seen = []
    real = fused.sort_batch

    def spy(batch, *a, **kw):
        seen.append(batch.capacity)
        return real(batch, *a, **kw)

    monkeypatch.setattr(fused, "sort_batch", spy)
    sess = _session(q9["catalog"])
    _bound(sess, Q9, ("%green%",))
    assert seen and set(seen) == {D}


# ------------------------------------------------------- Q9 on the mesh ---

MESH_CAP = 2048
N_DEV = 4
needs_mesh = pytest.mark.skipif(len(jax.devices()) < N_DEV,
                                reason="needs four virtual CPU devices")


@pytest.fixture(scope="module")
def mesh9():
    loaded = tpch_pname.load(MVCCStore(), {"sf": 0.01},
                             Q9_STMT["tables"] + ["customer"], 2147483999)
    loaded["mesh"] = make_mesh(N_DEV)
    return loaded


@pytest.fixture
def mesh_catalog(mesh9):
    """SF1's layout at SF 0.01 (tests/test_session_distsql.py's catalog9):
    partsupp and orders routed, part and supplier MIRROR."""
    s = Settings()
    old = s.get(dist_flow.BROADCAST_LIMIT)
    s.set(dist_flow.BROADCAST_LIMIT, 2 * MESH_CAP)
    cat = mesh9["catalog"].with_mesh(mesh9["mesh"])
    dist_flow.progs_clear()
    try:
        yield cat
    finally:
        cat.with_mesh(None)
        s.set(dist_flow.BROADCAST_LIMIT, old)
        stats.disable()


@pytest.fixture
def merges(monkeypatch):
    """What the mesh's aggregate merge was handed: ('hash', lanes) for a
    second hash_aggregate over the gathered partials, ('dense', lanes)
    for every lane-wise merge of two partials."""
    seen = []
    real_hash, real_dense = dist_flow.hash_aggregate, dist_flow.dense_merge

    def spy_hash(batch, *a, **kw):
        seen.append(("hash", batch.capacity))
        return real_hash(batch, *a, **kw)

    def spy_dense(a, b, *rest, **kw):
        seen.append(("dense", a.capacity))
        return real_dense(a, b, *rest, **kw)

    monkeypatch.setattr(dist_flow, "hash_aggregate", spy_hash)
    monkeypatch.setattr(dist_flow, "dense_merge", spy_dense)
    return seen


@needs_mesh
def test_the_mesh_merges_q9s_partials_lane_by_lane(mesh9, mesh_catalog,
                                                   merges):
    ref = tpch_q9.Reference(mesh9["data"], mesh9["dicts"], {})
    values = ("%green%",)
    sess = _session(mesh_catalog, MESH_CAP, "set distsql = always")
    col = stats.enable()
    try:
        payload = _bound(sess, Q9, values)
    finally:
        stats.disable()
    assert _events(col, "dist.exec") == 1
    assert _events(col, "fused.agg_dense") == _events(col, "dist.compile")
    # every shard's partial is the D lanes, merged pair by pair: no
    # hash_aggregate over gathered lanes, so no collision flag; the one
    # flag the aggregate owns is its range's
    assert merges == [("dense", D)] * (N_DEV - 1)
    (prog,) = dist_flow._PROGS.values()
    assert prog.flag_types.count("HashAggOp") == 1
    assert prog.result_cap == D
    rows = _q9_rows(payload, mesh9["dicts"])
    oks, compared = ref.check([(values, rows)])
    assert oks == [True], compared
    # and the one-device run's rows
    mesh_catalog.with_mesh(None)
    one = _session(mesh_catalog, MESH_CAP)
    assert _q9_rows(_bound(one, Q9, values), mesh9["dicts"]) == rows


@needs_mesh
def test_a_key_without_a_domain_still_merges_by_hash(mesh9, mesh_catalog,
                                                     merges):
    sess = _session(mesh_catalog, MESH_CAP, "set distsql = always")
    kind, got, _schema = sess.execute(_cell_sql("tpch-sf1.q3-1stream"))
    assert kind == "rows" and len(got["l_orderkey"]) == 10
    (agg,) = _aggs(_prepared_op(sess))
    assert agg._dense_sizes is None and agg.key_domains is None
    assert [kind for kind, _lanes in merges] == ["hash"]
    (prog,) = dist_flow._PROGS.values()
    # the local hash aggregate's collision flag and the merge's
    assert prog.flag_types.count("HashAggOp") == 2
    mesh_catalog.with_mesh(None)
    one = _session(mesh_catalog, MESH_CAP)
    want = one.execute(_cell_sql("tpch-sf1.q3-1stream"))[1]
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


# ------------------------------------ the other cells' programs stay put ---

# statement -> (loader, tables' statement, binding, lowering events a traced
# program, each aggregate's _dense_sizes): the six statements the one-chip
# cells run. The digests of the lowered texts, parent beside change, are in
# CHANGES.md (PR 44; PR 42 for the five it had); `python -m
# tests.test_agg_by_slot` prints them.
OTHERS = {
    "q1": ("tpch", "tpch-sf1.q1-2streams", None,
           {"fused.agg_dense": 1}, [[4, 3]]),
    "q3": ("tpch", "tpch-sf1.q3-1stream", None,
           {"fused.agg_ordered": 1, "fused.join_compact": 2,
            "fused.join_key_int": 2}, [None]),
    "q3_qgen": ("tpch", "tpch-sf1-qgen.q3-1stream",
                ("BUILDING", "1995-03-15"),
                {"fused.agg_ordered": 1, "fused.join_compact": 2,
                 "fused.join_key_int": 2}, [None]),
    "q6_qgen": ("tpch", "tpch-sf1-qgen.q6-2streams",
                ("1994-01-01", "0.06", "24"),
                {"fused.agg_materialized": 1}, [None]),
    "q18_qgen": ("tpch_cname", "tpch-sf1-q18.q18-1stream", ("300",),
                 {"fused.agg_int_key": 1, "fused.agg_ordered": 1,
                  "fused.join_compact": 2, "fused.join_key_int": 3},
                 [None, None]),
    "q9_qgen": ("tpch_pname", "tpch-sf1-q9.q9-1stream", ("%green%",),
                {"fused.agg_dense": 1, "fused.join_compact": 1,
                 "fused.join_key_int": 4, "fused.join_key_hash": 1},
                [[26, 8]]),
}
LOWERING_EVENTS = (
    "fused.agg_dense", "fused.agg_materialized", "fused.agg_folded",
    "fused.agg_int_key", "fused.agg_ordered", "fused.join_compact",
    "fused.join_key_int", "fused.join_key_hash")


def lowered(name, monkeypatch=None):
    """-> (sha256 of each program exec/fused lowers for statement `name`
    at SF 0.01, the aggregate and join lowerings a traced program counted,
    each aggregate's _dense_sizes)."""
    loader, cell, binding = OTHERS[name][:3]
    stmt = manifest.cell(cell)["statements"][0]
    module = {"tpch": tpch_loader, "tpch_cname": tpch_cname,
              "tpch_pname": tpch_pname}[loader]
    loaded = module.load(MVCCStore(), {"sf": 0.01}, stmt["tables"], SEED)
    texts, lower = [], fused.lower_program

    def recording(fn, args):
        low = lower(fn, args)
        texts.append(low.as_text())
        return low

    fused.lower_program = recording
    col = stats.enable()
    try:
        sess = _session(loaded["catalog"])
        if binding is None:
            assert sess.execute(stmt["sql"])[0] == "rows"
        else:
            _bound(sess, stmt["sql"], binding)
    finally:
        stats.disable()
        fused.lower_program = lower
    traced = _events(col, "fused.compile")
    counted = {e: _events(col, e) // traced for e in LOWERING_EVENTS
               if _events(col, e)}
    return ([hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts],
            counted, [a._dense_sizes for a in _aggs(_prepared_op(sess))])


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_cells_statements_lower_as_at_the_parent(name):
    _loader, _cell, _binding, events, sizes = OTHERS[name]
    digests, counted, dense = lowered(name)
    assert digests and counted == events and dense == sizes


if __name__ == "__main__":      # the digests, for CHANGES.md
    for which in sorted(OTHERS):
        print(which, *lowered(which))
