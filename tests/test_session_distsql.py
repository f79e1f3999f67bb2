"""DistSQL on the served path (ISSUE 27): `SET distsql = off | on |
always` in `Session` and over pgwire, the node's mesh on its catalog
(`Catalog.mesh`), the distributed statement's span tree and counters in
the stage seam, and the one-device mesh the benchmark's rehearsal runs.

A four-device mesh of the suite's eight virtual CPU devices; TPC-H SF 0.01
from the benchmark's loader, judged by the benchmark's plain references
(benchmark/reference/tpch_q3.py, tpch_q1.py) and by the same session with
`distsql = off`. The broadcast limit is lowered (as tests/test_dist_flow.py
does) so that Q3's big join takes the BY_HASH exchange.
"""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from benchmark import manifest
from benchmark.loaders import tpch as tpch_loader
from benchmark.reference import tpch_q1, tpch_q3
from benchmark.wire import WireClient
from cockroach_tpu.exec import stats
from cockroach_tpu.exec.operators import JoinOp, walk_operators
from cockroach_tpu.parallel import dist_flow, make_mesh
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.session import Session, SessionCatalog, SQLError
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util import tracing
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.settings import Settings

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four virtual CPU devices")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483999
CAP = 4096
N_DEV = 4
Q3 = manifest.cell("tpch-sf1.q3-1stream")["statements"][0]["sql"]
Q1 = manifest.cell("tpch-sf1.q1-2streams")["statements"][0]["sql"]
# what one device sends through Q3's exchanges in one dispatch at this
# scale, by hand. From lanes (ISSUE 27): lineitem's 15 chunks shard 4 to a
# device (16,384 lanes), so the probe's bucket is pow2(16384 // 4 * 2) =
# 8192 rows; the build (orders' 4 chunks + customer's 1 = 20,480 rows,
# 5,120 a device) gets pow2(5120 // 4 * 2) = 4096. From the planner's
# estimates (ISSUE 30), which the loader's statistics give and which are
# what runs: 32,278 lineitem rows pass the date, 8,069 a shard, so
# pow2(8069 // 4 * 2) = 4096; 1,458 orders reach the join, 364 a shard, so
# pow2(364 // 4 * 2) = 256. Three of a device's four buckets leave it.
# A probe row: l_orderkey, l_extendedprice, l_discount (8 bytes each),
# l_shipdate (4) and the selection lane (1); a build row: o_orderkey,
# o_custkey, o_shippriority (8 each), o_orderdate (4), selection (1).
Q3_EST = dist_flow._Exchange(probe=2048, build=4096,   # a probe CHUNK's
                             probe_est=4096, build_est=256)
Q3_A2A_BYTES = (N_DEV - 1) * (4096 * (8 + 8 + 8 + 4 + 1)
                              + 256 * (8 + 8 + 8 + 4 + 1))
Q3_A2A_BYTES_LANES = (N_DEV - 1) * (8192 * (8 + 8 + 8 + 4 + 1)
                                    + 4096 * (8 + 8 + 8 + 4 + 1))
Q3_BY_HASH = ("  inner join on l_orderkey=o_orderkey: BY_HASH (all_to_all "
              "of both sides; buckets of 4096 probe (estimated 32278 rows) "
              "and 256 build (estimated 1458 rows) rows a shard)")

WARM_TREE = {
    "wire.statement": ["wire.decode", "session.execute", "wire.render",
                       "wire.encode", "wire.flush"],
    "session.execute": ["session.admit", "sql.lookup", "flow.dist",
                        "session.account"],
    "flow.dist": ["dist.prepare", "dist.exec", "dist.readback",
                  "dist.unpack"],
    "dist.exec": ["dist.dispatch", "dist.wait"],
}


@pytest.fixture(scope="module")
def tpch():
    store = MVCCStore()
    loaded = tpch_loader.load(store, {"sf": 0.01},
                              ["lineitem", "orders", "customer"], SEED)
    loaded["mesh"] = make_mesh(N_DEV)
    return loaded


@pytest.fixture
def catalog(tpch):
    """The loaded catalog with a four-device mesh, the broadcast limit
    lowered to one chunk: orders + customer (5 chunks) go BY_HASH,
    customer alone (1 chunk) stays MIRROR."""
    s = Settings()
    old = s.get(dist_flow.BROADCAST_LIMIT)
    s.set(dist_flow.BROADCAST_LIMIT, CAP)
    cat = tpch["catalog"].with_mesh(tpch["mesh"])
    try:
        yield cat
    finally:
        cat.with_mesh(None)
        s.set(dist_flow.BROADCAST_LIMIT, old)
        stats.disable()


def _session(cat, *setup):
    sess = Session(cat, capacity=CAP)
    for text in setup:
        assert sess.execute(text)[0] == "ok"
    return sess


def _run(sess, sql):
    """-> (payload, root span) of one statement under a root of its own."""
    with tracing.tracer().span("test.root") as root:
        kind, payload, _schema = sess.execute(sql)
    assert kind == "rows"
    return payload, root


def _same(a, b):
    assert set(a) == set(b)
    for name in a:
        np.testing.assert_array_equal(a[name], b[name], err_msg=name)


def _events(col, name):
    s = col.stages.get(name)
    return s.events if s is not None else 0


def _dist_stages(col):
    return sorted(n for n in col.stages if n.startswith("dist."))


# ---------------------------------------------- rows, through Session ----

def test_q3_always_matches_the_reference_and_distsql_off(tpch, catalog):
    col = stats.enable()
    dist, root = _run(_session(catalog, "set distsql = always"), Q3)
    assert root.tags["tier"] == "dist"
    want = tpch_q3.Reference(tpch["data"], tpch["dicts"], {}).answer()
    got = list(zip(dist["l_orderkey"].tolist(), dist["revenue"].tolist(),
                   dist["o_orderdate"].tolist(),
                   dist["o_shippriority"].tolist()))
    assert got == want and len(got) == 10
    # the BY_HASH path was taken, and the exchange is what the shapes say
    (prog,) = dist_flow._PROGS.values()
    assert prog.a2a_bytes == Q3_A2A_BYTES
    assert col.stages["dist.a2a"].events == 1
    assert col.stages["dist.a2a"].bytes == Q3_A2A_BYTES
    assert "all-to-all" in prog.compiled.as_text()
    off, root = _run(_session(catalog, "set vectorize = tpu"), Q3)
    assert root.tags["tier"] == "fused"
    _same(dist, off)


def test_q3s_big_join_is_repartitioned_and_the_semi_join_is_local(catalog):
    sess = _session(catalog, "set distsql = always")
    sess.execute(Q3)
    (prep,) = sess._prepared.values()
    assert prep.dist and prep.bspec is None
    runner = dist_flow.DistFusedRunner(prep.op, catalog.mesh)
    _scans, _sources, chunks = runner._prime()
    sharded, repart = runner._classify(chunks)
    joins = [op for op in walk_operators(prep.op) if isinstance(op, JoinOp)]
    assert [(j.how, id(j) in repart) for j in joins] == [
        ("inner", True), ("semi", False)]
    assert repart[id(joins[0])] == Q3_EST
    assert len(sharded) == 2                       # lineitem and orders
    images = {img.role: img for img in dist_flow.ingest._CACHE.values()}
    li = images[dist_flow.ingest.SHARDED]
    assert len({s.device for s in li.bufs.addressable_shards}) == N_DEV


def test_q1_always_matches_the_reference_and_distsql_off(tpch, catalog):
    col = stats.enable()
    dist, root = _run(_session(catalog, "set distsql = always"), Q1)
    assert root.tags["tier"] == "dist"
    assert col.stages["dist.a2a"].bytes == 0      # no join: no exchange
    want = tpch_q1.Reference(tpch["data"], tpch["dicts"], {}).answer()
    rf, ls = tpch["dicts"]["l_returnflag"], tpch["dicts"]["l_linestatus"]
    got = {(rf[int(a)], ls[int(b)]): (int(q), int(p), int(d), int(c), int(n))
           for a, b, q, p, d, c, n in zip(
               dist["l_returnflag"], dist["l_linestatus"], dist["sum_qty"],
               dist["sum_base_price"], dist["sum_disc_price"],
               dist["sum_charge"], dist["count_order"])}
    assert got == {k: (w[0], w[1], w[2], w[3], w[7])
                   for k, w in want.items()}
    off, root = _run(_session(catalog, "set vectorize = tpu"), Q1)
    assert root.tags["tier"] == "fused"
    _same(dist, off)


# ------------------------------------------------ rows, over the wire ----

@pytest.fixture
def served(catalog, monkeypatch):
    """PgServer(catalog, capacity=): the only call the benchmark's harness
    makes; a stdlib client; the roots of the statements it served."""
    roots = []
    finish = tracing.Tracer.finish_statement

    def keep(self, root):
        finish(self, root)
        roots.append(root)

    monkeypatch.setattr(tracing.Tracer, "finish_statement", keep)
    pg = PgServer(catalog, capacity=CAP).start()
    client = WireClient(pg.addr, timeout=300.0)
    try:
        yield client, roots
    finally:
        client.close()
        pg.close()


def _ask(client, roots, sql):
    """-> (rows, the statement's root once the server has finished it)."""
    n = len(roots)
    rows, code = client.query(sql)
    assert code is None, code
    deadline = time.monotonic() + 30.0
    while len(roots) == n and time.monotonic() < deadline:
        time.sleep(0.001)   # a root ends after its answer is flushed
    assert len(roots) == n + 1
    return rows, roots[-1]


@pytest.mark.parametrize("sql,module", [(Q3, tpch_q3), (Q1, tpch_q1)],
                         ids=["q3", "q1"])
def test_served_text_over_pgwire_matches_the_reference(tpch, served, sql,
                                                       module):
    client, roots = served
    ref = module.Reference(tpch["data"], tpch["dicts"], {})
    _ask(client, roots, "set vectorize = tpu")
    off, root = _ask(client, roots, sql)
    assert root.tags["tier"] == "fused"
    _ask(client, roots, "set distsql = always")
    for _ in range(2):      # the cold path, then the prepared hit
        rows, root = _ask(client, roots, sql)
        assert root.tags["tier"] == "dist"
        oks, compared = ref.check([((), [tuple(r) for r in rows])])
        assert oks == [True], compared
        assert rows == off
    # and the control still bites on what the wire carried
    oks, _ = ref.check([((), ref.control_rows((), "float32"))])
    assert oks == [False]


# ------------------------------------------------------ the warm path ----

def test_the_second_execution_is_a_prepared_hit_of_one_dispatch(catalog):
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    sess = _session(catalog, "set distsql = always")
    col = stats.enable()
    first, _ = _run(sess, Q3)
    assert _events(col, "dist.compile") == 1
    assert _events(col, "dist.prime") == 3 == _events(col, "dist.ingest")
    assert col.stages["dist.ingest"].bytes == sum(
        img.nbytes for img in dist_flow.ingest._CACHE.values())
    n_compiles = len(compiles)
    col = stats.enable()
    again, root = _run(sess, Q3)
    _same(first, again)
    assert root.tags["tier"] == "dist"
    assert len(compiles) == n_compiles
    assert _events(col, "sql.prepared_hit") == 1
    assert _events(col, "dist.prime_skipped") == 1
    assert _events(col, "dist.exec") == 1 == _events(col, "dist.dispatch")
    for stage in ("dist.compile", "dist.prime", "dist.ingest", "sql.parse",
                  "sql.plan"):
        assert _events(col, stage) == 0, stage


def test_the_registry_counts_statements_and_exchanged_bytes(catalog):
    reg = default_registry()
    done = reg.counter("sql_distsql_queries_total")
    moved = reg.counter("sql_distsql_exchange_bytes_total")
    sess = _session(catalog, "set distsql = always")
    d0, m0 = done.value(), moved.value()
    for _ in range(3):
        sess.execute(Q3)
    assert done.value() == d0 + 3
    assert moved.value() == m0 + 3 * Q3_A2A_BYTES
    _session(catalog, "set vectorize = tpu").execute(Q3)
    assert done.value() == d0 + 3       # a single-chip statement: neither
    assert moved.value() == m0 + 3 * Q3_A2A_BYTES
    assert "sql_distsql_queries_total" in reg.export_prometheus()


# ------------------------------------------------ the variable's modes ----

def test_distsql_is_off_by_default_and_takes_three_values(catalog):
    sess = _session(catalog)
    assert sess.execute("show distsql")[1]["distsql"].tolist() == ["off"]
    for mode in ("on", "always", "off"):
        sess.execute(f"set distsql = {mode}")
        assert sess.execute("show distsql")[1]["distsql"].tolist() == [mode]
    with pytest.raises(SQLError) as e:
        sess.execute("set distsql = sometimes")
    assert e.value.pgcode == "22023"
    assert sess.vars["distsql"] == "off"


def test_distsql_off_counts_no_dist_stage(catalog):
    """A node with a mesh, a default session: the single-chip ladder,
    statement for statement what it ran before there was a variable."""
    sess = _session(catalog, "set vectorize = tpu")
    col = stats.enable()
    for _ in range(2):
        _payload, root = _run(sess, Q3)
        assert root.tags["tier"] == "fused"
    assert _dist_stages(col) == []
    assert _events(col, "fused.exec") == 2
    assert not dist_flow._PROGS and not dist_flow.ingest._CACHE


def test_always_without_a_mesh_is_an_error_and_on_runs_single_chip(tpch):
    cat = tpch["catalog"].with_mesh(None)
    sess = _session(cat, "set vectorize = tpu", "set distsql = always")
    with pytest.raises(SQLError) as e:
        sess.execute(Q3)
    assert e.value.pgcode == "0A000" and "no device mesh" in str(e.value)
    with pytest.raises(SQLError):
        sess.execute("explain " + Q3)
    assert sess.execute("set distsql = on")[0] == "ok"   # SET still runs
    col = stats.enable()
    try:
        on, root = _run(sess, Q3)
        assert root.tags["tier"] == "fused" and _dist_stages(col) == []
        _same(on, _run(sess, Q3)[0])
        assert _events(col, "sql.prepared_hit") == 1
    finally:
        stats.disable()


def test_always_over_pgwire_without_a_mesh_carries_the_sqlstate(tpch):
    pg = PgServer(tpch["catalog"].with_mesh(None), capacity=CAP).start()
    client = WireClient(pg.addr, timeout=60.0)
    try:
        assert client.query("set distsql = always") == ([], None)
        rows, code = client.query(Q3)
        assert (rows, code) == ([], "0A000")
        assert client.query("set distsql = off") == ([], None)
        assert client.query("set distsql = 7")[1] == "22023"
    finally:
        client.close()
        pg.close()


def test_a_plan_outside_the_grammar_errors_under_always_only(catalog):
    """With the limit under one chunk the semi join's build would be
    repartitioned inside the big join's build, which the distributed
    runner declines: `always` says so, `on` runs the single-chip ladder
    and counts the fallback."""
    Settings().set(dist_flow.BROADCAST_LIMIT, CAP // 4)
    sess = _session(catalog, "set vectorize = tpu", "set distsql = always")
    with pytest.raises(Exception) as e:
        sess.execute(Q3)
    assert getattr(e.value, "pgcode", None) == "0A000"
    assert "nested inside a build" in str(e.value)
    lines = sess.execute("explain " + Q3)[1]
    assert any(ln.startswith("distribution: local") for ln in lines)
    sess.execute("set distsql = on")
    col = stats.enable()
    on, root = _run(sess, Q3)
    assert root.tags["tier"] == "fused"
    assert _events(col, "dist.fallback_unsupported") == 1
    assert _events(col, "dist.exec") == 0
    _same(on, _run(_session(catalog, "set vectorize = tpu"), Q3)[0])


def test_a_prepared_entry_does_not_survive_a_change_of_distsql(catalog):
    sess = _session(catalog, "set vectorize = tpu", "set distsql = on")
    col = stats.enable()
    tiers = []
    for text in (Q3, Q3, "set distsql = off", Q3, Q3,
                 "set distsql = always", Q3, Q3):
        if text is Q3:
            tiers.append(_run(sess, Q3)[1].tags["tier"])
        else:
            sess.execute(text)
            assert not sess._prepared       # SET drops every entry
    assert tiers == ["dist", "dist", "fused", "fused", "dist", "dist"]
    # one hit in each pair, none across a SET
    assert _events(col, "sql.prepared_hit") == 3
    assert _events(col, "sql.parse") == 3 + 2


@pytest.fixture
def shared(tpch):
    """A SessionCatalog (its sessions share one prepared cache) with a
    mesh, over a small table of its own."""
    cat = SessionCatalog(MVCCStore(), mesh=tpch["mesh"])
    sess = Session(cat, capacity=1 << 10)
    sess.execute("create table kv (k int primary key, g int, v int)")
    sess.execute("insert into kv values " + ", ".join(
        f"({i}, {i % 7}, {i * 3})" for i in range(500)))
    try:
        yield cat
    finally:
        stats.disable()


GROUPED = "select g, sum(v) sv, count(*) n from kv group by g order by g"


def test_sessions_of_one_catalog_never_share_across_distsql(shared):
    a = Session(shared, capacity=1 << 10)
    b = Session(shared, capacity=1 << 10)
    b.execute("set vectorize = tpu")       # clears the shared cache: first
    a.execute("set distsql = on")
    assert a._prepared is b._prepared
    col = stats.enable()
    got_a, root = _run(a, GROUPED)
    assert root.tags["tier"] == "dist"
    assert a._prepared[GROUPED].dist
    got_b, root = _run(b, GROUPED)          # a's entry is not b's kind
    assert root.tags["tier"] == "fused"
    assert _events(col, "sql.prepared_hit") == 0
    assert not b._prepared[GROUPED].dist
    _same(got_a, got_b)
    assert got_a["sv"].tolist() == [
        sum(i * 3 for i in range(500) if i % 7 == g) for g in range(7)]
    assert _run(b, GROUPED)[1].tags["tier"] == "fused"
    assert _events(col, "sql.prepared_hit") == 1
    assert _run(a, GROUPED)[1].tags["tier"] == "dist"
    assert _events(col, "sql.prepared_hit") == 1


def test_a_select_in_an_open_transaction_stays_on_the_gateway(shared):
    sess = Session(shared, capacity=1 << 10)
    sess.execute("set distsql = on")
    sess.execute("begin")
    sess.execute("insert into kv values (1000, 0, 5)")
    col = stats.enable()
    got, root = _run(sess, GROUPED)         # reads its own write
    assert root.tags.get("tier") != "dist" and _dist_stages(col) == []
    assert got["n"].tolist()[0] == 72 + 1
    sess.execute("rollback")
    sess.execute("set distsql = always")
    sess.execute("begin")
    with pytest.raises(SQLError) as e:
        sess.execute(GROUPED)
    assert e.value.pgcode == "0A000" and "transaction" in str(e.value)
    sess.execute("rollback")
    assert _run(sess, GROUPED)[1].tags["tier"] == "dist"


def test_a_one_device_mesh_runs_the_same_code(tpch):
    """What the benchmark's CPU rehearsal runs (`chips: 1`): tier `dist`,
    every stage of the distributed runner, the exchange degenerate."""
    cat = tpch["catalog"].with_mesh(make_mesh(1))
    try:
        col = stats.enable()
        sess = _session(cat, "set distsql = always")
        one, root = _run(sess, Q3)
        assert root.tags["tier"] == "dist"
        assert col.stages["dist.a2a"].events == 1
        assert col.stages["dist.a2a"].bytes == 0
        _same(one, _run(sess, Q3)[0])
        assert _events(col, "dist.prime_skipped") == 1
        _same(one, _run(_session(cat, "set vectorize = tpu"), Q3)[0])
    finally:
        cat.with_mesh(None)
        stats.disable()


# ------------------------------------------------------------- EXPLAIN ----

def test_explain_shows_shards_routers_and_placements(catalog):
    sess = _session(catalog, "set distsql = on")
    kind, lines, _ = sess.execute("explain " + Q3)
    assert kind == "explain"
    at = lines.index("distribution: full (4 shards, mesh axis 'x')")
    assert lines[at + 1:] == [
        Q3_BY_HASH,
        "  scan lineitem: sharded (15 chunks of 4096 rows)",
        "  semi join on o_custkey=c_custkey: MIRROR (build of 4096 rows "
        "replicated, local join)",
        "  scan orders: sharded (4 chunks of 4096 rows)",
        "  scan customer: replicated (1 chunks of 4096 rows)"]
    assert not dist_flow.ingest._CACHE      # an EXPLAIN moves nothing
    sess.execute("set distsql = off")
    assert not any(ln.startswith("distribution:")
                   for ln in sess.execute("explain " + Q3)[1])


def test_explain_under_always_prints_the_estimated_buckets(catalog):
    sess = _session(catalog, "set distsql = always")
    lines = sess.execute("explain " + Q3)[1]
    assert Q3_BY_HASH in lines
    assert not dist_flow._PROGS and not dist_flow.ingest._CACHE


# ---------------------------- bound parameters on the mesh (ISSUE 31) ----

Q3_PARAMS = manifest.cell("tpch-sf1-qgen.q3-1stream")["statements"][0]["sql"]


def test_bound_parameters_are_never_baked_into_a_mesh_program(tpch, catalog):
    """The shard_map program has no argument for a statement's bound
    values, so a parameterised statement is outside the distributed
    grammar: under `on` the single-chip ladder answers with the values as
    arguments of its ONE fused program (no distributed program is traced,
    nothing is primed on the mesh), under `always` it is 0A000."""
    from benchmark.reference import tpch_q3_qgen

    sess = _session(catalog, "set vectorize = tpu", "set distsql = on")
    ref = tpch_q3_qgen.Reference(tpch["data"], tpch["dicts"], {})
    col = stats.enable()
    for n, values in enumerate((("BUILDING", "1995-03-15"),
                                ("MACHINERY", "1995-03-01"),
                                ("HOUSEHOLD", "1995-03-31")), 1):
        bound, text = sess.bind_params(Q3_PARAMS, values)
        assert bound is not None and text == Q3_PARAMS
        with tracing.tracer().span("test.root") as root:
            _kind, got, _schema = sess.execute(text, params=bound)
        assert root.tags["tier"] == "fused"
        assert list(zip(got["l_orderkey"].tolist(), got["revenue"].tolist(),
                        got["o_orderdate"].tolist(),
                        got["o_shippriority"].tolist())) == ref.answer(values)
        assert _events(col, "dist.fallback_unsupported") == n
    assert _dist_stages(col) == ["dist.fallback_unsupported"]
    assert not dist_flow._PROGS and not dist_flow.ingest._CACHE
    (prep,) = sess._prepared.values()          # one entry for all bindings
    assert len(prep.slots) == 2 and prep.dist
    runner = prep.op._fused_runner
    assert runner._takes_params and len(runner._exec_cache) == 1
    lines = sess.execute("explain " + Q3_PARAMS,
                         params=sess.bind_params("explain " + Q3_PARAMS,
                                                 values)[0])[1]
    assert ("distribution: local (outside the distributed grammar: bound "
            "parameters are not arguments of the distributed program)"
            in lines)
    assert "parameters: $1 string(code), $2 date" in lines
    sess.execute("set distsql = always")
    bound, text = sess.bind_params(Q3_PARAMS, values)
    with pytest.raises(Exception) as e:
        sess.execute(text, params=bound)
    assert getattr(e.value, "pgcode", None) == "0A000"
    assert "bound parameters" in str(e.value)


# ---------------------------------------- a low estimate (ISSUE 30) ----

def test_a_low_estimate_restarts_once_and_answers_exactly(
        tpch, catalog, monkeypatch):
    """The planner's estimates forced far too low (40 rows a side):
    buckets of 64, which fill. The router's flag restarts the flow ONCE,
    on the buckets the lanes give (the program of ISSUE 27, by its
    exchanged bytes), the rows are the reference's, and the counter of
    such restarts moves by one; the prepared statement stays there."""
    from cockroach_tpu.sql import plan_compile

    real = plan_compile.estimate_cardinality
    monkeypatch.setattr(plan_compile, "estimate_cardinality",
                        lambda node, cat: min(real(node, cat), 40.0))
    reg = default_registry()
    bucket = reg.counter("sql_distsql_bucket_restarts_total")
    flow = reg.counter("sql_flow_restarts_total")
    b0, f0 = bucket.value(), flow.value()
    col = stats.enable()
    sess = _session(catalog, "set distsql = always")
    dist, root = _run(sess, Q3)
    assert root.tags["tier"] == "dist"
    want = tpch_q3.Reference(tpch["data"], tpch["dicts"], {}).answer()
    got = list(zip(dist["l_orderkey"].tolist(), dist["revenue"].tolist(),
                   dist["o_orderdate"].tolist(),
                   dist["o_shippriority"].tolist()))
    assert got == want and len(got) == 10
    assert (bucket.value(), flow.value()) == (b0 + 1, f0 + 1)
    restarts = [(tags["n"], tags["op"]) for s in root.walk()
                for _t, msg, tags in s.events if msg == "flow.restart"]
    assert restarts == [(1, "_BucketGuard")]
    # two programs: the estimate's and, after the restart, the lanes'
    assert _events(col, "dist.compile") == 2
    chosen = [[(tags["side"], tags["est_rows"], tags["bucket"],
                tags["lanes_bucket"])
               for _t, msg, tags in s.events if msg == "dist.bucket"]
              for s in root.walk() if s.name == "dist.compile"]
    assert chosen == [
        [("probe", 40, 64, 8192), ("build", 40, 64, 4096)],
        [("probe", 40, 8192, 8192), ("build", 40, 4096, 4096)]]
    assert sorted(p.a2a_bytes for p in dist_flow._PROGS.values()) == [
        (N_DEV - 1) * 2 * 64 * 29, Q3_A2A_BYTES_LANES]
    assert col.stages["dist.a2a"].events == 2
    # the prepared tree keeps the lanes' buckets: no restart, no compile
    again, root = _run(sess, Q3)
    _same(dist, again)
    assert (bucket.value(), flow.value()) == (b0 + 1, f0 + 1)
    assert _events(col, "dist.compile") == 2
    assert "sql_distsql_bucket_restarts_total" in reg.export_prometheus()


# ------------------------------------------------- spans and counters ----

def _check_tree(root):
    tree = {}
    for s in root.walk():
        assert s.trace_id == root.trace_id and s.end is not None
        if s.children:
            tree[s.name] = [c.name for c in s.children]
        for c in s.children:
            assert c.parent_id == s.span_id
            assert s.start <= c.start <= c.end <= s.end
    return tree


def test_a_served_distributed_statement_is_one_trace_of_every_stage(served):
    client, roots = served
    _ask(client, roots, "set distsql = always")
    col = stats.enable()
    n = len(roots)
    _rows, first = _ask(client, roots, Q3)
    cold = _check_tree(first)
    assert cold["wire.statement"] == WARM_TREE["wire.statement"]
    assert cold["dist.prepare"] == ["dist.prime"] * 3 + \
        ["dist.ingest"] * 3 + ["dist.compile"]
    _rows, root = _ask(client, roots, Q3)
    assert _check_tree(root) == WARM_TREE
    assert root.tags["tier"] == "dist"
    by_name = {s.name: s for s in root.walk()}
    assert by_name["session.execute"].tags["tier"] == "dist"
    assert by_name["flow.dist"].tags["shards"] == N_DEV
    assert by_name["dist.readback"].tags["bytes"] > 0
    exec_s, wait_s = (by_name["dist.exec"].duration,
                      by_name["dist.wait"].duration)
    assert by_name["dist.dispatch"].duration + wait_s <= exec_s
    # the root's host time leaves the device call and the readback out
    host = col.stages["wire.statement.host"]
    assert host.events == len(roots) - n
    assert host.seconds == pytest.approx(sum(
        r.duration - sum(s.duration for s in r.walk()
                         if s.name in ("dist.exec", "dist.readback"))
        for r in roots[n:]), abs=1e-9)
    assert root.duration - exec_s > 0
    assert root not in tracing.tracer().finished


def test_a_slow_distributed_statement_splits_on_dist_wait():
    """finish_statement reads `dist.wait` as it reads `fused.wait`, and
    the kept tree shows the `dist.*` children."""
    tr = tracing.Tracer()

    def hist(name):
        h = default_registry().histogram(name).snapshot()
        return h["sum"]

    w0, h0 = (hist("sql_slow_stmt_wait_seconds"),
              hist("sql_slow_stmt_host_seconds"))
    root = tr.start_span("wire.statement")
    flow = tr.start_span("flow.dist")
    ex = tr.start_span("dist.exec")
    wait = tr.start_span("dist.wait")
    for s in (wait, ex, flow, root):
        tr.finish_span(s)
    t0 = root.start
    root.end, flow.end, ex.end = t0 + 1.0, t0 + 0.95, t0 + 0.9
    ex.start, wait.start, wait.end = t0 + 0.1, t0 + 0.2, t0 + 0.9
    root.usual = (0.3, 0.2)          # usually 300 ms, 200 of them waiting
    col = stats.enable()
    try:
        tr.finish_statement(root)
        # host time: the second less dist.exec's 0.8 s
        assert col.stages["wire.statement.host"].seconds == \
            pytest.approx(0.2)
    finally:
        stats.disable()
    assert hist("sql_slow_stmt_wait_seconds") - w0 == pytest.approx(0.5)
    assert hist("sql_slow_stmt_host_seconds") - h0 == pytest.approx(0.2)
    assert list(tr.finished) == [root]
    names = {r["name"] for r in tr.inflight_summaries()}
    assert {"flow.dist", "dist.exec", "dist.wait"} <= names


def test_dist_stages_and_device_seconds():
    """dist.exec and dist.readback are a statement's device seconds; the
    halves, the prepare and the ingest are not counted twice."""
    col = stats.StatsCollection()
    for name in ("dist.exec", "dist.readback", "dist.dispatch", "dist.wait",
                 "dist.prepare", "dist.prime", "dist.ingest",
                 "dist.compile", "dist.unpack"):
        col.add(name, seconds=1.0)
    col.add("dist.a2a", bytes=10)
    assert stats.device_seconds(col) == pytest.approx(2.0)
    assert stats.operator_device(col) == {"dist": pytest.approx(2.0)}


# ------------------------------------- the benchmark's cell, rehearsed ----

def test_the_mesh_cell_rehearses_on_the_cpu():
    """`tpch-sf1-mesh4.q3-1stream` end to end at rehearsal scale, traced:
    correct, on the CPU by its own word, and the per-layer metrics it
    prints are the ones BENCHMARK.json lists for it."""
    cell = "tpch-sf1-mesh4.q3-1stream"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=420)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads([ln for ln in p.stdout.splitlines()
                       if ln.startswith("{")][-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), cell, "per_layer")}
    assert set(last["metrics"]) == want
    assert {"dist_exec_ms", "dist_wait_ms", "dist_readback_ms",
            "dist_compile_s", "dist_ingest_mb", "a2a_mb"} <= want
    assert "stmt_program_roofline" not in want
    assert last["breakdown"]["device_ops"]
