"""Tracing (util/tracing.py), invariants checker (exec/invariants.py),
EXPLAIN / EXPLAIN ANALYZE (sql/explain.py), and the CLI shell surface
(cli.py) — SURVEY.md §5.1/§5.2 + L9."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cockroach_tpu.cli import format_rows, run_statement
from cockroach_tpu.coldata.batch import Batch, Column, Field, INT, Schema
from cockroach_tpu.exec.invariants import (
    INVARIANTS, CheckedOp, InvariantViolation, check_batch,
)
from cockroach_tpu.sql import TPCHCatalog
from cockroach_tpu.sql.explain import execute, render_plan
from cockroach_tpu.util.settings import Settings
from cockroach_tpu.util.tracing import (
    MAX_EVENTS_PER_SPAN, child_span, record, summarize, tracer,
)
from cockroach_tpu.workload.tpch import TPCH

GEN = TPCH(sf=0.01)
CAT = TPCHCatalog(GEN)


# ------------------------------------------------------------- tracing --

def test_span_nesting_and_render():
    tr = tracer()
    with tr.span("root", query="q1") as root:
        record("phase one")
        with tr.span("child"):
            record("inner event", rows=10)
    assert root.end is not None
    assert len(root.children) == 1
    assert root.children[0].parent_id == root.span_id
    assert root.children[0].trace_id == root.trace_id
    text = root.render()
    assert "root" in text and "child" in text and "inner event" in text


def test_span_carrier_propagation():
    tr = tracer()
    with tr.span("gateway") as g:
        carrier = tr.carrier()
    assert carrier == {"trace_id": g.trace_id, "span_id": g.span_id}
    with tr.from_carrier(carrier, "remote-flow") as r:
        assert r.trace_id == g.trace_id
        assert r.parent_id == g.span_id


def test_inflight_registry():
    tr = tracer()
    with tr.span("live") as s:
        assert s.span_id in tr.inflight
    assert s.span_id not in tr.inflight


# ----------------------------------------------------------- invariants --

def _ok_batch():
    return Batch({"a": Column(jnp.arange(4, dtype=jnp.int64))},
                 jnp.ones(4, dtype=bool), jnp.asarray(4, dtype=jnp.int32))


def test_check_batch_accepts_valid():
    check_batch(_ok_batch(), Schema([Field("a", INT)]))


def test_check_batch_rejects_bad_length():
    b = _ok_batch()
    bad = Batch(b.columns, b.sel, jnp.asarray(3, dtype=jnp.int32))
    with pytest.raises(InvariantViolation):
        check_batch(bad, Schema([Field("a", INT)]))


def test_check_batch_rejects_wrong_columns():
    with pytest.raises(InvariantViolation):
        check_batch(_ok_batch(), Schema([Field("b", INT)]))


def test_checked_build_runs_queries():
    """With sql.tpu.invariants on, every operator is wrapped and the
    TPC-H plans still execute correctly (unfused path materializes the
    intermediate batches the checker validates)."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.sql import run_sql
    from cockroach_tpu.sql.plan import build
    from cockroach_tpu.workload import tpch_queries as Q

    s = Settings()
    prev = s.get(INVARIANTS)
    s.set(INVARIANTS, True)
    try:
        op = build(Q.q3_plan(), CAT, 1 << 14)
        assert isinstance(op, CheckedOp)
        got = collect(op, fuse=False)
        want = Q.q3_oracle(GEN)
        rows = [(int(got["l_orderkey"][i]), int(got["revenue"][i]),
                 int(got["o_orderdate"][i]))
                for i in range(len(got["l_orderkey"]))]
        assert rows == want
    finally:
        s.set(INVARIANTS, prev)


# -------------------------------------------------------------- explain --

def test_explain_renders_plan_tree():
    kind, lines = execute(
        "explain select n_name from nation where n_regionkey = 1 "
        "order by n_name limit 3", CAT, capacity=64)
    assert kind == "explain"
    text = "\n".join(lines)
    assert "limit" in text and "sort" in text and "scan nation" in text


def test_explain_analyze_runs_and_reports():
    kind, lines = execute(
        "explain analyze select n_regionkey, count(*) as n from nation "
        "group by n_regionkey", CAT, capacity=64)
    assert kind == "explain"
    text = "\n".join(lines)
    assert "aggregate" in text
    assert "execution:" in text
    assert "result rows" in text
    assert "query:" in text  # the trace span rendering


def test_execute_rows_path():
    kind, res = execute("select count(*) as n from nation", CAT,
                        capacity=64)
    assert kind == "rows"
    assert int(res["n"][0]) == len(GEN.table("nation")["n_nationkey"])


# ------------------------------------------------------------------ cli --

def test_format_rows_decodes_dictionaries_and_nulls():
    schema = GEN.schema("nation")
    res = {
        "n_name": np.array([0, 1]),
        "n_name__valid": np.array([True, False]),
        "n_nationkey": np.array([0, 1]),
        "n_nationkey__valid": np.array([True, True]),
    }
    lines = format_rows(res, schema)
    text = "\n".join(lines)
    assert str(schema.dicts["n_name"][0]) in text
    assert "NULL" in text
    assert "(2 rows)" in text


def test_run_statement_end_to_end():
    out = run_statement(
        "select n_name, n_regionkey from nation "
        "where n_regionkey = 0 order by n_name", CAT, 64)
    text = "\n".join(out)
    assert "time:" in text
    # region-0 nations decoded as strings
    t = GEN.table("nation")
    d = GEN.schema("nation").dicts["n_name"]
    want_any = str(d[t["n_name"][t["n_regionkey"] == 0][0]])
    assert any(want_any in line for line in out)


def test_run_statement_reports_errors():
    out = run_statement("select nope from nation", CAT, 64)
    assert out and out[0].startswith("error:")
    out = run_statement("selec broken", CAT, 64)
    assert out and out[0].startswith("error:")
    # zero-arg window aggregate: BindError, not a raw KeyError
    out = run_statement("select sum() over () from nation", CAT, 64)
    assert out and out[0].startswith("error:") and "argument" in out[0]


def test_window_string_min_is_lexicographic():
    from cockroach_tpu.sql import run_sql

    got = run_sql("select min(n_name) over () as m from nation", CAT,
                  capacity=64)
    d = GEN.schema("nation").dicts["n_name"]
    want = sorted(str(x) for x in d[GEN.table("nation")["n_name"]])[0]
    assert str(d[int(got["m"][0])]) == want


# -------------------------------------------- tracing: events / digest --

def test_span_event_cap_truncates_with_marker():
    tr = tracer()
    with tr.span("busy") as s:
        for i in range(MAX_EVENTS_PER_SPAN + 37):
            record("tick", i=i)
    assert len(s.events) == MAX_EVENTS_PER_SPAN
    assert s.dropped == 37
    assert "(+37 events dropped)" in s.render()
    assert s.as_dict()["dropped_events"] == 37


def test_child_span_is_noop_without_active_root():
    with child_span("orphan") as s:
        assert s is None  # nothing tracing: zero-cost path
    tr = tracer()
    with tr.span("root") as root:
        with child_span("kid", rows=3) as kid:
            assert kid is not None
    assert [c.name for c in root.children] == ["kid"]
    assert root.children[0].tags == {"rows": 3}


def test_summarize_derives_tier_and_counts_events():
    tr = tracer()
    with tr.span("query") as sp:
        with tr.span("flow.fused"):
            record("retry", name="scan.transfer", backoff_s=0.01)
            record("degrade", from_tier="fused", to_tier="streaming")
        with tr.span("flow.streaming"):
            record("flow.restart", n=1)
    summ = summarize(sp)
    # the LAST flow.* rung entered is the one the query finished on
    assert summ["tier"] == "streaming"
    assert summ["retries"] == 1
    assert summ["degradations"] == 1
    assert summ["restarts"] == 1
    assert set(summ["stages"]) == {"flow.fused", "flow.streaming"}
    assert summ["events"] == 3
    assert summarize(None) is None


def test_explain_analyze_q3_renders_span_tree():
    kind, lines = execute(
        "explain analyze select l_orderkey, "
        "sum(l_extendedprice * (1 - l_discount)) as revenue, "
        "o_orderdate, o_shippriority "
        "from customer, orders, lineitem "
        "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey "
        "and l_orderkey = o_orderkey "
        "and o_orderdate < date '1995-03-15' "
        "and l_shipdate > date '1995-03-15' "
        "group by l_orderkey, o_orderdate, o_shippriority "
        "order by revenue desc, o_orderdate limit 10",
        CAT, capacity=1 << 12)
    assert kind == "explain"
    text = "\n".join(lines)
    # the span tree covers the scan -> compile -> exec stages of the
    # tier that ran, plus the one-line resilience digest
    assert "flow." in text
    assert "scan." in text
    assert "compile" in text
    assert "exec" in text
    assert "resilience: tier=" in text
    assert "retries=" in text and "degradations=" in text
    # the stage table counts the Join+Shrink pairs lowered as one step
    compacts = [ln.split() for ln in lines
                if ln.startswith("fused.join_compact")]
    assert compacts and compacts[0][-2:] == ["2", "ev"]
    # and names the lowering its aggregate took (exec/fused._mat_agg):
    # in place, over the order its compacting join left
    assert any(ln.startswith("fused.agg_ordered") for ln in lines)
    assert not any(ln.startswith(("fused.agg_folded",
                                  "fused.agg_materialized"))
                   for ln in lines)


def test_explain_analyze_trace_shows_retry_on_armed_fault():
    from cockroach_tpu.exec.scan_cache import scan_image_cache
    from cockroach_tpu.util.fault import registry

    # a warm scan-image cache would skip the transfer seam entirely
    scan_image_cache().clear()
    registry().arm("scan.transfer", after=0)
    try:
        kind, lines = execute(
            "explain analyze select count(*) as n from lineitem", CAT,
            capacity=1 << 12)
    finally:
        fired = registry().fires("scan.transfer")
        registry().disarm()
    assert kind == "explain"
    assert fired == 1
    text = "\n".join(lines)
    assert "retry" in text
    assert "scan.transfer" in text


def test_slow_query_log_fires_above_threshold_only():
    from cockroach_tpu.sql.session import (
        SLOW_QUERY_LATENCY, Session, SessionCatalog,
    )
    from cockroach_tpu.storage.engine import PyEngine
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.hlc import HLC, ManualClock
    from cockroach_tpu.util.log import Channel, MemorySink, get_logger

    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    sess = Session(SessionCatalog(store), capacity=64)
    sess.execute("create table t (a int)")
    sess.execute("insert into t values (1), (2)")

    lg = get_logger()
    mem = MemorySink()
    lg.add_sink(Channel.SQL_EXEC, mem)
    s = Settings()
    try:
        # below threshold (disabled at 0.0): silent
        sess.execute("select a from t")
        assert not mem.entries
        # any query beats a sub-nanosecond threshold
        s.set(SLOW_QUERY_LATENCY, 1e-9)
        sess.execute("select a from t")
    finally:
        s.set(SLOW_QUERY_LATENCY, 0.0)
        lg._sinks[Channel.SQL_EXEC].remove(mem)
    slow = [e for e in mem.entries if e.get("event") == "slow_query"]
    assert len(slow) == 1
    assert "select a from t" in slow[0]["sql"]
    assert float(slow[0]["latency_s"]) >= 0.0
    # sql text stays inside redaction markers in the formatted line
    from cockroach_tpu.util.log import redact

    assert "select a from t" not in redact(slow[0]["msg"])


# ------------------------- crdb_internal / registry / insights (M15) --


def _mvcc_session(capacity=64):
    from cockroach_tpu.sql.session import Session, SessionCatalog
    from cockroach_tpu.storage.engine import PyEngine
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.hlc import HLC, ManualClock

    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    return Session(SessionCatalog(store), capacity=capacity)


def test_vtable_node_metrics_where_and_limit_compose():
    """crdb_internal.* materializes through the normal plan path, so
    WHERE / ORDER BY / LIMIT / aggregates all compose."""
    from cockroach_tpu.sql.explain import execute_with_plan
    from cockroach_tpu.util.metric import default_registry

    default_registry().counter("obs_vtable_probe_total",
                               "vtable test probe").inc(3)
    kind, res, schema = execute_with_plan(
        "select name, value from crdb_internal.node_metrics "
        "where name = 'obs_vtable_probe_total'", CAT, capacity=64)
    assert kind == "rows"
    f = next(f for f in schema.fields if f.name == "name")
    d = schema.dicts[f.dict_ref]
    assert [str(d[int(c)]) for c in res["name"]] == [
        "obs_vtable_probe_total"]
    assert float(res["value"][0]) == 3.0
    # LIMIT bounds the row count
    kind, res2 = execute(
        "select name from crdb_internal.node_metrics limit 3",
        CAT, capacity=64)
    assert kind == "rows" and len(res2["name"]) == 3
    # aggregates over a vtable
    kind, res3 = execute(
        "select count(*) as n from crdb_internal.node_metrics",
        CAT, capacity=64)
    assert kind == "rows" and int(res3["n"][0]) >= 3


def test_vtable_cluster_queries_shows_self_and_registry_drains():
    """A session-executed statement registers before bind, so the
    vtable snapshot taken at bind time includes the statement itself —
    and the entry is gone once it finishes."""
    from cockroach_tpu.server.registry import default_query_registry

    sess = _mvcc_session()
    kind, res, schema = sess.execute(
        "select query_id, phase, sql from "
        "crdb_internal.cluster_queries")
    assert kind == "rows"
    f = next(f for f in schema.fields if f.name == "sql")
    d = schema.dicts[f.dict_ref]
    texts = [str(d[int(c)]) for c in res["sql"]]
    assert any("cluster_queries" in t for t in texts)
    # statement finished -> its registry entry is gone
    assert default_query_registry().query_count() == 0


def test_show_queries_sessions_jobs_and_cancel_unknown_id():
    from cockroach_tpu.sql.session import SQLError

    sess = _mvcc_session()
    kind, payload, _ = sess.execute("show queries")
    assert kind == "rows"
    assert "show queries" in list(payload["sql"])
    assert list(payload["phase"]) == ["executing"]
    kind, payload, _ = sess.execute("show sessions")
    assert sess.session_id in list(payload["session_id"])
    kind, payload, _ = sess.execute("show jobs")
    assert set(payload) == {"job_id", "node_id", "kind", "state",
                            "progress", "error", "frontier_lag",
                            "folds", "rescans"}
    with pytest.raises(SQLError) as ei:
        sess.execute("cancel query 123456789")
    assert ei.value.pgcode == "42704"


def test_explain_analyze_operator_breakdown():
    sess = _mvcc_session()
    sess.execute("create table t (a int)")
    sess.execute("insert into t values (1), (2), (3)")
    kind, lines, _ = sess.execute(
        "explain analyze select a from t where a > 1")
    assert kind == "explain"
    text = "\n".join(lines)
    assert "operators:" in text
    assert "device-ms" in text
    # the scan family is attributed separately from the fused kernel
    op_lines = [ln for ln in lines if "device-ms" in ln]
    assert any(ln.strip().startswith("scan") for ln in op_lines)


def test_sqlstats_rolls_up_device_time():
    from cockroach_tpu.sql.sqlstats import default_sqlstats, fingerprint

    sess = _mvcc_session()
    sess.execute("create table dt (a int)")
    sess.execute("insert into dt values (1), (2)")
    q = "select a from dt where a >= 1"
    default_sqlstats().reset()
    sess.execute(q)
    hit = [s for s in default_sqlstats().top(1000)
           if s["fingerprint"] == fingerprint(q)]
    assert hit
    assert "device_seconds" in hit[0] and "bytes_scanned" in hit[0]
    assert hit[0]["device_seconds"] >= 0.0


def test_insights_slow_flagged_against_own_baseline():
    from cockroach_tpu.sql.insights import InsightsRegistry

    reg = InsightsRegistry()
    q = "select a from t where b = 1"
    for _ in range(6):
        assert reg.observe(q, 0.01) is None
    ins = reg.observe(q, 1.0)
    assert ins is not None and "slow" in ins.kinds
    assert ins.baseline_mean_s < 0.1
    # back to normal: no flag; and a different fingerprint has its own
    # baseline (cold -> never flags below min_samples)
    assert reg.observe(q, 0.01) is None
    assert reg.observe("select z from w", 10.0) is None


def test_insights_ring_caps_and_errors_skip_baseline():
    from cockroach_tpu.sql.insights import (
        INSIGHTS_CAPACITY, InsightsRegistry,
    )

    reg = InsightsRegistry()
    s = Settings()
    prev = s.get(INSIGHTS_CAPACITY)
    s.set(INSIGHTS_CAPACITY, 4)
    try:
        for i in range(10):
            ins = reg.observe("q%d" % i, 0.0, shed=True, error=True)
            assert ins is not None and ins.kinds == ("shed",)
        assert len(reg.insights()) == 4
        # error/shed executions never feed the latency baseline
        b = reg.baseline("q0")
        assert b is not None and b.count == 0
    finally:
        s.set(INSIGHTS_CAPACITY, prev)


def test_insight_fires_on_session_shed():
    from cockroach_tpu.sql.insights import default_insights
    from cockroach_tpu.sql.session import SQLError
    from cockroach_tpu.sql.sqlstats import fingerprint
    from cockroach_tpu.util.admission import (
        SESSION_QUEUE_TIMEOUT, SESSION_SLOTS, session_queue,
    )

    sess = _mvcc_session()
    sess.execute("create table st (a int)")
    sess.execute("insert into st values (1)")
    q = "select a from st where a = 1"
    s = Settings()
    prev_slots = s.get(SESSION_SLOTS)
    prev_to = s.get(SESSION_QUEUE_TIMEOUT)
    s.set(SESSION_SLOTS, 1)
    s.set(SESSION_QUEUE_TIMEOUT, 0.05)
    default_insights().reset()
    try:
        qq = session_queue()
        qq.acquire()  # hold the only slot -> next statement sheds
        try:
            with pytest.raises(SQLError) as ei:
                sess.execute(q)
            assert ei.value.pgcode == "53300"
        finally:
            qq.release()
    finally:
        s.set(SESSION_SLOTS, prev_slots)
        s.set(SESSION_QUEUE_TIMEOUT, prev_to)
    hits = [i for i in default_insights().insights()
            if i["fingerprint"] == fingerprint(q)]
    assert hits and "shed" in hits[0]["kinds"]


def test_insight_fires_on_injected_slow_execution():
    from cockroach_tpu.sql.insights import default_insights
    from cockroach_tpu.sql.sqlstats import fingerprint
    from cockroach_tpu.util.fault import registry
    import time as _time

    sess = _mvcc_session(capacity=256)
    sess.execute("create table sl (a int)")
    sess.execute("insert into sl values (1), (2)")
    q = "select a from sl where a >= 1"
    sess.execute(q)  # compile-warm so the baseline stays flat
    ins = default_insights()
    ins.reset()
    for _ in range(6):
        ins.observe(q, 0.001)  # healthy baseline: ~1ms

    def make():
        _time.sleep(0.25)
        return ConnectionError("transfer failed")

    registry().arm("fused.exec", after=0, make=make)  # fires once
    try:
        sess.execute(q)  # one stalled fire, then the retry succeeds
    finally:
        registry().disarm()
    hits = [i for i in ins.insights()
            if i["fingerprint"] == fingerprint(q)]
    assert hits and "slow" in hits[-1]["kinds"]
    assert hits[-1]["elapsed_s"] >= 0.25


def test_sqlstats_lru_eviction_and_counter():
    from cockroach_tpu.sql.sqlstats import (
        MAX_STMT_FINGERPRINTS, SQLStats, fingerprint,
    )
    from cockroach_tpu.util.metric import default_registry

    st = SQLStats()
    ctr = default_registry().counter(
        "sqlstats_fingerprints_evicted_total")
    before = ctr.value()
    s = Settings()
    prev = s.get(MAX_STMT_FINGERPRINTS)
    s.set(MAX_STMT_FINGERPRINTS, 3)
    try:
        for i in range(6):
            st.record("select c%d from tbl%d" % (i, i), 0.001)
        tops = st.top(100)
        assert len(tops) == 3
        assert ctr.value() - before == 3
        fps = {t["fingerprint"] for t in tops}
        # least-recently-updated evicted first
        assert fingerprint("select c5 from tbl5") in fps
        assert fingerprint("select c0 from tbl0") not in fps
    finally:
        s.set(MAX_STMT_FINGERPRINTS, prev)


def test_histogram_snapshot_cumulative_buckets():
    from cockroach_tpu.util.metric import Histogram

    h = Histogram("h_snap", "snap help", buckets=[1.0, 2.0])
    for v in (0.5, 1.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["sum"] == 7.0
    assert snap["buckets"] == {"1.0": 1, "2.0": 2, "+Inf": 3}


def test_status_endpoints_are_thin_views_over_vtable_providers():
    import json as _json
    from http.client import HTTPConnection

    from cockroach_tpu.server.status import StatusServer

    srv = StatusServer().start()
    try:
        def get(path):
            conn = HTTPConnection(srv.addr[0], srv.addr[1], timeout=10)
            conn.request("GET", path)
            r = conn.getresponse()
            assert r.status == 200, path
            out = _json.loads(r.read())
            conn.close()
            return out

        data = get("/_status/queries")
        assert "queries" in data and "sessions" in data
        assert "insights" in get("/_status/insights")
        classes = get("/_status/serving")["classes"]
        assert all("batch_class" in c for c in classes)
    finally:
        srv.close()


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device CPU mesh")
def test_dist_flow_carrier_grafts_worker_span():
    from cockroach_tpu.parallel import make_mesh
    from cockroach_tpu.parallel.dist_flow import collect_distributed
    from cockroach_tpu.workload import tpch_queries as Q

    tr = tracer()
    with tr.span("query") as root:
        collect_distributed(Q.q1(GEN, 1 << 12), make_mesh(8))
    names = [s.name for s in root.walk()]
    assert "flow.dist" in names
    dist = next(s for s in root.walk() if s.name == "flow.dist")
    # the carrier hop links the dist flow onto the gateway's trace
    assert dist.trace_id == root.trace_id
    assert dist.parent_id == root.span_id
    assert root.tags.get("tier") == "dist"
    kids = [s.name for s in dist.walk()]
    assert "dist.compile" in kids and "dist.exec" in kids
    assert summarize(root)["tier"] == "dist"
