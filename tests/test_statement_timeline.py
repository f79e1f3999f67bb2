"""The statement timeline (ISSUE 25): one stage seam from the socket to
the flush (exec/stats.timed = stage = span = profiler annotation), slow
statements' finished trees kept in a ring, host stalls counted
(util/tracing.py, util/metric.py), and the contract the benchmark's
harness has with the seam (benchmark/observe.py)."""

import gc
import glob
import os
import time

import pytest

from benchmark import observe
from benchmark.wire import WireClient
from cockroach_tpu.exec import stats
from cockroach_tpu.sql import pgwire
from cockroach_tpu.sql.insights import default_insights
from cockroach_tpu.sql.pgwire import PgServer
from cockroach_tpu.sql.session import SessionCatalog
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util import tracing
from cockroach_tpu.util.fault import registry as fault_registry
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.settings import Settings

Q = "select a, sum(b) sb from tl group by a order by a"
ROWS = [("1", "2"), ("3", "4"), ("5", "6")]

# a warm served SELECT, simple protocol: every stage once
WARM_TREE = {
    "wire.statement": ["wire.decode", "session.execute", "wire.render",
                       "wire.encode", "wire.flush"],
    "session.execute": ["session.admit", "sql.lookup", "flow.fused",
                        "session.account"],
    "flow.fused": ["fused.prepare", "fused.exec", "fused.readback",
                   "fused.unpack"],
    "fused.exec": ["fused.dispatch", "fused.wait"],
}


@pytest.fixture
def served(monkeypatch):
    """A PgServer over a three-row table, a stdlib client forced onto the
    device route, and the roots of the statements it served (in order)."""
    roots = []
    finish = tracing.Tracer.finish_statement

    def keep(self, root):
        finish(self, root)
        roots.append(root)

    monkeypatch.setattr(tracing.Tracer, "finish_statement", keep)
    pg = PgServer(SessionCatalog(MVCCStore()), capacity=1 << 10).start()
    client = WireClient(pg.addr, timeout=120.0)
    try:
        setup = ("create table tl (a int primary key, b int)",
                 "insert into tl values (1,2),(3,4),(5,6)",
                 "set vectorize = tpu")
        for sql in setup:
            assert client.query(sql) == ([], None)
        deadline = time.monotonic() + 30.0
        while len(roots) < len(setup) and time.monotonic() < deadline:
            time.sleep(0.001)   # a root ends after its answer is flushed
        yield pg, client, roots
    finally:
        client.close()
        pg.close()
        stats.disable()


def _run(client, roots, extended=False):
    """Q through the wire, and its root once the server has finished it
    (the answer is flushed before the root span ends)."""
    n = len(roots)
    got = client.query_extended(Q) if extended else client.query(Q)
    assert got == (ROWS, None)
    deadline = time.monotonic() + 30.0
    while len(roots) == n and time.monotonic() < deadline:
        time.sleep(0.001)
    assert len(roots) == n + 1
    return roots[-1]


def _check_tree(root):
    """Children inside their parent's interval, one trace id, self time =
    duration - children; -> {name: [child names]} of the inner spans."""
    tree = {}
    for s in root.walk():
        assert s.trace_id == root.trace_id
        assert s.end is not None
        kids = s.children
        if kids:
            tree[s.name] = [c.name for c in kids]
        for c in kids:
            assert c.parent_id == s.span_id
            assert s.start <= c.start <= c.end <= s.end
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start
        assert s.self_time == pytest.approx(
            s.duration - sum(c.duration for c in kids), abs=1e-9)
        assert s.self_time >= 0.0
    return tree


# ------------------------------------------------ (a) harness contract --

def test_the_benchmarks_observer_reads_the_seam(served, monkeypatch):
    """benchmark/observe.py replaces stats.timed, StatsCollection.add and
    tracing.query_span from outside; what it then needs of the program."""
    # registered first, so that what Observer replaces is put back
    monkeypatch.setattr(stats, "timed", stats.timed)
    monkeypatch.setattr(stats.StatsCollection, "add",
                        stats.StatsCollection.add)
    monkeypatch.setattr(tracing, "query_span", tracing.query_span)
    for method, _span in observe._WIRE_SPANS:
        monkeypatch.setattr(pgwire._Conn, method,
                            getattr(pgwire._Conn, method))
    _pg, client, roots = served
    obs = observe.Observer(annotate=True)
    _run(client, roots)      # first execution
    before = obs.snapshot()
    _run(client, roots)
    after = obs.snapshot()
    events = obs.events_between(before, after)
    for stage in ("fused.exec", "fused.dispatch", "fused.wait",
                  "fused.readback", "fused.unpack", "wire.statement.host"):
        assert len(events[stage]) == 1, stage
    assert events["fused.dispatch"][0] + events["fused.wait"][0] \
        <= events["fused.exec"][0]
    window = observe.delta(before, after)
    assert window["tiers"] == {("session.execute", "fused"): 1}
    assert window["stages"]["sql.prepared_hit"]["events"] == 1
    assert observe.faults(obs.snapshot()["stages"]) == []
    for h in ("runtime_gc_pause_seconds", "sql_slow_stmt_wait_seconds",
              "sql_slow_stmt_host_seconds"):
        assert h in window["histograms"]


# ------------------------------------------- (b) one statement's trace --

def test_a_served_statement_is_one_trace_of_every_stage(served):
    _pg, client, roots = served
    col = stats.enable()
    n = len(roots)
    first = _run(client, roots)
    cold = _check_tree(first)
    assert {"sql.parse", "sql.bind", "sql.plan", "fused.compile"} <= {
        s.name for s in first.walk()}
    assert cold["wire.statement"] == WARM_TREE["wire.statement"]

    root = _run(client, roots)
    assert _check_tree(root) == WARM_TREE
    assert root.tags["protocol"] == "simple"
    assert root.tags["tier"] == "fused"
    by_name = {s.name: s for s in root.walk()}
    assert by_name["session.execute"].tags["tier"] == "fused"
    assert by_name["wire.encode"].tags["rows"] == 3
    assert by_name["wire.flush"].tags["bytes"] > 0
    # the root's host time, as the stage table has it
    host = col.stages["wire.statement.host"]
    assert host.events == len(roots) - n
    assert host.seconds == pytest.approx(sum(
        r.duration - sum(s.duration for s in r.walk()
                         if s.name in ("fused.exec", "fused.readback"))
        for r in roots[n:]), abs=1e-9)
    # nothing of a statement that was not slow stays behind
    assert root not in tracing.tracer().finished
    assert root.span_id not in tracing.tracer().inflight


def test_extended_protocol_traces_execute_through_the_sync(served):
    _pg, client, roots = served
    col = stats.enable()
    _run(client, roots, extended=True)
    root = _run(client, roots, extended=True)
    assert col.stages["wire.parse"].events == 2
    assert col.stages["wire.bind"].events == 2
    assert root.tags["protocol"] == "extended"
    tree = _check_tree(root)
    # the Sync that came with the Execute is answered inside the trace
    assert tree["wire.statement"] == WARM_TREE["wire.statement"]
    assert tree["session.execute"] == WARM_TREE["session.execute"]


# --------------------------------------- (c) slow statements are kept --

def _hist(name):
    h = default_registry().histogram(name).snapshot()
    return h["count"], h["sum"]


def test_a_slow_statement_keeps_its_tree_and_splits_its_excess(served):
    _pg, client, roots = served
    tr = tracing.tracer()
    ins = default_insights()
    _run(client, roots)     # compiled and primed
    _run(client, roots)
    ins.reset()
    tr.finished.clear()
    for _ in range(6):      # the fingerprint's usual time: 50 ms, 20 waiting
        ins.observe(Q, 0.05, wait_s=0.02)
    assert ins.baseline(Q).mean == pytest.approx(0.05)
    assert ins.baseline(Q).wait == pytest.approx(0.02)
    wait0, host0 = (_hist("sql_slow_stmt_wait_seconds"),
                    _hist("sql_slow_stmt_host_seconds"))
    _run(client, roots)
    assert not tr.finished                      # a normal one: in neither
    assert _hist("sql_slow_stmt_host_seconds") == host0

    def stall():
        time.sleep(0.25)
        return ConnectionError("transfer failed")

    fault_registry().arm("fused.exec", after=0, make=stall)  # fires once
    try:
        root = _run(client, roots)              # the retry absorbs it
    finally:
        fault_registry().disarm()
    assert list(tr.finished) == [root]
    assert root.duration >= 0.25
    wait1, host1 = (_hist("sql_slow_stmt_wait_seconds"),
                    _hist("sql_slow_stmt_host_seconds"))
    assert wait1[0] == wait0[0] + 1 and host1[0] == host0[0] + 1
    # the stall sat on the host (before the dispatch), not in fused.wait
    assert host1[1] - host0[1] >= 0.2
    assert wait1[1] - wait0[1] < 0.05
    excess = root.duration - root.usual[0]
    assert (host1[1] - host0[1]) + (wait1[1] - wait0[1]) == \
        pytest.approx(excess, abs=1e-6)
    # served where the inflight spans are, marked finished
    rows = [r for r in tr.inflight_summaries()
            if r["trace_id"] == root.trace_id]
    assert {r["name"] for r in rows} >= {"wire.statement", "fused.exec",
                                         "fused.wait", "wire.flush"}
    assert all(r["finished"] for r in rows)
    top = [r for r in rows if r["parent_id"] is None][0]
    assert top["start_ms"] == 0.0 and "host_excess_ms" in top["tags"]
    from cockroach_tpu.sql.vtable import provider_rows

    assert any(r["finished"] == 1 and r["name"] == "fused.wait"
               for r in provider_rows("node_inflight_traces"))


def test_the_finished_ring_never_exceeds_its_size():
    tr = tracing.Tracer()
    for _ in range(tracing.FINISHED_RING + 9):
        with tr.span("wire.statement") as root:
            pass
        root.usual = (root.duration / 4, 0.0)   # four times its usual
        tr.finish_statement(root)
    assert len(tr.finished) == tracing.FINISHED_RING
    assert tr.finished[-1] is root


# ------------------------------------------------- (d) the collector --

def test_collections_are_counted_where_they_happen():
    pause = default_registry().histogram("runtime_gc_pause_seconds")
    full = default_registry().counter("runtime_gc_full_total")
    n0, s0, f0 = (pause.snapshot()["count"], pause.snapshot()["sum"],
                  full.value())
    gc.collect()
    gc.collect(0)
    snap = pause.snapshot()
    assert snap["count"] >= n0 + 2 and snap["sum"] > s0
    assert full.value() == f0 + 1 or full.value() > f0
    assert "runtime_gc_pause_seconds_count" in \
        default_registry().export_prometheus()


# ----------------------------------- (e) the program's own annotations --

def test_a_cpu_profile_holds_the_programs_annotations(served, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _pg, client, roots = served
    _run(client, roots)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run(client, roots)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    prefix = tracing.ANNOTATION_PREFIX
    assert prefix == "crdb."
    for stage in ("wire.statement", "session.execute", "fused.exec",
                  "fused.dispatch", "fused.wait", "wire.flush"):
        assert prefix + stage in names, stage


# ------------------------------------------ (f) nothing on: one branch --

def test_nothing_listening_is_a_noop():
    st = Settings()
    stats.disable()
    tr = tracing.tracer()
    st.set(tracing.TRACE_ENABLED, False)
    try:
        before = next(tr._next_id)
        with tracing.statement_span("wire.statement") as root, \
                tracing.query_span("session.execute") as qs:
            cm = stats.timed("fused.exec", rows=3)
            with cm as span:
                tracing.set_tag(bucket=1)
                tracing.record("nothing")
        assert root is None and qs is None and span is None
        assert cm is stats.timed("fused.wait")      # the one shared no-op
        assert next(tr._next_id) == before + 1      # no span was made
        assert stats.active() is None and not tr.inflight
    finally:
        st.set(tracing.TRACE_ENABLED, True)
    # tracing on, no collection: a span and no stage
    with tracing.query_span("session.execute") as qs:
        with stats.timed("fused.exec") as span:
            assert span is not None and span.parent_id == qs.span_id
    assert [c.name for c in qs.children] == ["fused.exec"]
    # a collection on, no trace: a stage and no span
    col = stats.enable()
    try:
        with stats.timed("fused.exec", rows=2) as span:
            assert span is None
        assert col.stages["fused.exec"].events == 1
        assert col.stages["fused.exec"].rows == 2
    finally:
        stats.disable()
