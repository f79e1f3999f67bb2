"""Warm-path dispatch elimination: prepared device-resident queries.

Covers the ISSUE 5 serving path end to end: a repeated SELECT through
Session must hit the prepared-statement cache AND the FusedRunner exec
cache — zero re-parse/re-bind/re-build, zero scan.stack / fused.prime /
fused.compile, exactly ONE device dispatch (fused.exec) — while one MVCC
write to any scanned table rotates the version key and forces a full
re-prime with correct (oracle-exact) results.
"""

import numpy as np
import pytest

from cockroach_tpu.exec import stats
from cockroach_tpu.exec.scan_cache import scan_image_cache
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.storage.engine import PyEngine
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.hlc import HLC, ManualClock


@pytest.fixture(autouse=True)
def _fresh_cache():
    scan_image_cache().clear()
    yield
    scan_image_cache().clear()
    stats.disable()


def _session(n_rows: int = 500) -> Session:
    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    sess = Session(SessionCatalog(store), capacity=256)
    sess.execute("create table t (a int, b int)")
    vals = ", ".join(f"({i % 7}, {i})" for i in range(n_rows))
    sess.execute(f"insert into t values {vals}")
    return sess


Q = "select a, sum(b) as sb from t group by a order by a"


def _oracle(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.array([b[a == g].sum() for g in sorted(set(a.tolist()))])


def test_warm_reexecution_is_single_dispatch():
    sess = _session()
    _, first, _ = sess.execute(Q)  # cold: parse/bind/build/prime/compile

    st = stats.enable()
    _, second, _ = sess.execute(Q)
    d = st.as_dict()
    stats.disable()

    # the warm run re-collects the prepared tree over cached device args
    assert "scan.stack" not in d, d
    assert "fused.prime" not in d, d
    assert "fused.compile" not in d, d
    assert d["fused.exec"]["events"] == 1, d
    assert d["prime.skipped"]["events"] >= 1, d
    assert d["sql.prepared_hit"]["events"] == 1, d
    assert np.array_equal(np.asarray(first["sb"]),
                          np.asarray(second["sb"]))


def test_write_invalidates_prepared_entry():
    sess = _session()
    sess.execute(Q)
    sess.execute(Q)  # warm the prepared path

    sess.execute("insert into t values (3, 100000)")
    st = stats.enable()
    _, res, _ = sess.execute(Q)
    d = st.as_dict()
    stats.disable()

    # the version bump forced a full re-prime (no stale prepared hit)
    assert "sql.prepared_hit" not in d, d
    assert d["fused.prime"]["events"] >= 1, d
    a = np.concatenate([np.arange(500) % 7, [3]])
    b = np.concatenate([np.arange(500), [100000]])
    assert np.array_equal(np.asarray(res["sb"], dtype=np.int64),
                          _oracle(a, b))


def test_prepared_cache_cleared_on_ddl_and_set():
    sess = _session(100)
    sess.execute(Q)
    assert Q in sess._prepared
    sess.execute("set workmem = 1073741824")
    assert not sess._prepared  # settings can change plans wholesale
    sess.execute(Q)
    assert Q in sess._prepared
    sess.execute("alter table t add column c int")
    assert not sess._prepared


def test_prepared_skipped_inside_transaction():
    sess = _session(100)
    sess.execute(Q)
    sess.execute("begin")
    try:
        st = stats.enable()
        _, res, _ = sess.execute(Q)
        d = st.as_dict()
        stats.disable()
        assert "sql.prepared_hit" not in d, d
        assert np.array_equal(np.asarray(res["sb"], dtype=np.int64),
                              _oracle(np.arange(100) % 7, np.arange(100)))
    finally:
        sess.execute("rollback")


def test_exec_cache_respects_snapshot_and_version_keys():
    """Direct flow, no Session version checks. Re-collecting the SAME op
    reads its pinned MVCC snapshot (exec-cache hits and buffer donation
    must not corrupt it); a NEW op built after a write gets a rotated
    version key and must see the new data, never the cached image."""
    from cockroach_tpu.coldata.batch import Field, INT, Schema
    from cockroach_tpu.exec import collect
    from cockroach_tpu.exec.operators import HashAggOp
    from cockroach_tpu.ops.agg import AggSpec

    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    tid = 7
    store.ingest_table(tid, list(range(50)),
                       {"v": np.arange(50, dtype=np.int64)})
    schema = Schema([Field("v", INT)])

    def flow():
        return HashAggOp(store.scan_op(tid, schema, 32), [],
                         [AggSpec("sum", "v", "s")])

    op = flow()
    r1 = collect(op)
    r2 = collect(op)  # warm: exec-cache hit
    assert r1["s"][0] == r2["s"][0] == np.arange(50).sum()
    store.put(tid, 50, [1000])  # bumps version + eagerly invalidates
    r3 = collect(op)  # same op: pinned ts, still the old snapshot
    assert r3["s"][0] == np.arange(50).sum()
    r4 = collect(flow())  # new op: rotated key, fresh image
    assert r4["s"][0] == np.arange(50).sum() + 1000


def test_scan_topk_batcher_bit_identical_and_oracle():
    from cockroach_tpu.workload.ycsb import ScanTopKBatcher, batch_bucket

    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 40, 2000).astype(np.int64)
    b = ScanTopKBatcher(vals, np.arange(2000, dtype=np.int64), k=10)
    starts = np.array([0, 17, 1990, 1999, 800, 1500], dtype=np.int64)
    lens = np.array([10, 100, 50, 1, 3, 100], dtype=np.int64)

    v_un, c_un = b.run_unbatched(starts, lens)
    v_ba, c_ba = b.run(starts, lens, batch_size=4)
    assert np.array_equal(v_un, v_ba)
    assert np.array_equal(c_un, c_ba)
    for i, (s, l) in enumerate(zip(starts, lens)):
        seg = vals[s:s + l]
        assert c_un[i] == len(seg)  # ranges clipped at the table end
        exp = np.sort(seg)[::-1][:10]
        assert np.array_equal(v_un[i][:len(exp)], exp)
    # pow2 padding: 6 ops in batches of 4 -> buckets of 4 and 2
    assert b.dispatches == 2
    assert b.slots_dispatched == batch_bucket(4) + batch_bucket(2)
    assert b.occupancy() == 1.0


def test_slow_query_interval_rate_limits_per_fingerprint():
    from cockroach_tpu.sql import session as sess_mod
    from cockroach_tpu.sql.session import (
        SLOW_QUERY_INTERVAL, SLOW_QUERY_LATENCY,
    )
    from cockroach_tpu.util.log import Channel, MemorySink, get_logger
    from cockroach_tpu.util.settings import Settings

    sess = _session(50)
    lg = get_logger()
    mem = MemorySink()
    lg.add_sink(Channel.SQL_EXEC, mem)
    s = Settings()
    sess_mod._slow_log_last.clear()
    try:
        s.set(SLOW_QUERY_LATENCY, 1e-9)
        s.set(SLOW_QUERY_INTERVAL, 3600.0)
        # same fingerprint (literals differ): ONE event per interval
        sess.execute("select a from t where b = 1")
        sess.execute("select a from t where b = 2")
        sess.execute("select a from t where b = 3")
        # a different fingerprint logs independently
        sess.execute("select b from t where a = 1")
    finally:
        s.set(SLOW_QUERY_LATENCY, 0.0)
        s.set(SLOW_QUERY_INTERVAL, 0.0)
        lg._sinks[Channel.SQL_EXEC].remove(mem)
        sess_mod._slow_log_last.clear()
    slow = [e for e in mem.entries if e.get("event") == "slow_query"]
    assert len(slow) == 2, slow
    assert "select a from t" in str(slow[0]["sql"])
    assert "select b from t" in str(slow[1]["sql"])


def test_mvcc_catalog_statement_is_prepared_and_warm():
    """TPCH.mvcc_load's read-only MVCCCatalog — what a bulk-loaded server
    serves — has MVCC-versioned scan keys like SessionCatalog, so its
    statements are prepared too: the second execution is one dispatch,
    no re-plan, no compile; a write rotates the key and re-primes."""
    from cockroach_tpu.coldata.batch import Field, INT, Schema
    from cockroach_tpu.sql.plan import MVCCCatalog

    store = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    n = 300
    store.ingest_table(7, np.arange(n, dtype=np.int64),
                       {"a": np.arange(n, dtype=np.int64) % 5,
                        "b": np.arange(n, dtype=np.int64)})
    cat = MVCCCatalog(store, {"t": (7, Schema([Field("a", INT),
                                                Field("b", INT)]))},
                      rows={"t": n})
    sess = Session(cat, capacity=128)
    sess.execute(Q)
    st = stats.enable()
    _k, payload, _s = sess.execute(Q)
    ev = {k: v["events"] for k, v in st.as_dict().items()}
    assert ev.get("sql.prepared_hit") == 1, ev
    assert ev.get("fused.exec") == 1 and "fused.compile" not in ev, ev
    want = [int((np.arange(n)[np.arange(n) % 5 == g]).sum())
            for g in range(5)]
    assert [int(x) for x in payload["sb"]] == want
    store.put(7, n, [0, 1000])  # rotates the version key
    _k, payload, _s = sess.execute(Q)
    assert int(payload["sb"][0]) == want[0] + 1000
