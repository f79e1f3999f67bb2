"""TPU-aware costing + engine routing (VERDICT r4 #8): the measured
dispatch floor flips small queries onto the host CPU backend; EXPLAIN
surfaces the decision (xform/coster.go's cost terms, TPU edition)."""

import numpy as np

from cockroach_tpu.exec import collect, stats
from cockroach_tpu.exec.operators import flow_backend
from cockroach_tpu.sql.cost import (
    crossover_rows, est_host_seconds, est_tpu_seconds, route_backend,
)
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.storage.engine import PyEngine
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util.hlc import HLC, ManualClock


def test_dispatch_floor_flips_the_plan():
    # below the crossover the host wins PURELY because of the flat
    # dispatch floor; above it the accelerator's rate dominates
    x = crossover_rows()
    assert 1_000_000 < x < 10_000_000
    assert route_backend(200_000) == "cpu"
    assert route_backend(6_000_000) == "tpu"
    assert est_host_seconds(200_000) < est_tpu_seconds(200_000)
    assert est_tpu_seconds(20_000_000) < est_host_seconds(20_000_000)
    # explicit settings override the coster
    assert route_backend(10, "tpu") == "tpu"
    assert route_backend(1 << 30, "cpu") == "cpu"


def _session():
    st = MVCCStore(engine=PyEngine(), clock=HLC(ManualClock(1000)))
    return Session(SessionCatalog(st), capacity=256)


def test_small_query_routes_to_host_engine():
    s = _session()
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i * 7})" for i in range(50)))
    st = stats.enable()
    try:
        kind, payload, _ = s.execute("select sum(v) from t")
        assert int(next(iter(payload.values()))[0]) == sum(
            i * 7 for i in range(50))
        assert st.stage("route.cpu").events >= 1
    finally:
        stats.disable()


def test_explain_surfaces_engine_choice():
    s = _session()
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values (1, 1)")
    kind, lines, _ = s.execute("explain select v from t")
    assert kind == "explain"
    engine_lines = [ln for ln in lines if ln.startswith("engine:")]
    assert engine_lines and "cpu" in engine_lines[0]
    assert "dispatch floor" in engine_lines[0]


def test_flow_backend_respects_est_rows():
    from cockroach_tpu.coldata.batch import Field, INT, Schema
    from cockroach_tpu.exec.operators import ScanOp

    schema = Schema([Field("k", INT)])

    def chunks():
        yield {"k": np.arange(8, dtype=np.int64)}

    small = ScanOp(schema, chunks, 8)
    small.est_rows = 1000
    assert flow_backend(small) == "cpu"
    big = ScanOp(schema, chunks, 8)
    big.est_rows = 50_000_000
    assert flow_backend(big) == "tpu"
    unknown = ScanOp(schema, chunks, 8)
    assert flow_backend(unknown) == "tpu"  # no estimate: accelerator


def test_set_vectorize_forces_a_side_else_the_coster_routes():
    """SET vectorize = tpu reaches the placement pass (cold) and the
    prepared re-collect (warm); the default, and any value that names no
    backend (`on`, as clients of the reference send), is the coster."""
    s = _session()
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i})" for i in range(50)))
    assert s.vars["vectorize"] == "auto"
    for value, want, other in (("tpu", "route.tpu", "route.cpu"),
                               ("on", "route.cpu", "route.tpu")):
        s.execute(f"set vectorize = {value}")
        for _ in ("cold", "warm"):
            st = stats.enable()
            try:
                s.execute("select sum(v) from t")
                assert st.stage(want).events == 1
                assert other not in st.stages
            finally:
                stats.disable()
