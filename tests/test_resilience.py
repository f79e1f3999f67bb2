"""Resilience layer: retry policy + classifier (util/retry.py), circuit
breakers (util/circuit.py), the run_flow degradation ladder, restart
exhaustion accounting, and the SQL error mapping.

The chaos-style end-to-end coverage (TPC-H under randomized fault arming)
lives in tests/test_chaos.py; this file pins the mechanisms in isolation.
"""

import numpy as np
import pytest

from cockroach_tpu.coldata.batch import Field, INT, Schema
from cockroach_tpu.exec import collect, stats
from cockroach_tpu.exec.operators import (
    FlowRestart, HashAggOp, ScanOp, run_flow,
)
from cockroach_tpu.ops.agg import AggSpec
from cockroach_tpu.util import circuit
from cockroach_tpu.util import retry
from cockroach_tpu.util.fault import InjectedFault, registry
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.mon import BytesMonitor
from cockroach_tpu.util.settings import Settings


def _no_sleep_options(**kw):
    kw.setdefault("initial_backoff", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return retry.Options(**kw)


def _int_scan(data, capacity):
    schema = Schema([Field(n, INT) for n in data])
    return ScanOp(schema, lambda: iter([data]), capacity)


@pytest.fixture(autouse=True)
def _fast_backoff():
    """Zero the retry backoff for every test here (process-global)."""
    s = Settings()
    old = s.get(retry.RESILIENCE_INITIAL_BACKOFF)
    s.set(retry.RESILIENCE_INITIAL_BACKOFF, 0.0)
    yield
    s.set(retry.RESILIENCE_INITIAL_BACKOFF, old)


# ------------------------------------------------------------ classifier --

def test_classify_verdicts():
    mon = BytesMonitor("m", budget=10)
    acct = mon.make_account()
    budget_err = None
    try:
        acct.grow(100)
    except Exception as e:  # noqa: BLE001
        budget_err = e

    assert retry.classify(InjectedFault("boom")) == retry.RETRYABLE
    assert retry.classify(budget_err) == retry.RESOURCE
    assert retry.classify(
        RuntimeError("RESOURCE_EXHAUSTED: allocating 2G")) == retry.RESOURCE
    assert retry.classify(
        RuntimeError("UNAVAILABLE: transfer failed")) == retry.RETRYABLE
    assert retry.classify(ConnectionError("reset")) == retry.RETRYABLE
    assert retry.classify(ValueError("bad plan")) == retry.TERMINAL
    scan = _int_scan({"k": np.arange(4, dtype=np.int64)}, 4)
    assert retry.classify(FlowRestart(scan)) == retry.RETRYABLE


def test_backoff_progression_and_jitter_bounds():
    opts = retry.Options(initial_backoff=0.1, max_backoff=0.5,
                         multiplier=2.0, jitter=0.2, max_retries=5)
    pauses = list(opts.backoffs())
    assert len(pauses) == 5
    nominal = [0.1, 0.2, 0.4, 0.5, 0.5]
    for p, n in zip(pauses, nominal):
        assert n * 0.8 <= p <= n * 1.2


def test_with_retry_recovers_then_exhausts():
    calls = {"n": 0}

    def flaky(fail_times):
        def fn():
            calls["n"] += 1
            if calls["n"] <= fail_times:
                raise InjectedFault("transient")
            return "ok"
        return fn

    assert retry.with_retry(flaky(3),
                            opts=_no_sleep_options(max_retries=5)) == "ok"

    calls["n"] = 0
    with pytest.raises(InjectedFault):
        retry.with_retry(flaky(100), opts=_no_sleep_options(max_retries=2))
    assert calls["n"] == 3  # initial attempt + 2 retries


def test_with_retry_terminal_not_retried():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise ValueError("terminal")

    with pytest.raises(ValueError):
        retry.with_retry(fn, opts=_no_sleep_options(max_retries=5))
    assert calls["n"] == 1


def test_with_retry_resource_not_retried():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    with pytest.raises(RuntimeError):
        retry.with_retry(fn, opts=_no_sleep_options(max_retries=5))
    assert calls["n"] == 1


# --------------------------------------------------------------- breaker --

def test_breaker_trip_halfopen_probe_cycle():
    clock = {"t": 0.0}
    br = circuit.CircuitBreaker("test.tier", threshold=3, cooldown_s=10.0,
                                clock=lambda: clock["t"])
    assert br.allow() and br.state() == circuit.CLOSED
    br.failure()
    br.failure()
    assert br.state() == circuit.CLOSED  # below threshold
    br.failure()
    assert br.state() == circuit.OPEN
    assert not br.allow()

    clock["t"] = 10.0  # cooldown elapsed: one half-open probe
    assert br.allow()
    assert br.state() == circuit.HALF_OPEN
    assert not br.allow()  # second caller blocked while probe in flight

    br.failure()  # probe failed: re-open immediately
    assert br.state() == circuit.OPEN
    clock["t"] = 20.0
    assert br.allow()
    br.success()  # probe succeeded: closed, failure streak reset
    assert br.state() == circuit.CLOSED
    assert br.allow()


def test_breaker_success_resets_streak():
    br = circuit.CircuitBreaker("test.streak", threshold=2, cooldown_s=1.0)
    br.failure()
    br.success()
    br.failure()
    assert br.state() == circuit.CLOSED  # never 2 consecutive


def test_breaker_state_gauge_exported():
    br = circuit.CircuitBreaker("test.gauge", threshold=1, cooldown_s=99.0)
    g = default_registry().gauge("sql_resilience_breaker_state_test_gauge")
    assert g.value() == 0
    br.failure()
    assert g.value() == 2
    br.reset()
    assert g.value() == 0


# --------------------------------------------------- restart exhaustion --

class _AlwaysRestart:
    """An operator whose deferred flag check always fails."""

    schema = Schema([Field("k", INT)])

    def __init__(self):
        self.expansion = 1
        self.widened = 0

    def widen(self):
        self.widened += 1

    def batches(self):
        raise FlowRestart(self)
        yield  # pragma: no cover


def test_restart_exhaustion_counts_and_raises_original():
    op = _AlwaysRestart()
    ctr = default_registry().counter("sql_flow_restarts_total")
    before = ctr.value()
    max_restarts = 5
    with pytest.raises(FlowRestart) as ei:
        run_flow(op, lambda: None, lambda b: None,
                 max_restarts=max_restarts, fuse=False)
    assert ei.value.op is op
    assert ctr.value() - before == max_restarts
    assert op.widened == max_restarts


# ------------------------------------------------------ degradation ladder --

class _OomUntilClamped:
    """Raises a device-OOM-shaped error until the ladder's spill tier
    clamps workmem — the stub analog of a working set that only fits once
    the out-of-core path bounds per-stage memory."""

    def __init__(self, inner):
        self._inner = inner
        self.schema = inner.schema
        self.workmem = 1 << 30

    def batches(self):
        if self.workmem > 64 << 20:
            raise RuntimeError("RESOURCE_EXHAUSTED: stub HBM allocation")
        yield from self._inner.batches()


def test_ladder_degrades_to_spill_tier_on_oom():
    scan = _int_scan({"k": np.arange(8, dtype=np.int64)}, 8)
    op = _OomUntilClamped(scan)
    deg = default_registry().counter("sql_resilience_degradations_total")
    before = deg.value()
    st = stats.enable()
    try:
        res = collect(op, fuse=False)
    finally:
        stats.disable()
    assert sorted(res["k"].tolist()) == list(range(8))
    assert deg.value() - before == 1  # streaming -> spill, once
    assert "resilience.degrade.streaming" in st.stages
    assert op.workmem == 1 << 30  # clamp restored after the tier ran


def test_ladder_last_tier_failure_propagates():
    class _AlwaysOom:
        schema = Schema([Field("k", INT)])
        workmem = 1 << 10  # already below the clamp: spill tier fails too

        def batches(self):
            raise RuntimeError("RESOURCE_EXHAUSTED: persistent")
            yield  # pragma: no cover

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        collect(_AlwaysOom(), fuse=False)


def test_tripped_tier_skipped_for_subsequent_queries():
    br = circuit.breaker("flow.fused")
    for _ in range(br.threshold):
        br.failure()
    assert br.state() == circuit.OPEN

    scan = _int_scan({"k": np.arange(32, dtype=np.int64) % 4,
                      "v": np.ones(32, dtype=np.int64)}, 8)
    agg = HashAggOp(scan, ["k"], [AggSpec("sum", "v", "s")])
    st = stats.enable()
    try:
        res = collect(agg, fuse=True)
    finally:
        stats.disable()
    assert sorted(zip(res["k"].tolist(), res["s"].tolist())) == \
        [(k, 8) for k in range(4)]
    assert "resilience.skip.fused" in st.stages  # open breaker skipped it
    assert "fused.exec" not in st.stages


def test_retry_exhaustion_steps_ladder_down():
    """A fault that keeps firing past the per-tier retry budget degrades
    to the next tier instead of failing the query."""
    registry().arm("fused.exec", probability=1.0)
    Settings().set(retry.RESILIENCE_MAX_RETRIES, 1)
    try:
        scan = _int_scan({"k": np.arange(16, dtype=np.int64) % 2,
                          "v": np.ones(16, dtype=np.int64)}, 8)
        agg = HashAggOp(scan, ["k"], [AggSpec("sum", "v", "s")])
        res = collect(agg, fuse=True)
    finally:
        Settings().set(retry.RESILIENCE_MAX_RETRIES, 6)
        registry().disarm()
    assert sorted(zip(res["k"].tolist(), res["s"].tolist())) == \
        [(0, 8), (1, 8)]


# ------------------------------------------------------ SQL error mapping --

def test_map_execution_error_pgcodes():
    from cockroach_tpu.sql.bind import BindError
    from cockroach_tpu.sql.session import map_execution_error

    mon = BytesMonitor("m", budget=1)
    acct = mon.make_account()
    try:
        acct.grow(100)
    except Exception as e:  # noqa: BLE001
        mapped = map_execution_error(e)
    assert mapped is not None and mapped.pgcode == "53200"

    scan = _int_scan({"k": np.arange(4, dtype=np.int64)}, 4)
    mapped = map_execution_error(FlowRestart(scan))
    assert mapped is not None and mapped.pgcode == "40001"

    mapped = map_execution_error(
        retry.RetriesExhausted("flow", 3, InjectedFault("x")))
    assert mapped is not None and mapped.pgcode == "40001"

    assert map_execution_error(BindError("no table")) is None
    assert map_execution_error(ValueError("x")) is None


def test_pgcode_helper():
    from cockroach_tpu.sql.pgwire import _pgcode
    from cockroach_tpu.sql.session import SQLError

    assert _pgcode(SQLError("53200", "oom")) == "53200"
    assert _pgcode(MemoryError("oom")) == "53200"
    assert _pgcode(ValueError("x")) == "42601"


def test_grace_join_abort_releases_spill_accounting():
    """A probe stream dying MID-Grace-partitioning must release the
    host-spill accounting as the flow unwinds (the partitioners are
    created before the replay loop's try/finally used to start)."""
    from cockroach_tpu.exec.operators import JoinOp
    from cockroach_tpu.exec.spill import host_spill_monitor

    build = {"bk": (np.arange(400, dtype=np.int64) % 200),
             "bv": np.arange(400, dtype=np.int64)}
    pschema = Schema([Field("pk", INT)])

    def probe_chunks():
        yield {"pk": np.arange(64, dtype=np.int64) % 200}
        raise ValueError("probe stream died")

    probe = ScanOp(pschema, probe_chunks, 64)
    # 1 KiB workmem: the 400-row build side Grace-spills mid-build
    join = JoinOp(probe, _int_scan(build, 64), ["pk"], ["bk"],
                  workmem=64 * 16)
    before = host_spill_monitor().used
    with pytest.raises(ValueError):
        collect(join, fuse=False)
    assert host_spill_monitor().used == before


def test_cache_insert_fault_degrades_to_miss():
    from cockroach_tpu.exec.scan_cache import ScanImageCache

    cache = ScanImageCache(budget=1 << 20)
    registry().arm("cache.insert", probability=1.0)
    try:
        assert cache.put(("k",), "value", 100) is False
    finally:
        registry().disarm()
    assert cache.get(("k",)) is None
    assert cache.put(("k",), "value", 100) is True
    assert cache.get(("k",)) == "value"


# ------------------------------ compile refusals vs too-large-for-HBM ----


class _RefusingLowered:
    """Stands in for a jax Lowered whose backend compile fails."""

    def __init__(self, msg):
        self.msg = msg

    def compile(self, *_a, **_k):
        raise RuntimeError(self.msg)


def _session_with_rows():
    from cockroach_tpu.sql.session import Session, SessionCatalog
    from cockroach_tpu.storage.engine import PyEngine
    from cockroach_tpu.storage.mvcc import MVCCStore
    from cockroach_tpu.util.hlc import HLC, ManualClock

    store = MVCCStore(PyEngine(), HLC(ManualClock(1000)))
    s = Session(SessionCatalog(store), capacity=64)
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values "
              + ", ".join(f"({i}, {i % 7})" for i in range(100)))
    s.execute("set vectorize = tpu")
    return s


def _compiler_says(monkeypatch, msg):
    from cockroach_tpu.exec import fused

    real = fused.FusedRunner._compile_lowered
    monkeypatch.setattr(
        fused.FusedRunner, "_compile_lowered",
        staticmethod(lambda _lowered: real(_RefusingLowered(msg))))


@pytest.mark.parametrize("msg", [
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space vmem. Used 17.5M of 16.0M vmem.",
    "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape",
])
def test_compile_refusal_fails_the_statement(monkeypatch, msg):
    """vmem / Mosaic: no lower tier answers in the program's place, and
    the client gets the compiler's words."""
    from cockroach_tpu.util.retry import CompileRefused

    s = _session_with_rows()
    _compiler_says(monkeypatch, msg)
    st = stats.enable()
    try:
        with pytest.raises(CompileRefused) as ei:
            s.execute("select k, v from t where v > 3 order by k limit 5")
    finally:
        stats.disable()
    assert msg[:60] in str(ei.value)
    assert not [n for n in st.stages
                if n.startswith(("resilience.degrade", "fused.fallback",
                                 "fused.stream_hbm"))], st.stages


def test_lowering_refusal_is_a_compile_refusal():
    import jax.numpy as jnp

    from cockroach_tpu.exec import fused
    from cockroach_tpu.util.retry import CompileRefused

    def prog(_x):
        raise NotImplementedError("Mosaic lowering: vmem out of memory")

    with pytest.raises(CompileRefused):
        fused.lower_program(prog, (jnp.zeros(4),))


def test_transient_compile_fault_is_retried_in_place(monkeypatch):
    """UNAVAILABLE / DEADLINE_EXCEEDED out of the backend's compile is a
    transient fault, not a refusal: with_retry("fused.compile") retries
    it and the fused tier answers."""
    from cockroach_tpu.exec import fused

    s = _session_with_rows()
    real = fused.FusedRunner._compile_lowered
    failures = ["UNAVAILABLE: connection to the compile service reset",
                "DEADLINE_EXCEEDED: compile rpc timed out"]

    def flaky(lowered):
        if failures:
            return real(_RefusingLowered(failures.pop()))
        return real(lowered)

    monkeypatch.setattr(fused.FusedRunner, "_compile_lowered",
                        staticmethod(flaky))
    st = stats.enable()
    try:
        _k, payload, _s = s.execute(
            "select k, v from t where v > 3 order by k limit 5")
    finally:
        stats.disable()
    assert [int(x) for x in payload["k"]] == [4, 5, 6, 11, 12]
    assert st.stage("resilience.retry.fused.compile").events == 2
    assert st.stage("fused.exec").events == 1
    assert not [n for n in st.stages
                if n.startswith(("resilience.degrade", "fused.fallback",
                                 "fused.stream_hbm"))], st.stages


def test_lowering_passes_the_programs_own_verdicts_by_type():
    """What tracer code raises on purpose passes through lower_program
    unchanged, chosen by exception type; a builtin it did not mean to
    raise is a refusal that names it."""
    import jax.numpy as jnp

    from cockroach_tpu.exec import fused
    from cockroach_tpu.util.cancel import QueryCancelled
    from cockroach_tpu.util.mon import BudgetExceededError
    from cockroach_tpu.util.retry import CompileRefused

    def raising(exc):
        def prog(_x):
            raise exc
        return prog

    x = (jnp.zeros(4),)
    for exc in (fused.Unsupported("outside the grammar"),
                InjectedFault("fused.compile"),
                QueryCancelled("statement timeout"),
                BudgetExceededError("workmem", 2, 1, 1),
                MemoryError("host")):
        with pytest.raises(type(exc)) as ei:
            fused.lower_program(raising(exc), x)
        assert ei.value is exc
    transient = RuntimeError("UNAVAILABLE: transfer failed")
    with pytest.raises(RuntimeError) as ei:
        fused.lower_program(raising(transient), x)
    assert ei.value is transient
    with pytest.raises(CompileRefused, match="KeyError"):
        fused.lower_program(raising(KeyError("l_orderkey")), x)


def test_hbm_step_to_streaming_is_counted_by_name(monkeypatch):
    """Too large for HBM is what the streaming tier is for: the statement
    answers, and says so under ONE name."""
    s = _session_with_rows()
    _compiler_says(
        monkeypatch,
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 20.1G of 15.75G hbm.")
    st = stats.enable()
    try:
        _k, payload, _s = s.execute(
            "select k, v from t where v > 3 order by k limit 5")
    finally:
        stats.disable()
    assert [int(x) for x in payload["k"]] == [4, 5, 6, 11, 12]
    assert st.stage(stats.STREAM_HBM).events == 1
