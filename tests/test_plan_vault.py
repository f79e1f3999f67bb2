"""Plan vault + pre-warm jobs: the cold-start elimination stack.

Covers the ISSUE-9 contract: restart-warm round-trip (a fresh runner —
the in-process proxy for a fresh process, whose true form the
scripts/check_cold_start.py subprocess gate exercises — serves from the
vault without recompiling, bit-exact), DDL/ANALYZE and environment
(jax-version) invalidation, corrupt-artifact rejection falling back to
JIT, and plan_prewarm job resume-from-checkpoint after a mid-prewarm
kill.
"""

import os

import numpy as np
import pytest

from cockroach_tpu.exec import stats
from cockroach_tpu.server import prewarm as prewarm_mod
from cockroach_tpu.sql.session import Session, SessionCatalog
from cockroach_tpu.storage.engine import PyEngine
from cockroach_tpu.storage.mvcc import MVCCStore
from cockroach_tpu.util import plan_vault as pv
from cockroach_tpu.util.hlc import HLC, ManualClock
from cockroach_tpu.util.settings import Settings

Q = "SELECT k, v FROM t WHERE v > 5 ORDER BY k LIMIT 10"


@pytest.fixture
def vault_dir(tmp_path):
    # The suite's persistent XLA cache must be off here: an executable
    # that was itself an XLA-cache HIT re-serializes without its compiled
    # symbols on CPU PjRt, so the vault (correctly) refuses to store it —
    # which would make these round-trip tests depend on whether a prior
    # run already warmed .jax_cache_cpu. Fresh compiles serialize fine.
    from cockroach_tpu.util.compile_cache import persistent_cache_disabled

    d = str(tmp_path / "vault")
    with persistent_cache_disabled():
        Settings().set(pv.PLAN_VAULT_DIR, d)
        try:
            yield d
        finally:
            Settings().set(pv.PLAN_VAULT_DIR, "")


def _session(rows: int = 400, capacity: int = 256):
    store = MVCCStore(PyEngine(), HLC(ManualClock(1000)))
    cat = SessionCatalog(store)
    s = Session(cat, capacity=capacity)
    s.execute("CREATE TABLE t (k INT PRIMARY KEY, v INT)")
    s.execute("INSERT INTO t VALUES "
              + ",".join(f"({i},{i * 3 % 17})" for i in range(rows)))
    return s


def _rows(payload):
    return {c: np.asarray(payload[c]) for c in payload}


def _run(sess, sql=Q):
    _kind, payload, _schema = sess.execute(sql)
    return _rows(payload)


# ------------------------------------------------------ vault round trip --


def test_restart_warm_round_trip_bit_exact(vault_dir):
    """Process 1 populates the vault; a fresh session+runner over fresh
    storage (the restart proxy: nothing shared but the vault dir) serves
    its FIRST execution from the vault — no XLA compile — bit-exact."""
    # both schemas exist BEFORE the first store: s2's CREATE TABLE is DDL
    # and would (correctly) garbage-collect artifacts tagged "t". A real
    # restart re-opens persistent storage — it never replays the DDL.
    s1 = _session()
    s2 = _session()  # fresh catalog/store/session: plans rebuild
    st = stats.enable()
    cold = _run(s1)
    sd = st.as_dict()
    assert sd.get("compile.vault_store", {}).get("events", 0) >= 1
    assert len(pv.plan_vault().entries()) >= 1

    st2 = stats.enable()
    warm = _run(s2)
    sd2 = st2.as_dict()
    assert sd2.get("compile.vault_hit", {}).get("events", 0) >= 1, sd2
    assert sd2.get("compile.vault_miss", {}).get("events", 0) == 0, sd2
    assert set(cold) == set(warm)
    for c in cold:
        np.testing.assert_array_equal(cold[c], warm[c])


def test_vault_artifacts_tagged_with_tables(vault_dir):
    s = _session()
    _run(s)
    tags = [e["tables"] for e in pv.plan_vault().entries()]
    assert any("t" in t for t in tags), tags


def test_first_execution_metric_recorded(vault_dir):
    from cockroach_tpu.util.metric import default_registry

    s = _session()
    st = stats.enable()
    _run(s)
    assert st.as_dict().get("fused.first_execution", {}) \
                       .get("events", 0) == 1
    h = default_registry().histogram("sql_first_execution_seconds")
    assert h._n >= 1


# --------------------------------------------------------- invalidation --


def test_env_version_mismatch_never_serves(vault_dir, monkeypatch):
    """An artifact written under another jax/jaxlib is rejected at load
    even when its key matches byte-for-byte (copied vault dirs)."""
    s1 = _session()
    _run(s1)
    vault = pv.plan_vault()
    entries = vault.entries()
    assert entries
    # rewrite every artifact header as if another jax had produced it
    import json
    for name in os.listdir(vault.directory):
        if not name.endswith(".planv"):
            continue
        path = os.path.join(vault.directory, name)
        with open(path, "rb") as f:
            header = json.loads(f.readline().decode())
            body = f.read()
        header["env"] = dict(header["env"], jax="0.0.0-other")
        import hashlib
        header["sha256"] = hashlib.sha256(body).hexdigest()
        with open(path, "wb") as f:
            f.write(json.dumps(header, sort_keys=True).encode()
                    + b"\n" + body)
    key = entries[0]["key"]
    assert vault.load(key) is None  # stale env: refuse, fall back to JIT


def test_ddl_invalidates_tagged_artifacts(vault_dir):
    s = _session()
    _run(s)
    vault = pv.plan_vault()
    assert len(vault.entries()) >= 1
    s.execute("ALTER TABLE t ADD COLUMN w INT")
    assert all("t" not in e["tables"] for e in vault.entries()), \
        vault.entries()


def test_analyze_invalidates_tagged_artifacts(vault_dir):
    s = _session()
    _run(s)
    vault = pv.plan_vault()
    assert len(vault.entries()) >= 1
    s.execute("ANALYZE t")
    assert len(vault.entries()) == 0


def test_corrupt_artifact_falls_back_to_jit(vault_dir):
    """Flipping bytes in an artifact body must not poison the query:
    load rejects on digest mismatch, the runner compiles normally, and
    results stay correct."""
    s1 = _session()
    s2 = _session()  # built BEFORE the store: its DDL must not GC "t"
    cold = _run(s1)
    vault = pv.plan_vault()
    for name in os.listdir(vault.directory):
        if name.endswith(".planv"):
            path = os.path.join(vault.directory, name)
            blob = open(path, "rb").read()
            # corrupt the tail (inside the pickled executable payload)
            open(path, "wb").write(blob[:-16] + b"\x00" * 16)
    st = stats.enable()
    warm = _run(s2)
    sd = st.as_dict()
    assert sd.get("compile.vault_corrupt", {}).get("events", 0) >= 1, sd
    assert sd.get("compile.vault_hit", {}).get("events", 0) == 0
    for c in cold:
        np.testing.assert_array_equal(cold[c], warm[c])
    # the rejected artifact was quarantined, then re-stored fresh
    assert len(vault.entries()) >= 1


# ------------------------------------------------------------- aot ladder --


def test_aot_compile_ladder_populates_vault(vault_dir):
    s = _session()
    _run(s)
    prep = s._prepared_lookup(Q)
    assert prep is not None
    runner = getattr(prep.op, "_fused_runner", None)
    assert runner is not None
    before = len(pv.plan_vault().entries())
    n = runner.aot_compile(extra_buckets=2)
    assert n == 3  # current bucket + two doublings
    assert len(pv.plan_vault().entries()) == before + 2


# ---------------------------------------------------------- prewarm jobs --


def test_prepare_enqueues_background_job(vault_dir):
    Settings().set(prewarm_mod.PREWARM_ENABLED, True)
    try:
        s = _session()
        _run(s)  # cold exec -> prepared store -> note_prepared
        svc = prewarm_mod.service_for(s.catalog, 256)
        jobs = [j for j in svc.registry.list_jobs()
                if j.kind == prewarm_mod.JOB_KIND]
        assert len(jobs) == 1
        assert jobs[0].payload["tasks"][0]["kind"] == "prepared"
        # enqueue-only at PREPARE time: foreground never compiled the
        # ladder; the job does, when the worker drains it
        svc.run_pending()
        rec = svc.registry.get(jobs[0].id)
        assert rec.state == "succeeded"
        assert rec.progress["done"] == rec.progress["total"]
    finally:
        Settings().set(prewarm_mod.PREWARM_ENABLED, False)


def test_prewarm_job_resumes_from_checkpoint_after_kill(vault_dir):
    """A mid-prewarm kill (process death: resumer raises through
    adopt_and_run without reaching a terminal state) leaves a RUNNING
    record with a checkpoint; after the lease expires, re-adoption
    resumes at the checkpoint instead of restarting task 0."""
    s = _session()
    svc = prewarm_mod.service_for(s.catalog, 256)
    tasks = [{"kind": "serving", "table": "t", "cols": ["v"],
              "window": 128, "buckets": [b], "capacity": 256}
             for b in (1, 2, 4)]
    job_id = svc.enqueue(tasks)

    done_kinds = []
    real = svc._run_task

    def dying(task):
        if len(done_kinds) == 2:
            raise KeyboardInterrupt  # simulated kill: tasks 1-2 ran and
            # checkpointed; the process dies entering task 3
        done_kinds.append(task)
        real(task)

    svc._run_task = dying
    with pytest.raises(KeyboardInterrupt):
        svc.run_pending()
    svc._run_task = real
    rec = svc.registry.get(job_id)
    assert rec.state == "running"  # never reached a terminal state
    assert rec.progress == {"done": 2, "total": 3}

    # "restart": a new registry holder adopts after the lease expires
    s.catalog.store.clock._wall_fn.advance(10_000)  # past the lease TTL
    svc2 = prewarm_mod.PrewarmService(s.catalog, 256)
    ran = svc2.run_pending()
    assert job_id in ran
    rec = svc2.registry.get(job_id)
    assert rec.state == "succeeded"
    # resumed AT the checkpoint: only the third task re-ran
    assert rec.progress == {"done": 3, "total": 3}


def test_prewarm_job_cancel_fences_running_holder(vault_dir):
    s = _session()
    svc = prewarm_mod.service_for(s.catalog, 256)
    job_id = svc.enqueue([{"kind": "serving", "table": "t",
                           "cols": ["v"], "window": 128, "buckets": [1],
                           "capacity": 256}])
    svc.registry.cancel(job_id)
    svc.run_pending()
    assert svc.registry.get(job_id).state == "cancelled"


def test_prewarm_enqueue_never_blocks_on_compile(vault_dir):
    """enqueue() persists a record and returns — no planning, no
    compilation on the caller's clock."""
    import time

    s = _session()
    svc = prewarm_mod.service_for(s.catalog, 256)
    t0 = time.perf_counter()
    svc.enqueue([{"kind": "prepared", "sql": Q, "capacity": 256,
                  "extra_buckets": 4}])
    assert time.perf_counter() - t0 < 0.5  # a put, not a compile


def test_serving_prewarm_shape_job_round_trip(vault_dir):
    """A serving task rebuilds the runner and compiles its buckets
    vault-first; a second fresh queue rebuild loads, not compiles."""
    from cockroach_tpu.sql.serving import ServingQueue

    s = _session()
    q1 = ServingQueue()
    st = stats.enable()
    n = q1.prewarm_shape(s.catalog, 256, "t", ("v",), 128, [1, 2, 4])
    assert n == 3
    stores = st.as_dict().get("compile.vault_store", {}).get("events", 0)
    assert stores >= 3

    q2 = ServingQueue()  # restart proxy: nothing shared but the vault
    st2 = stats.enable()
    assert q2.prewarm_shape(s.catalog, 256, "t", ("v",), 128,
                            [1, 2, 4]) == 3
    sd2 = st2.as_dict()
    assert sd2.get("compile.vault_hit", {}).get("events", 0) >= 3, sd2


# ------------------------------------------- devices + compile refusals --


def test_one_device_artifact_loads_in_eight_device_process(vault_dir):
    """serialize_executable drops the device assignment and
    deserialize_and_load defaults to EVERY device of the backend: the
    vault records the devices in the artifact header, so a program
    compiled for one device loads — and runs — as a one-device program
    in this eight-device process (and on a four-chip host)."""
    import jax
    import jax.numpy as jnp

    assert len(jax.devices()) == 8
    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(16, dtype=jnp.int64), dev)
    lowered = jax.jit(lambda a: a * 3 + 1).lower(x)
    vault = pv.plan_vault()
    key = vault.key_for(lowered.as_text())
    assert vault.store(key, lowered.compile(), tables=("t",))
    (entry,) = [e for e in vault.entries() if e["key"] == key]
    loaded = vault.load(key)
    assert loaded is not None
    out = loaded(x)
    assert out.devices() == {dev}
    np.testing.assert_array_equal(np.asarray(out), np.arange(16) * 3 + 1)


@pytest.mark.parametrize("error, counter", [
    ("backend", "compile.vault_unsupported"),
    ("ours", "compile.vault_store_error"),
])
def test_store_failures_keep_separate_names(vault_dir, monkeypatch,
                                            error, counter):
    """"The backend cannot serialize this" and "our call was wrong" are
    two counters; neither takes the compiled program from the query."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as se

    def refuse(_compiled):
        if error == "backend":
            raise jax.errors.JaxRuntimeError(
                "UNIMPLEMENTED: executable type does not serialize")
        raise TypeError("serialize() got an unexpected keyword argument")

    monkeypatch.setattr(se, "serialize", refuse)
    x = jnp.arange(4, dtype=jnp.int64)
    lowered = jax.jit(lambda a: a + 1).lower(x)
    compiled = lowered.compile()
    vault = pv.plan_vault()
    st = stats.enable()
    try:
        assert vault.store(vault.key_for(lowered.as_text()),
                           compiled) is False
    finally:
        stats.disable()
    assert st.stage(counter).events == 1
    assert len([n for n in st.stages
                if n.startswith("compile.vault_")]) == 1, st.stages
    assert not vault.entries()
    np.testing.assert_array_equal(np.asarray(compiled(x)), [1, 2, 3, 4])


def test_compile_refusal_is_terminal_device_loss_steps_down():
    """A compile-time refusal is the program's defect (TERMINAL, whatever
    OOM words it holds); losing a device, or running out of memory while
    EXECUTING, still steps the ladder down."""
    from cockroach_tpu.exec import fused
    from cockroach_tpu.parallel.mesh import DeviceLost
    from cockroach_tpu.util import retry

    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space vmem. Used 17.5M of 16.0M vmem.")
    assert retry.classify(vmem) == retry.RESOURCE  # raised at execution
    refused = fused._refusal("compile", vmem)
    assert isinstance(refused, retry.CompileRefused)
    assert "memory space vmem" in str(refused)  # the compiler's words
    assert retry.classify(refused) == retry.TERMINAL
    mosaic = fused._refusal("lowering", NotImplementedError(
        "Mosaic failed to compile TPU kernel: out of memory"))
    assert retry.classify(mosaic) == retry.TERMINAL
    hbm = fused._refusal("compile", RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 20.1G of 15.75G hbm."))
    assert isinstance(hbm, fused.HBMExceeded)  # -> streaming, counted
    assert retry.classify(DeviceLost("chip 2 gone")) == retry.RESOURCE
    assert retry.classify(MemoryError()) == retry.RESOURCE
