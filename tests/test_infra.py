"""L0 infrastructure tests: gossip (+ cluster wiring), admission
control, fault injection, and the BY_RANGE router (P5)."""

import struct
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cockroach_tpu.kv.kvserver import Cluster
from cockroach_tpu.util.admission import (
    ADMISSION_SLOTS, HIGH, LOW, WorkQueue,
)
from cockroach_tpu.util.fault import (
    FaultRegistry, InjectedFault, maybe_fail, registry,
)
from cockroach_tpu.util.gossip import Gossip
from cockroach_tpu.util.settings import Settings


def k(i: int) -> bytes:
    return struct.pack(">HQ", 1, i)


# -------------------------------------------------------------- gossip --

def test_gossip_propagates_and_versions_dominate():
    inboxes = {1: [], 2: [], 3: []}
    nodes = {}
    for i in (1, 2, 3):
        nodes[i] = Gossip(i, lambda to, infos: inboxes[to].append(infos),
                          [1, 2, 3])
    nodes[1].add_info("k", "v1")
    for _ in range(6):
        for g in nodes.values():
            g.step()
        for i, g in nodes.items():
            for infos in inboxes[i]:
                g.receive(infos)
            inboxes[i].clear()
    assert nodes[2].get_info("k") == "v1"
    assert nodes[3].get_info("k") == "v1"
    # newer version wins regardless of arrival order
    nodes[1].add_info("k", "v2")
    old = nodes[2].infos["k"]
    for _ in range(6):
        for g in nodes.values():
            g.step()
        for i, g in nodes.items():
            for infos in inboxes[i]:
                g.receive(infos)
            inboxes[i].clear()
    assert nodes[3].get_info("k") == "v2"
    nodes[3].receive([old])  # stale replay: must not regress
    assert nodes[3].get_info("k") == "v2"


def test_gossip_ttl_expiry():
    g = Gossip(1, lambda to, infos: None, [1])
    g.add_info("x", 1, ttl=3)
    assert g.get_info("x") == 1
    for _ in range(4):
        g.step()
    assert g.get_info("x") is None


def test_cluster_settings_propagate_via_gossip():
    c = Cluster(3, seed=31)
    c.await_leases()
    c.set_cluster_setting("sql.workmem", 123, via=1)
    c.pump(10)
    for i in c.nodes:
        assert c.nodes[i].settings_view.get("sql.workmem") == 123


def test_gossip_liveness_view_goes_stale_for_partitioned_node():
    c = Cluster(3, seed=32)
    c.await_leases()
    c.pump(5)
    assert c.liveness_view(1, 2)
    c.partitioned.add(2)
    c.pump(c.liveness.ttl + 20)
    # node 1's view of node 2 expires (no fresh gossip through the
    # partition); node 2 still sees itself
    assert not c.liveness_view(1, 2)
    assert c.liveness_view(2, 2)
    c.partitioned.clear()
    c.pump(10)
    assert c.liveness_view(1, 2)


# ----------------------------------------------------------- admission --

def test_workqueue_bounds_concurrency_and_prefers_priority():
    q = WorkQueue(1)
    order = []
    with q.admit():
        # start two waiters; HIGH must win the slot
        def worker(prio, tag):
            with q.admit(priority=prio, timeout=10):
                order.append(tag)

        lo = threading.Thread(target=worker, args=(LOW, "low"))
        lo.start()
        time.sleep(0.05)
        hi = threading.Thread(target=worker, args=(HIGH, "high"))
        hi.start()
        time.sleep(0.05)
    lo.join(5)
    hi.join(5)
    assert order == ["high", "low"]


def test_admit_timeout_recheck_claims_freed_slot(monkeypatch):
    """A release() landing in the window between the wait timing out and
    the waiter reacquiring the lock must ADMIT the waiter, not shed it —
    the timed-out-but-now-eligible re-check."""
    q = WorkQueue(1)
    q._available = 0  # slot currently held elsewhere

    def racy_wait(timeout=None):
        # the holder releases exactly as our wait times out
        q._available += 1
        return False

    monkeypatch.setattr(q._cv, "wait", racy_wait)
    admitted = False
    with q.admit(timeout=5):
        admitted = True
    assert admitted


def test_admit_timeout_sheds_and_counts():
    from cockroach_tpu.util.metric import default_registry

    q = WorkQueue(1)
    cnt = default_registry().counter("admission.timeouts_total")
    before = cnt.value()
    with q.admit():
        with pytest.raises(TimeoutError):
            with q.admit(timeout=0.01):
                pass
    assert cnt.value() - before == 1
    # shed load is visible on /_status/vars
    assert "admission.timeouts_total" in \
        default_registry().export_prometheus()


def test_workqueue_low_priority_not_starved():
    """Sustained HIGH traffic must not pin a LOW waiter forever: the
    anti-starvation rotation hands every Nth grant to the oldest waiter,
    so the LOW request admits while HIGH work is still arriving."""
    q = WorkQueue(1)
    order = []
    done = threading.Event()

    def low_worker():
        with q.admit(priority=LOW, timeout=30):
            order.append("low")
        done.set()

    def high_worker(i):
        with q.admit(priority=HIGH, timeout=30):
            order.append(f"high{i}")
            time.sleep(0.01)

    with q.admit():  # hold the slot so everyone below queues behind it
        lo = threading.Thread(target=low_worker)
        lo.start()
        time.sleep(0.05)  # LOW is the oldest waiter
        highs = [threading.Thread(target=high_worker, args=(i,))
                 for i in range(3 * WorkQueue.ANTI_STARVATION_EVERY)]
        for t in highs:
            t.start()
            time.sleep(0.01)
    assert done.wait(20), "LOW waiter starved"
    lo.join(5)
    for t in highs:
        t.join(5)
    # LOW admitted before the HIGH stream fully drained (rotation), not
    # merely last-by-default once all HIGH work happened to finish
    assert order.index("low") < len(order) - 1
    assert q.used.value() == 0 and q.waiting.value() == 0


def test_flow_queue_slot_swap_reuses_gauges():
    """Changing sql.tpu.admission_slots swaps the queue; the registry
    gauges are REUSED (same objects, live queue's values) rather than
    orphaned copies of the old queue's state."""
    from cockroach_tpu.util.admission import flow_queue
    from cockroach_tpu.util.metric import default_registry

    s = Settings()
    prev = s.get(ADMISSION_SLOTS)
    try:
        s.set(ADMISSION_SLOTS, 2)
        q1 = flow_queue()
        s.set(ADMISSION_SLOTS, 3)
        q2 = flow_queue()
        assert q1 is not q2
        assert q1.used is q2.used and q1.waiting is q2.waiting
        # a late release on the retired queue must not clobber the live
        # queue's published gauge
        reg = default_registry()
        q2.acquire()
        q1.release()
        assert reg.gauge("flow.slots_used").value() == 1
        q2.release()
        assert reg.gauge("flow.slots_used").value() == 0
    finally:
        s.set(ADMISSION_SLOTS, prev)


def test_admission_gates_flow_runtime():
    from cockroach_tpu.exec import collect
    from cockroach_tpu.sql import TPCHCatalog, run_sql
    from cockroach_tpu.workload.tpch import TPCH

    s = Settings()
    prev = s.get(ADMISSION_SLOTS)
    s.set(ADMISSION_SLOTS, 2)
    try:
        gen = TPCH(sf=0.01)
        got = run_sql("select count(*) as n from nation",
                      TPCHCatalog(gen), capacity=64)
        assert int(got["n"][0]) == 25
        from cockroach_tpu.util.admission import flow_queue

        q = flow_queue()
        assert q is not None and q.used.value() == 0  # released
    finally:
        s.set(ADMISSION_SLOTS, prev)


# --------------------------------------------------------------- fault --

def test_fault_injection_counted_and_probabilistic():
    r = FaultRegistry(seed=1)
    r.arm("p1", after=2)
    r.maybe_fail("p1")
    r.maybe_fail("p1")
    with pytest.raises(InjectedFault):
        r.maybe_fail("p1")
    r.maybe_fail("p1")  # once only
    r.arm("p2", probability=1.0)
    with pytest.raises(InjectedFault):
        r.maybe_fail("p2")
    r.disarm()
    r.maybe_fail("p2")  # disarmed: no-op


def test_fault_global_registry_fast_path():
    registry().disarm()
    maybe_fail("anything")  # unarmed: free
    registry().arm("x", probability=1.0,
                   make=lambda: ValueError("custom"))
    with pytest.raises(ValueError):
        maybe_fail("x")
    registry().disarm()


# ------------------------------------------------------- range routing --

def test_range_repartition_local_on_mesh():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.parallel import make_mesh
    from cockroach_tpu.parallel.repartition import (
        range_repartition_local,
    )

    n_dev = 8
    mesh = make_mesh(n_dev)
    per_dev = 64
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 800, n_dev * per_dev).astype(np.int64)
    vals = np.arange(n_dev * per_dev, dtype=np.int64)
    sel = rng.random(n_dev * per_dev) > 0.2
    batch = Batch({"key": Column(jnp.asarray(keys)),
                   "v": Column(jnp.asarray(vals))},
                  jnp.asarray(sel),
                  jnp.asarray(int(sel.sum()), dtype=jnp.int32))
    boundaries = jnp.asarray([100 * i for i in range(1, n_dev)],
                             dtype=jnp.int64)

    def local(b):
        out, overflow = range_repartition_local(
            b, "key", boundaries, "x", n_dev, bucket_cap=256)
        return out, jax.lax.psum(overflow.astype(jnp.int32), "x") > 0

    from cockroach_tpu.parallel.repartition import _batch_pspecs

    in_specs = _batch_pspecs(batch, "x")
    f = shard_map(local, mesh=mesh,
                  in_specs=(in_specs,),
                  out_specs=(_batch_pspecs(batch, "x"), P()),
                  check_rep=False)
    out, overflow = f(batch)
    assert not bool(np.asarray(overflow))
    # every surviving row landed on the device owning its key range
    okeys = np.asarray(out.col("key").values).reshape(n_dev, -1)
    osel = np.asarray(out.sel).reshape(n_dev, -1)
    for d in range(n_dev):
        mine = okeys[d][osel[d]]
        lo = 0 if d == 0 else 100 * d
        hi = 800 if d == n_dev - 1 else 100 * (d + 1)
        assert ((mine >= lo) & (mine < hi)).all(), d
    # conservation
    assert osel.sum() == sel.sum()


# ----------------------------------------- the one compile-cache resolver --

def test_compile_cache_env_var_wins_and_sets_no_directory(monkeypatch,
                                                          tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set JAX reads the variable itself:
    the resolver reports it and sets NO directory — not the setting's,
    not a caller's default."""
    import jax

    from cockroach_tpu.util import compile_cache as cc
    from cockroach_tpu.util.settings import COMPILATION_CACHE_DIR, Settings

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(cc, "_resolved", cc._resolved)  # restored after
    x = str(tmp_path / "x")
    monkeypatch.setenv(cc.ENV_VAR, x)
    Settings().set(COMPILATION_CACHE_DIR, "/from/the/setting")
    try:
        assert cc.resolve(default="/a/default") == x
        assert cc.enable_persistent_cache(default="/a/default") == x
        assert cc.enable_persistent_cache() == x
    finally:
        Settings().set(COMPILATION_CACHE_DIR, "")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout_dot_jax_cache(monkeypatch):
    import os

    import jax

    from cockroach_tpu.util import compile_cache as cc

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(cc, "_resolved", None)  # as at first import
    try:
        assert cc.resolve() == os.path.join(checkout, ".jax_cache")
        # a later call without a default leaves a caller's choice alone
        assert cc.enable_persistent_cache(default=before) == before
        assert cc.enable_persistent_cache() == before
    finally:
        cc.enable_persistent_cache(default=before)
    assert jax.config.jax_compilation_cache_dir == before


def test_only_the_resolver_touches_the_cache_directory():
    """No module but util/compile_cache.py updates
    jax_compilation_cache_dir, and no cache path is made from a temporary
    name, a pid or the time."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setter = re.compile(r"update\(\s*[\"']jax_compilation_cache_dir")
    offenders = []
    files = [os.path.join(root, "chip_smoke.py"),
             os.path.join(root, "__graft_entry__.py")]
    for sub in ("cockroach_tpu", "scripts", "tests"):
        for d, _dirs, names in os.walk(os.path.join(root, sub)):
            files += [os.path.join(d, n) for n in names
                      if n.endswith(".py")]
    resolver = os.path.join(root, "cockroach_tpu", "util",
                            "compile_cache.py")
    for path in files:
        if path == resolver or not os.path.exists(path):
            continue
        with open(path) as f:
            if setter.search(f.read()):
                offenders.append(os.path.relpath(path, root))
    assert not offenders, offenders
    with open(resolver) as f:
        src = f.read()
    assert len(setter.findall(src)) == 1
    for word in ("mkdtemp", "getpid", "time.time", "tempfile"):
        assert word not in src, word
