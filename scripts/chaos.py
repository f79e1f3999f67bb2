"""Chaos harness: TPC-H under randomized fault arming.

For every (query, fault point) pair: run the flow fault-free to get a
baseline, then re-run with the point armed at a fire probability and
assert the results are BIT-IDENTICAL — the resilience layer (seam
retries, the run_flow degradation ladder, grace spill) must absorb every
injected fault without changing the answer. The reference's analog is
the colexecerror + TestingKnobs chaos configs: the same fixture corpus
re-run under forced failures.

Also runs a spill-forcing aggregation (Q18 under a 16 KiB workmem, the
north-star config #4 shape) with the spill seams armed, so the
out-of-core block write/read retry paths see chaos too.

Run: JAX_PLATFORMS=cpu python scripts/chaos.py
     [--queries 1,3,18] [--points scan.transfer,...] [--prob 0.3]
     [--sf 0.01] [--log2-capacity 13] [--seed 0] [--no-spill]
     [--cluster]      kill a scanned range's leaseholder mid-query
     [--concurrent]   16 pgwire client threads of mixed YCSB-E +
                      TPC-H trickle + vector queries under p=0.2
                      faults, random CancelRequests, and a mid-run
                      drain/restart — bit-exact vs a serial reference,
                      zero deadlocks / leaked admission slots, p50/p99
                      latencies in the report JSON
     [--crash]        kill -9 nemesis: child processes killed at
                      randomized durable-write crash points (plus torn
                      tails, corrupted bytes, and full-SQL rounds);
                      every restart must recover bit-exactly
                      [--rounds 20]
Exits non-zero on any result mismatch.
"""

import argparse
import json
import os
import random
import socket
import struct
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# must precede any jax import: chaos runs on the CPU backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the seams a plain in-HBM query crosses (spill.* need a forced-spill
# flow and are exercised by the --spill config below)
DEFAULT_POINTS = ("scan.transfer", "scan.stack", "fused.compile",
                  "fused.exec", "cache.insert")
SPILL_POINTS = ("scan.transfer", "spill.block_write", "spill.block_read")

_COUNTERS = ("sql_resilience_retries_total",
             "sql_resilience_degradations_total",
             "sql_resilience_breaker_trips_total",
             "sql_flow_restarts_total",
             "sql_scan_failovers_total")


def _setup_jax():
    """CPU backend + the shared persistent compile cache (conftest's)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from cockroach_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache(default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache_cpu"))


def _sorted_rows(res, names):
    import numpy as np

    cols = [np.asarray(res[n]) for n in names]
    order = np.lexsort(cols[::-1])
    return [tuple(c[i] for c in cols) for i in order]


def _counters():
    from cockroach_tpu.util.metric import default_registry

    reg = default_registry()
    return {n: reg.counter(n).value() for n in _COUNTERS}


def run_case(make_flow, baseline_rows, names, point, prob, seed):
    """One armed run vs. the fault-free baseline; returns a report dict."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.util import circuit
    from cockroach_tpu.util.fault import registry

    # each case starts from closed breakers, a cold scan-image cache (a
    # warm one would skip the scan seams entirely) and a known RNG
    # stream, so a case's verdict never depends on what ran before it
    circuit.reset_all()
    from cockroach_tpu.exec.scan_cache import scan_image_cache

    scan_image_cache().clear()
    reg = registry()
    reg.set_seed(seed)
    reg.arm(point, probability=prob)
    before = _counters()
    t0 = time.monotonic()
    try:
        got = collect(make_flow())
    finally:
        fires = reg.fires(point)
        reg.disarm(point)
    after = _counters()
    return {
        "point": point,
        "ok": _sorted_rows(got, names) == baseline_rows,
        "fires": fires,
        "elapsed_s": round(time.monotonic() - t0, 3),
        "deltas": {k.replace("sql_", "").replace("_total", ""):
                   after[k] - before[k] for k in _COUNTERS},
    }


def _zero_backoff():
    """Chaos runs retry a lot by design; don't sleep through them."""
    from cockroach_tpu.util.retry import RESILIENCE_INITIAL_BACKOFF
    from cockroach_tpu.util.settings import Settings

    Settings().set(RESILIENCE_INITIAL_BACKOFF, 0.0)


def run_chaos(queries=(1, 3, 18), points=DEFAULT_POINTS, prob=0.3,
              sf=0.01, capacity=1 << 13, seed=0, spill=True,
              emit=print):
    """Full chaos sweep; returns the list of per-case report dicts."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.util.settings import Settings, WORKMEM
    from cockroach_tpu.workload import tpch_queries as Q
    from cockroach_tpu.workload.tpch import TPCH

    _zero_backoff()
    gen = TPCH(sf=sf)
    report = []

    def sweep(label, make_flow, pts, case_seed):
        flow = make_flow()
        names = [f.name for f in flow.schema]
        baseline = _sorted_rows(collect(flow), names)
        for i, point in enumerate(pts):
            r = run_case(make_flow, baseline, names, point, prob,
                         case_seed + i)
            r["query"] = label
            report.append(r)
            emit("%-12s %-18s %-4s fires=%-3d %6.2fs %s" % (
                label, point, "ok" if r["ok"] else "FAIL", r["fires"],
                r["elapsed_s"],
                json.dumps({k: v for k, v in r["deltas"].items() if v})))

    for qn in queries:
        # q18's second positional is the threshold, not the capacity
        def make_flow(qn=qn):
            if qn == 18:
                return Q.q18(gen, capacity=capacity)
            return Q.QUERIES[qn](gen, capacity)

        sweep("q%d" % qn, make_flow, points, seed + 100 * qn)

    if spill:
        # north-star config #4 shape: Q18 under a 16 KiB workmem grace-
        # spills its big GROUP BY, so the block write/read seams fire
        s = Settings()
        old = s.get(WORKMEM)
        s.set(WORKMEM, 1 << 14)
        try:
            sweep("q18-spill",
                  lambda: Q.q18(gen, threshold=50, capacity=1024),
                  SPILL_POINTS, seed + 9000)
        finally:
            s.set(WORKMEM, old)

    return report


# ------------------------------------------------- cluster nemesis mode

_QUERY_TABLES = {1: ("lineitem",),
                 3: ("customer", "orders", "lineitem"),
                 18: ("customer", "orders", "lineitem")}


def _cluster_catalog(cluster, loaded, on_chunk=None):
    """A fresh ClusterCatalog over the same loaded tables (same read
    timestamp, so every run observes the identical table image)."""
    from cockroach_tpu.parallel.spans import ClusterCatalog

    return ClusterCatalog(cluster, loaded.tables, rows=loaded.rows,
                          ts=loaded.ts, pks=loaded.pks,
                          stats=loaded.stats, on_chunk=on_chunk)


def run_cluster_chaos(queries=(1, 3, 18), sf=0.01, capacity=1 << 13,
                      seed=0, kill_after_chunks=2, emit=print):
    """Cluster-level nemesis: each query runs over a 3-node replicated
    Cluster; mid-scan the nemesis kills the leaseholder of the range
    being scanned. The per-range failover resume (parallel/spans.py)
    must finish the query bit-exact vs the no-chaos run WITHOUT a
    whole-query restart. Afterwards the victim restarts and must catch
    up through an engine snapshot (live leaders compact their raft logs
    first, forcing InstallSnapshot), and a post-recovery run must again
    be bit-exact."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.kv.kvserver import Cluster
    from cockroach_tpu.kv.raft import LEADER
    from cockroach_tpu.workload import tpch_queries as Q
    from cockroach_tpu.workload.tpch import TPCH

    _zero_backoff()
    gen = TPCH(sf=sf)
    report = []
    for qn in queries:
        cluster = Cluster(3, seed=seed + qn)
        loaded = gen.cluster_load(cluster, _QUERY_TABLES[qn])

        def make_flow(catalog, qn=qn):
            if qn == 18:
                return Q.q18(gen, capacity=capacity, catalog=catalog)
            return Q.QUERIES[qn](gen, capacity, catalog=catalog)

        flow = make_flow(loaded)
        names = [f.name for f in flow.schema]
        baseline = _sorted_rows(collect(flow), names)

        killed = []

        def nemesis(part, idx, cluster=cluster, killed=killed):
            # one kill per query, mid-stream: the scanned range's OWN
            # leaseholder dies between two of its chunks
            if not killed and idx >= kill_after_chunks:
                killed.append(part.node_id)
                cluster.kill(part.node_id)

        before = _counters()
        t0 = time.monotonic()
        got = _sorted_rows(
            collect(make_flow(_cluster_catalog(cluster, loaded,
                                               on_chunk=nemesis))),
            names)
        after = _counters()

        # recovery: compact live leaders' logs so the victim's rejoin
        # MUST go through the engine snapshot seam, then re-run
        recovered = None
        if killed:
            for node in cluster.nodes.values():
                if node.id == killed[0]:
                    continue
                for rep in node.replicas.values():
                    if rep.raft.role == LEADER:
                        rep.raft.compact(rep.raft.applied,
                                         rep._make_snapshot())
            cluster.restart(killed[0])
            cluster.pump(200)
            cluster.await_leases()
            post = _sorted_rows(
                collect(make_flow(_cluster_catalog(cluster, loaded))),
                names)
            recovered = post == baseline
        r = {
            "query": "q%d" % qn,
            "point": "cluster.kill_leaseholder",
            "ok": got == baseline and bool(killed)
            and recovered is not False,
            "fires": len(killed),
            "elapsed_s": round(time.monotonic() - t0, 3),
            "deltas": {k.replace("sql_", "").replace("_total", ""):
                       after[k] - before[k] for k in _COUNTERS},
        }
        report.append(r)
        emit("%-12s %-22s %-4s killed=n%s %6.2fs recovered=%s %s" % (
            r["query"], r["point"], "ok" if r["ok"] else "FAIL",
            killed[0] if killed else "-", r["elapsed_s"], recovered,
            json.dumps({k: v for k, v in r["deltas"].items() if v})))
    return report


# ------------------------------------------- concurrent serving nemesis
#
# The fixtures (wire client, serving catalog, query pool) live in
# cockroach_tpu/workload/servebench.py so the smoke gates drive the
# SAME tables and queries this nemesis does; the aliases keep
# this module's internal names stable.


def _servebench():
    from cockroach_tpu.workload import servebench

    return servebench


def _WireClient(addr, timeout=120.0):
    return _servebench().WireClient(addr, timeout=timeout)


def _send_cancel(addr, pid, secret):
    return _servebench().send_cancel(addr, pid, secret)


def _load_serving_catalog():
    return _servebench().load_serving_catalog()


def _query_pool():
    return _servebench().query_pool()


def _percentiles(lat):
    return _servebench().percentiles(lat)


def run_concurrent_chaos(threads=16, ops_per_thread=24, prob=0.2,
                         seed=0, slots=4, drain_mid_run=True,
                         cancel_period_s=0.08, serving=True, emit=print):
    """N pgwire client threads against one server under chaos: p=`prob`
    fault arming on the execution seams, a nemesis thread firing random
    CancelRequests, and a mid-run drain + restart on the same catalog.
    Reads verify bit-exact against a serial fault-free reference; the
    report carries p50/p99 latencies per workload class, aggregate and
    per-class throughput, the serving-queue coalescing stats, the drain
    summaries, and the leaked-slot check. `serving=False` runs the same
    chaos with cross-session batching off — the unbatched baseline the
    3x throughput gate compares against. Returns the report dict."""
    from cockroach_tpu.sql import serving as _serving
    from cockroach_tpu.sql.pgwire import PgServer
    from cockroach_tpu.util.admission import (
        SESSION_QUEUE_TIMEOUT, SESSION_SLOTS, session_queue,
    )
    from cockroach_tpu.util.fault import registry
    from cockroach_tpu.util.metric import default_registry
    from cockroach_tpu.util.settings import Settings

    _zero_backoff()
    s = Settings()
    prev_slots = s.get(SESSION_SLOTS)
    prev_to = s.get(SESSION_QUEUE_TIMEOUT)
    prev_serving = s.get(_serving.SERVING_ENABLED)
    s.set(SESSION_SLOTS, slots)
    s.set(SESSION_QUEUE_TIMEOUT, 15.0)
    s.set(_serving.SERVING_ENABLED, serving)
    store, cat = _load_serving_catalog()
    pool = _query_pool()
    serving_before = _serving.serving_queue().snapshot()

    handle = {"srv": PgServer(cat, capacity=256).start()}
    hmu = threading.Lock()

    def addr():
        with hmu:
            return handle["srv"].addr

    # serial fault-free reference over the same wire path (rendering
    # identical to what the concurrent clients will see); two passes so
    # the second stores + exercises the WARM prepared entries (shared
    # across sessions via the catalog) and compiles the batched serving
    # programs — the chaos run then measures serving, not first-compiles
    ref = {}
    c = _WireClient(addr())
    for _ in range(2):
        for _cls, q in pool:
            rows, code = c.query(q)
            assert code is None, (q, code)
            ref[q] = sorted(rows)
    c.close()
    if serving:
        # compile the pow2 batch-bucket shapes up front (the serial
        # reference only reaches batch=1) so the chaos p99 measures
        # serving, not first-compiles
        _serving.serving_queue().prewarm(max_batch=threads)

    reg = registry()
    reg.set_seed(seed)
    for pt in DEFAULT_POINTS:
        reg.arm(pt, probability=prob)

    mu = threading.Lock()
    cancel_keys = {}
    counts = {"ok": 0, "mismatch": 0, "cancelled": 0, "shed": 0,
              "drained": 0, "reconnects": 0, "inserts_ok": 0,
              "inserts_attempted": 0, "unexpected": []}
    lat = {cls: [] for cls, _q in pool}
    lat["insert"] = []
    total_ops = threads * ops_per_thread
    done_ops = [0]
    halfway = threading.Event()
    stop_nemesis = threading.Event()
    mismatches = []

    def bump_done():
        with mu:
            done_ops[0] += 1
            if done_ops[0] >= total_ops // 2:
                halfway.set()

    def client(tid):
        rng = random.Random(seed * 7919 + tid)
        conn = None
        seq = 0
        for _ in range(ops_per_thread):
            if rng.random() < 0.25:
                # YCSB-E insert leg: UPSERT (idempotent, so a retry
                # after a connection lost mid-statement can't
                # double-apply) to a pk strictly above every read range
                cls = "insert"
                pk = _servebench().INSERT_BASE + tid * 100_000 + seq
                seq += 1
                sql = "upsert into kv values (%d, %d, %d)" % (
                    pk, 37 * pk % 1009, pk % 7919)
                expect = None
                with mu:
                    counts["inserts_attempted"] += 1
            else:
                cls, sql = pool[rng.randrange(len(pool))]
                expect = ref[sql]
            attempts = 0
            while True:
                attempts += 1
                if attempts > 400:
                    with mu:
                        counts["unexpected"].append(
                            (tid, cls, "retries exhausted"))
                    break
                if conn is None:
                    try:
                        conn = _WireClient(addr())
                        with mu:
                            cancel_keys[tid] = (addr(), conn.key)
                    except OSError:
                        with mu:
                            counts["reconnects"] += 1
                        time.sleep(0.05)
                        conn = None
                        continue
                t0 = time.monotonic()
                try:
                    rows, code = conn.query(sql)
                except (ConnectionError, OSError):
                    # drain closed the socket (or the server restarted
                    # under us): reconnect and retry the op
                    conn.close()
                    conn = None
                    with mu:
                        counts["reconnects"] += 1
                    continue
                dt = time.monotonic() - t0
                with mu:
                    if code is None:
                        if expect is not None and sorted(rows) != expect:
                            counts["mismatch"] += 1
                            mismatches.append((tid, sql, len(rows)))
                        else:
                            counts["ok"] += 1
                            lat[cls].append(dt)
                            if cls == "insert":
                                counts["inserts_ok"] += 1
                    elif code == "57014":
                        counts["cancelled"] += 1
                    elif code == "53300":
                        counts["shed"] += 1
                    elif code == "57P01":
                        counts["drained"] += 1
                    else:
                        counts["unexpected"].append((tid, sql, code))
                if code == "57P01":
                    # draining: this conn is doomed; park briefly, then
                    # retry the op against the restarted server
                    conn.close()
                    conn = None
                    time.sleep(0.1)
                    continue
                break
            bump_done()
        if conn is not None:
            conn.close()

    def nemesis():
        rng = random.Random(seed * 104729 + 1)
        while not stop_nemesis.wait(cancel_period_s
                                    * (0.5 + rng.random())):
            with mu:
                keys = list(cancel_keys.values())
            if keys:
                a, key = keys[rng.randrange(len(keys))]
                if key is not None:
                    _send_cancel(a, *key)

    workers = [threading.Thread(target=client, args=(tid,),
                                name=f"chaos-client-{tid}", daemon=True)
               for tid in range(threads)]
    nem = threading.Thread(target=nemesis, name="chaos-nemesis",
                           daemon=True)
    t0 = time.monotonic()
    for w in workers:
        w.start()
    nem.start()

    drains = []
    if drain_mid_run:
        if halfway.wait(300):
            old = handle["srv"]
            summary = old.drain(timeout=10.0)
            drains.append(summary)
            with hmu:
                handle["srv"] = PgServer(cat, capacity=256).start()
            emit("mid-run drain: %s; restarted on %s:%d" % (
                summary, *addr()))
        else:
            emit("WARN: halfway mark never reached; skipping drain")

    deadline = t0 + 600
    deadlocked = []
    for w in workers:
        w.join(max(1.0, deadline - time.monotonic()))
        if w.is_alive():
            deadlocked.append(w.name)
    stop_nemesis.set()
    nem.join(5)
    reg.disarm()
    elapsed = time.monotonic() - t0

    # post-chaos verification: the surviving server answers every pool
    # query bit-exact, and the applied-insert count is sane (every op
    # reported ok definitely applied; cancelled ones may or may not
    # have, upserts make the distinction harmless)
    post_ok = True
    applied = -1
    if not deadlocked:
        c = _WireClient(addr())
        for _cls, q in pool:
            rows, code = c.query(q)
            if code is not None or sorted(rows) != ref[q]:
                post_ok = False
                emit("POST-CHECK mismatch: %s (code=%s)" % (q, code))
        rows, code = c.query(
            "select count(*) as n from kv where pk >= %d"
            % _servebench().INSERT_BASE)
        applied = int(rows[0][0]) if code is None else -1
        c.close()
        if not (counts["inserts_ok"] <= applied
                <= counts["inserts_attempted"]):
            post_ok = False
            emit("POST-CHECK insert accounting: applied=%d ok=%d "
                 "attempted=%d" % (applied, counts["inserts_ok"],
                                   counts["inserts_attempted"]))
    drains.append(handle["srv"].drain(timeout=10.0))

    # leaked-slot check: after the final drain nothing may hold or wait
    # on a session admission slot
    q = session_queue()
    mreg = default_registry()
    leaked = {"slots_used": int(mreg.gauge(
                  "sql.admission.slots_used").value()),
              "waiting": int(mreg.gauge(
                  "sql.admission.waiting").value())}
    shed_total = int(q.timeouts.value()) if q is not None else 0
    s.set(SESSION_SLOTS, prev_slots)
    s.set(SESSION_QUEUE_TIMEOUT, prev_to)
    s.set(_serving.SERVING_ENABLED, prev_serving)

    # per-run serving-queue deltas (the singleton's counters are
    # process-cumulative) + aggregate throughput for the 3x gate
    serving_after = _serving.serving_queue().snapshot()
    serving_stats = dict(serving_after)
    for k in ("batched_dispatch_total", "coalesced_statements",
              "fallbacks", "dispatches"):
        serving_stats[k] = serving_after[k] - serving_before[k]
    cls_b = serving_before.get("classes", {})
    serving_stats["classes"] = {}
    for cls, a in serving_after.get("classes", {}).items():
        d = dict(a)
        b = cls_b.get(cls, {})
        for k in ("batched_dispatch_total", "coalesced_statements",
                  "fallbacks"):
            d[k] = a.get(k, 0) - b.get(k, 0)
        serving_stats["classes"][cls] = d
    serving_stats["enabled"] = serving

    report = {
        "mode": "concurrent",
        "threads": threads,
        "ops_per_thread": ops_per_thread,
        "fault_prob": prob,
        "session_slots": slots,
        "elapsed_s": round(elapsed, 2),
        "counts": {k: v for k, v in counts.items() if k != "unexpected"},
        "unexpected_errors": counts["unexpected"][:20],
        "latency": {cls: _percentiles(v) for cls, v in lat.items()},
        "throughput": dict(
            {"aggregate_qps": round(counts["ok"] / elapsed, 1)
             if elapsed > 0 else 0.0},
            **{cls + "_qps": round(len(v) / elapsed, 1)
               if elapsed > 0 else 0.0 for cls, v in lat.items()}),
        "serving": serving_stats,
        "queue_wait": {"sheds_total": shed_total},
        "drains": drains,
        "inserts_applied": applied,
        "deadlocked": deadlocked,
        "leaked_admission": leaked,
        "post_check_ok": post_ok,
        "ok": (not deadlocked and post_ok
               and counts["mismatch"] == 0
               and not counts["unexpected"]
               and leaked["slots_used"] == 0
               and leaked["waiting"] == 0),
    }
    emit(json.dumps(report, indent=2))
    return report


def run_crash_chaos(rounds: int, seed: int, sql_rounds: int = 2,
                    base_dir=None) -> dict:
    """The kill -9 nemesis: `rounds` child processes each killed by a
    deterministically-armed crash point (wal.append / wal.sync /
    engine.flush at a randomized write #N) during write-heavy load on a
    durable engine (both engines when the native library builds), plus
    scripted torn-tail and corrupted-byte rounds and full-SQL rounds.
    Every restart must recover without error, keep every acknowledged
    write (engine_fingerprint at the last acked timestamp, bit-exact vs
    a pristine reference), truncate torn WAL tails, and flag corruption
    via CRC. See util/crash_harness.py for the child/parent protocol."""
    import shutil
    import tempfile

    from cockroach_tpu.util import crash_harness as ch

    engines = ["py", "native"] if ch.native_available() else ["py"]
    plans = ch.build_plans(rounds, seed, engines, sql_rounds=sql_rounds)
    owned = base_dir is None
    base = base_dir or tempfile.mkdtemp(prefix="crash_chaos_")
    results = []
    try:
        for plan in plans:
            r = ch.run_round(plan, base)
            tag = "ok" if r["ok"] else "FAIL"
            print("crash round %2d %-7s eng=%-6s point=%-13s at=%-3s "
                  "%s" % (plan["idx"], plan["kind"], plan["engine"],
                          plan.get("point") or "-",
                          plan.get("at", "-"), tag), flush=True)
            if not r["ok"]:
                print("  " + r.get("error", "?"), flush=True)
            results.append(r)
    finally:
        if owned:
            shutil.rmtree(base, ignore_errors=True)
    failed = [r for r in results if not r["ok"]]
    return {
        "rounds": len(results),
        "kills": sum(1 for r in results if r["rc"] == -9),
        "torn_rounds": sum(1 for r in results
                           if r.get("stats", {}).get("torn_bytes", 0)),
        "crc_detected": sum(1 for r in results
                            if r.get("stats", {}).get("crc_failures", 0)),
        "failed": failed,
        "ok": not failed,
    }


def run_changefeed_chaos(rounds: int, seed: int, base_dir=None) -> dict:
    """The changefeed kill -9 nemesis: each child runs a continuous
    file-sink changefeed job plus an incrementally-maintained view over
    deterministic write bursts, and dies by an armed SIGKILL on the
    checkpoint or segment-flush seam. The parent re-adopts the job from
    its checkpointed frontier and demands exactly-once emission at the
    acked horizon (no duplicate (key, ts) across the segment chain),
    envelope replay bit-equal to the recovered table, prefix-consistent
    survival of every acked burst, and a re-built materialized view
    bit-exact vs the engine's own GROUP BY."""
    import shutil
    import tempfile

    from cockroach_tpu.util import crash_harness as ch

    engines = ["py", "native"] if ch.native_available() else ["py"]
    plans = ch.build_changefeed_plans(rounds, seed, engines)
    owned = base_dir is None
    base = base_dir or tempfile.mkdtemp(prefix="changefeed_chaos_")
    results = []
    try:
        for plan in plans:
            r = ch.run_round(plan, base)
            tag = "ok" if r["ok"] else "FAIL"
            print("feed round %2d eng=%-6s point=%-18s at=%-3s "
                  "acked=%s events=%s %s" % (
                      plan["idx"], plan["engine"], plan["point"],
                      plan["at"], r.get("acked_bursts", "-"),
                      r.get("events", "-"), tag), flush=True)
            if not r["ok"]:
                print("  " + r.get("error", "?"), flush=True)
            results.append(r)
    finally:
        if owned:
            shutil.rmtree(base, ignore_errors=True)
    failed = [r for r in results if not r["ok"]]
    return {
        "changefeed": {
            "rounds": len(results),
            "kills": sum(1 for r in results if r["rc"] == -9),
            "exactly_once": not any(
                "duplicate" in r.get("error", "") for r in results),
            "view_bit_exact": not any(
                "matview" in r.get("error", "") for r in results),
            "failures": failed,
        },
        "ok": not failed,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--queries", default="1,3,18")
    p.add_argument("--points", default=",".join(DEFAULT_POINTS))
    p.add_argument("--prob", type=float, default=None,
                   help="fault fire probability (default 0.3; 0.2 "
                        "for --concurrent)")
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--log2-capacity", type=int, default=13)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-spill", action="store_true")
    p.add_argument("--cluster", action="store_true",
                   help="run the cluster nemesis instead: kill the "
                        "leaseholder of a scanned range mid-query over "
                        "a 3-node replicated Cluster")
    p.add_argument("--concurrent", action="store_true",
                   help="run the concurrent-serving nemesis instead: "
                        "N pgwire client threads of mixed YCSB-E + "
                        "TPC-H trickle + vector queries with faults "
                        "armed, random CancelRequests, and a mid-run "
                        "drain/restart; results bit-exact vs serial")
    p.add_argument("--threads", type=int, default=16)
    p.add_argument("--ops", type=int, default=24,
                   help="ops per client thread (--concurrent)")
    p.add_argument("--slots", type=int, default=4,
                   help="sql.admission.session_slots (--concurrent)")
    p.add_argument("--no-serving", action="store_true",
                   help="disable cross-session continuous batching "
                        "(--concurrent): the unbatched baseline the "
                        "3x throughput gate compares against")
    p.add_argument("--crash", action="store_true",
                   help="run the crash nemesis instead: kill -9 child "
                        "processes at randomized durable-write points "
                        "during write-heavy load, restart, assert "
                        "bit-exact recovery of every acked write plus "
                        "CRC-truncated torn WAL tails")
    p.add_argument("--rounds", type=int, default=20,
                   help="randomized kill -9 rounds (--crash / "
                        "--changefeed)")
    p.add_argument("--changefeed", action="store_true",
                   help="run the changefeed nemesis instead: kill -9 a "
                        "continuous changefeed + matview child on the "
                        "checkpoint/segment seams, resume from the "
                        "checkpointed frontier, assert exactly-once "
                        "emission at the acked horizon and a bit-exact "
                        "rebuilt view")
    args = p.parse_args(argv)

    if args.changefeed:
        t0 = time.monotonic()
        report = run_changefeed_chaos(rounds=args.rounds, seed=args.seed)
        cf = report["changefeed"]
        print("changefeed chaos: %d rounds (%d kill -9), exactly_once=%s "
              "view_bit_exact=%s, %d failures in %.1fs" % (
                  cf["rounds"], cf["kills"], cf["exactly_once"],
                  cf["view_bit_exact"], len(cf["failures"]),
                  time.monotonic() - t0))
        print(json.dumps(report, indent=2, default=str))
        return 0 if report["ok"] else 1
    if args.crash:
        t0 = time.monotonic()
        report = run_crash_chaos(rounds=args.rounds, seed=args.seed)
        print("crash chaos: %d rounds (%d kill -9, %d torn, %d CRC "
              "detections), %d failures in %.1fs" % (
                  report["rounds"], report["kills"],
                  report["torn_rounds"], report["crc_detected"],
                  len(report["failed"]), time.monotonic() - t0))
        return 0 if report["ok"] else 1
    _setup_jax()
    if args.concurrent:
        report = run_concurrent_chaos(
            threads=args.threads, ops_per_thread=args.ops,
            prob=args.prob if args.prob is not None else 0.2,
            seed=args.seed, slots=args.slots,
            serving=not args.no_serving)
        return 0 if report["ok"] else 1
    t0 = time.monotonic()
    queries = [int(q) for q in args.queries.split(",") if q]
    if args.cluster:
        report = run_cluster_chaos(
            queries=queries, sf=args.sf,
            capacity=1 << args.log2_capacity, seed=args.seed)
    else:
        report = run_chaos(
            queries=queries,
            points=[pt for pt in args.points.split(",") if pt],
            prob=args.prob if args.prob is not None else 0.3,
            sf=args.sf, capacity=1 << args.log2_capacity,
            seed=args.seed, spill=not args.no_spill)
    failed = [r for r in report if not r["ok"]]
    fired = sum(r["fires"] for r in report)
    print("chaos: %d cases, %d fault fires, %d mismatches in %.1fs" % (
        len(report), fired, len(failed), time.monotonic() - t0))
    if failed:
        for r in failed:
            print("MISMATCH: %s %s" % (r["query"], r["point"]))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
