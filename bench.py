"""Benchmark driver — BASELINE.md matrix; prints ONE JSON line.

Primary metric (the harness contract): TPC-H Q1 SF1 rows/sec/chip — the
scan -> decimal projection -> GROUP BY pipeline (BASELINE.md config #1;
reference CPU path cfetcher.go:758 + hash_aggregator.go:62). The JSON
line's `configs` field carries the rest of the matrix: Q3 (3-way join,
config #2), Q9 (6-way join, #3), Q18 (large-state agg + forced-spill
variant, #4), and the hash-join build+probe GB/s microbench.

Measurement protocol (BASELINE.md): warm cache, median of >=BENCH_RUNS
runs. Warm = packed table shards HBM-resident (the Pebble block-cache
analog) and the fused whole-query program compiled. Every query runs
through the fused single-program path (exec/fused.py) — a warm query is
ONE device execution plus ONE packed readback.

vs_baseline compares against single-threaded *columnar numpy* evaluations
of the same queries on this host (tpch_queries.q*_oracle_columnar) — a
stand-in for the reference's CPU vectorized engine until a side-by-side
CockroachDB run exists (the reference publishes no absolute numbers
in-repo).

Per-stage attribution (VERDICT r2 item 1) prints to stderr: the stats
collector's host-side stages (prime/compile/exec-dispatch/readback, pack/
transfer/stack) plus each config's cold/warm/numpy split.
"""

import json
import os
import statistics
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _sqlstats_block():
    """The /_status/statements payload, embedded in BENCH JSON so per-
    fingerprint latency trajectories are trackable across PRs."""
    from cockroach_tpu.sql.sqlstats import default_sqlstats

    return {"statements": default_sqlstats().top()}


def _placement_block(gen, catalog, capacity):
    """Per-query operator placement (sql/plan_compile.py): the tier the
    placement pass assigns every operator of every TPC-H plan, plus the
    fused-coverage count — how many of the plans lower whole-query into
    ONE fused device program. `backend`/`source` report the auto routing
    decision (measured when sqlstats has history for the fingerprint);
    tiers are taken with the device backend forced so structural fused
    coverage is visible even when cost routing sends a small scale
    factor to the host engine."""
    from cockroach_tpu.sql import TPCHCatalog
    from cockroach_tpu.sql.plan_compile import compile_plan
    from cockroach_tpu.workload.tpch_queries import PLANS

    cat = catalog or TPCHCatalog(gen)
    out = {"queries": {}, "fused_coverage": 0, "total_queries": len(PLANS)}
    for n, plan_fn in sorted(PLANS.items()):
        try:
            auto = compile_plan(plan_fn(gen), cat, capacity,
                                sql=f"TPCH Q{n}", record=False)
            dev = auto if auto.backend != "cpu" else compile_plan(
                plan_fn(gen), cat, capacity, sql=f"TPCH Q{n}",
                setting="tpu", record=False)
        except Exception as e:  # noqa: BLE001 — advisory block
            out["queries"][f"q{n}"] = {"error": str(e)}
            continue
        tiers = dev.placement.tier_counts()
        whole = tiers.get("fused", 0) == len(dev.placement.ops)
        out["fused_coverage"] += int(whole)
        out["queries"][f"q{n}"] = {
            "backend": auto.placement.backend,
            "source": auto.placement.source,
            "tiers": tiers,
            "whole_fused": whole,
            "ops": [{"op": oc.name, "tier": oc.tier, "src": oc.source}
                    for oc in dev.placement.ops],
        }
    return out


def _make_resident(flow):
    from cockroach_tpu.exec.operators import ScanOp, walk_operators

    for op in walk_operators(flow):
        if isinstance(op, ScanOp):
            op.resident = True


def _bench_query(name, flow, n_rows, baseline_fn, runs, fuse=True):
    from cockroach_tpu.exec import collect
    from cockroach_tpu.sql.sqlstats import default_sqlstats
    from cockroach_tpu.util.tracing import summarize, tracer

    _make_resident(flow)
    t0 = time.perf_counter()
    collect(flow, fuse=fuse)
    t_cold = time.perf_counter() - t0
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        collect(flow, fuse=fuse)
        times.append(time.perf_counter() - t0)
    warm = statistics.median(times)
    # one extra TRACED run, off the clock: the timed medians above stay
    # unperturbed, and the JSON carries each query's span digest (stage
    # durations, retries, tier reached)
    with tracer().span("bench." + name) as sp:
        collect(flow, fuse=fuse)
    # bench bypasses Session, so feed the statements page by hand — the
    # "sqlstats" block tracks per-fingerprint latency across PRs
    default_sqlstats().record(f"BENCH {name}", warm, rows=n_rows)

    cfg = {
        "rows_per_sec": round(n_rows / warm),
        "warm_s": round(warm, 4),
        "cold_s": round(t_cold, 2),
        "trace": summarize(sp),
    }
    if baseline_fn is not None:
        baseline_fn()  # warm: table datagen memoizes off the clock
        np_times = []
        for _ in range(max(1, runs // 2)):
            t0 = time.perf_counter()
            baseline_fn()
            np_times.append(time.perf_counter() - t0)
        np_elapsed = statistics.median(np_times)
        cfg["numpy_s"] = round(np_elapsed, 4)
        cfg["vs_baseline"] = round(np_elapsed / warm, 3)
        vs = f" ({cfg['vs_baseline']}x numpy)"
    else:
        vs = ""
    log(f"{name}: cold={t_cold:.2f}s warm={[round(t, 3) for t in times]} "
        f"-> {cfg['rows_per_sec']:,} rows/s{vs}")
    return cfg


def _join_microbench(runs):
    """Hash-join build+probe GB/s on the real chip (BASELINE.md metric #2).
    Measured with explicit syncs, as every real query runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cockroach_tpu.coldata.batch import Batch, Column
    from cockroach_tpu.ops.join import hash_join_prepared, prepare_build

    # round 4: the unique sort-join (ops/sortjoin.py) — the TPC-H FK->PK
    # fast path the queries actually run
    n = 1 << int(os.environ.get("BENCH_JOIN_LOG2", "22"))
    rng = np.random.default_rng(0)
    bkeys = rng.permutation(n).astype(np.int64)
    pkeys = rng.integers(0, n, n).astype(np.int64)
    build = Batch.from_columns({
        "bk": Column(jnp.asarray(bkeys)),
        "bv": Column(jnp.asarray(np.arange(n, dtype=np.int64)))})
    probe = Batch.from_columns({
        "pk": Column(jnp.asarray(pkeys)),
        "pv": Column(jnp.asarray(np.arange(n, dtype=np.int64)))})

    prep = jax.jit(lambda b: prepare_build(b, ("bk",), mode="unique"))
    joinf = jax.jit(lambda p, bt: hash_join_prepared(
        p, bt, ("pk",), ("bk",), how="inner", out_capacity=n))
    # whole-join single dispatch (build + probe in ONE program): two
    # dispatches would put the per-dispatch floor into the metric twice
    wholef = jax.jit(lambda p, b: hash_join_prepared(
        p, prepare_build(b, ("bk",), mode="unique"),
        ("pk",), ("bk",), how="inner", out_capacity=n))
    bt = jax.block_until_ready(prep(build))
    res = jax.block_until_ready(joinf(probe, bt))
    _ = np.asarray(res.batch.length)  # enter the real (post-readback) mode
    jax.block_until_ready(wholef(probe, build))

    tb, tp, tw = [], [], []
    for _i in range(runs):
        t0 = time.perf_counter()
        bt = jax.block_until_ready(prep(build))
        tb.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(joinf(probe, bt))
        tp.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(wholef(probe, build))
        tw.append(time.perf_counter() - t0)
    t_build, t_probe = statistics.median(tb), statistics.median(tp)
    t_whole = statistics.median(tw)
    build_bytes = n * 16  # 2 int64 columns
    probe_bytes = n * 16
    gbps = (build_bytes + probe_bytes) / t_whole / 1e9
    log(f"join microbench ({n >> 20}M build x {n >> 20}M probe int64): "
        f"build={t_build * 1e3:.0f}ms probe={t_probe * 1e3:.0f}ms "
        f"whole={t_whole * 1e3:.0f}ms -> {gbps:.2f} GB/s")
    return {"build_s": round(t_build, 4), "probe_s": round(t_probe, 4),
            "whole_s": round(t_whole, 4), "rows": n,
            "gb_per_sec": round(gbps, 3)}


def _ycsb_bench(runs):
    """Config #5: YCSB-E — (a) the operational 95/5 scan/insert mix on the
    CPU MVCC engine, (b) the analytical MVCC-scan -> device top-K flow."""
    import numpy as np

    from cockroach_tpu.exec import collect
    from cockroach_tpu.storage import MVCCStore, NativeEngine
    from cockroach_tpu.util.hlc import HLC, ManualClock
    from cockroach_tpu.workload import ycsb

    n_records = int(os.environ.get("BENCH_YCSB_RECORDS", "200000"))
    n_ops = int(os.environ.get("BENCH_YCSB_OPS", "2000"))
    rng = np.random.default_rng(0)
    st = MVCCStore(engine=NativeEngine(), clock=HLC(ManualClock(1000)))
    t0 = time.perf_counter()
    ycsb.load(st, n_records, rng)
    t_load = time.perf_counter() - t0
    ops_per_sec, rows = ycsb.run_e(st, n_ops, n_records, rng)

    flow = ycsb.scan_topk_flow(st, capacity=1 << 17, k=100)
    _make_resident(flow)
    collect(flow)  # cold
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        collect(flow)
        times.append(time.perf_counter() - t0)
    warm = statistics.median(times)

    # numpy baseline: top-K over the already-scanned host columns
    chunks = list(st.scan_chunks(ycsb.TABLE_ID, ycsb.N_FIELDS, 1 << 17))
    t0 = time.perf_counter()
    f0 = np.concatenate([c["f0"] for c in chunks])
    topk = np.sort(np.partition(f0, len(f0) - 100)[-100:])[::-1]
    np_elapsed = time.perf_counter() - t0
    assert len(topk) == 100

    # batched micro-queries (the operational shape of workload E): B
    # concurrent scan+top-K ops coalesce into ONE device dispatch
    # (workload/ycsb.py ScanTopKBatcher) vs one dispatch per op. The two
    # paths trace the same kernel and must match bit-for-bit.
    k_ops = int(os.environ.get("BENCH_YCSB_TOPK", "10"))
    batch_b = int(os.environ.get("BENCH_YCSB_BATCH", "256"))
    batcher = ycsb.ScanTopKBatcher.from_store(st, capacity=1 << 17,
                                              k=k_ops)
    qrng = np.random.default_rng(7)
    q_starts = ycsb.fnv_scramble(ycsb.Zipf(n_records, rng=qrng)
                                 .draw(n_ops), n_records)
    q_lens = qrng.integers(1, ycsb.MAX_SCAN_LEN + 1, n_ops)
    # warm both paths (compiles off the clock)
    batcher.run(q_starts[:batch_b], q_lens[:batch_b],
                batch_size=batch_b)
    batcher.run_unbatched(q_starts[:2], q_lens[:2])
    t0 = time.perf_counter()
    unb_v, unb_c = batcher.run_unbatched(q_starts, q_lens)
    t_unbatched = time.perf_counter() - t0
    bat_times = []
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        bat_v, bat_c = batcher.run(q_starts, q_lens, batch_size=batch_b)
        bat_times.append(time.perf_counter() - t0)
    t_batched = statistics.median(bat_times)
    batched_match = bool(np.array_equal(unb_v, bat_v)
                         and np.array_equal(unb_c, bat_c))
    covered = int(unb_c.sum())

    cfg = {
        "ops_per_sec": round(ops_per_sec),
        "rows_scanned": rows,
        # the serving metric: micro-query rows/sec through the BATCHED
        # dispatch path (was: full-scan flow rows/sec, now kept below as
        # full_scan_topk_rows_per_sec)
        "scan_topk_rows_per_sec": round(covered / t_batched),
        "scan_topk_rows_per_sec_unbatched": round(covered / t_unbatched),
        "scan_topk_ops_per_sec": round(n_ops / t_batched),
        "batch_speedup": round(t_unbatched / t_batched, 2),
        "batched_match": batched_match,
        "op_batch_occupancy": round(batcher.occupancy(), 4),
        "op_batch_dispatches": batcher.dispatches,
        "full_scan_topk_rows_per_sec": round(n_records / warm),
        "full_scan_topk_warm_s": round(warm, 4),
        "vs_baseline": round(np_elapsed / warm, 3),
        "load_s": round(t_load, 2),
    }
    assert batched_match, "batched YCSB results diverge from per-op path"
    log(f"ycsb-e: {cfg['ops_per_sec']:,} ops/s (mix), batched micro "
        f"{cfg['scan_topk_rows_per_sec']:,} rows/s vs unbatched "
        f"{cfg['scan_topk_rows_per_sec_unbatched']:,} "
        f"({cfg['batch_speedup']}x, match={batched_match}, occupancy="
        f"{cfg['op_batch_occupancy']}), full scan+topk warm="
        f"{warm * 1e3:.0f}ms ({cfg['full_scan_topk_rows_per_sec']:,} "
        f"rows/s, {cfg['vs_baseline']}x numpy)")
    return cfg


def _mvcc_scan_bench(runs):
    """Config #6: device-resident MVCC scans (storage/resident.py).
    Host MVCC walk vs the resident visibility-kernel tier on the same
    store: cold (attach + base build + first image), warm (memoized
    image), and delta-warm (a write burst folded incrementally — the
    point of the tier: no full restack). Also reports the delta append
    rate, host<->device bytes moved, and how many scans each tier
    actually served."""
    import numpy as np

    from cockroach_tpu.exec import stats
    from cockroach_tpu.storage import MVCCStore, NativeEngine, PyEngine
    from cockroach_tpu.storage import resident
    from cockroach_tpu.util.hlc import HLC, ManualClock, Timestamp

    n = int(os.environ.get("BENCH_MVCC_SCAN_ROWS", "200000"))
    d = int(os.environ.get("BENCH_MVCC_SCAN_DELTAS", "2000"))
    versions = int(os.environ.get("BENCH_MVCC_SCAN_VERSIONS", "3"))
    ncols, tid, cap = 4, 77, 1 << 17
    try:
        store = MVCCStore(engine=NativeEngine(),
                          clock=HLC(ManualClock(1000)))
    except RuntimeError:
        store = MVCCStore(engine=PyEngine(),
                          clock=HLC(ManualClock(1000)))
    rng = np.random.default_rng(11)
    pks = np.arange(n, dtype=np.int64)
    # realistic MVCC shape: every key carries version history, so the
    # host walk pays O(versions) per key while the resident image stays
    # O(live rows)
    for v in range(versions):
        cols = {f"f{i}": rng.integers(-1 << 40, 1 << 40, n)
                .astype(np.int64) for i in range(ncols)}
        store.ingest_table(tid, pks, cols, ts=Timestamp(2000 + v, 0))
    tread = Timestamp(10**9, 0)

    def scan_rows():
        return sum(len(next(iter(c.values())))
                   for c in store.scan_chunks(tid, ncols, cap, ts=tread))

    resident.detach(store, tid)  # host-walk baseline, no device tier
    host_times = []
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        n_seen = scan_rows()
        host_times.append(time.perf_counter() - t0)
    t_host = statistics.median(host_times)
    assert n_seen == n

    st = stats.active()

    def stage(name):
        if st is None:
            return (0, 0, 0)
        s = st.stage(name)
        return (s.events, s.rows, s.bytes)

    res0, fall0, xfer0 = (stage("scan.resident"),
                          stage("scan.resident_fallback"),
                          stage("scan.resident_transfer"))

    t0 = time.perf_counter()
    ok = store.make_resident(tid, ncols)
    n_seen = scan_rows()
    t_cold = time.perf_counter() - t0
    assert ok and n_seen == n
    res_times = []
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        scan_rows()
        res_times.append(time.perf_counter() - t0)
    t_warm = statistics.median(res_times)

    rt = resident.lookup(store, tid)
    rebuilds_before = rt.rebuilds
    t0 = time.perf_counter()
    for i in range(d):
        store.put(tid, int(rng.integers(0, n)),
                  [int(v) for v in rng.integers(-100, 100, ncols)],
                  ts=Timestamp(3000 + i, 0))
    t_append = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_seen = scan_rows()  # folds the delta tail into the image
    t_fold_scan = time.perf_counter() - t0
    assert n_seen == n
    dw_times = []
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        scan_rows()
        dw_times.append(time.perf_counter() - t0)
    t_delta_warm = statistics.median(dw_times)
    folded = bool(rt.rebuilds == rebuilds_before)

    res1, fall1, xfer1 = (stage("scan.resident"),
                          stage("scan.resident_fallback"),
                          stage("scan.resident_transfer"))
    cfg = {
        "rows": n,
        "versions_per_key": versions,
        "host_walk_rows_per_sec": round(n / t_host),
        "scan_rows_per_sec": round(n / t_warm),
        "scan_rows_per_sec_cold": round(n / t_cold),
        "scan_rows_per_sec_delta_warm": round(n / t_delta_warm),
        "vs_host_walk": round(t_host / t_warm, 2),
        "deltas": d,
        "delta_append_per_sec": round(d / t_append),
        "delta_fold_scan_s": round(t_fold_scan, 4),
        "folded_incrementally": folded,
        "resident_tier_scans": res1[0] - res0[0],
        "host_tier_fallbacks": fall1[0] - fall0[0],
        "bytes_transferred": xfer1[2] - xfer0[2],
    }
    resident.detach(store, tid)
    log(f"mvcc-scan: host walk {cfg['host_walk_rows_per_sec']:,} rows/s "
        f"vs resident warm {cfg['scan_rows_per_sec']:,} "
        f"({cfg['vs_host_walk']}x), delta-warm "
        f"{cfg['scan_rows_per_sec_delta_warm']:,}; append "
        f"{cfg['delta_append_per_sec']:,} deltas/s, folded="
        f"{folded}, {cfg['bytes_transferred'] / 1e6:.1f} MB moved, "
        f"tiers resident={cfg['resident_tier_scans']}/"
        f"fallback={cfg['host_tier_fallbacks']}")
    return cfg


def _changefeed_bench(runs):
    """PR 13 changefeed + incremental-view block: envelope emit
    throughput and frontier lag (the gap between a write's HLC horizon
    and the poll that resolves it) over repeated write bursts, plus the
    incremental scatter-add fold vs full re-scan refresh differential
    at 1k and 10k-row bursts. The fold path must keep the re-scan
    counter at 0 — the view refreshes through the device fold alone."""
    import numpy as np

    from cockroach_tpu.kv.rangefeed import _metrics
    from cockroach_tpu.sql import changefeed as cfmod
    from cockroach_tpu.sql.session import Session, SessionCatalog
    from cockroach_tpu.storage.mvcc import MVCCStore

    store = MVCCStore()
    cat = SessionCatalog(store)
    sess = Session(cat, capacity=1 << 13)
    rng = np.random.default_rng(5)

    def burst(table, start, n):
        ks = np.arange(start, start + n)
        grps = rng.integers(0, 64, n)
        vs = rng.integers(0, 100_000, n)
        for i in range(0, n, 500):
            vals = ",".join(
                "(%d,%d,%d)" % (ks[j], grps[j], vs[j])
                for j in range(i, min(i + 500, n)))
            sess.execute(f"insert into {table} values {vals}")

    # emit throughput + frontier lag: poll a live stream after each
    # burst; the lag gauge records horizon-grab -> frontier-advance
    sess.execute("create table cf (k int primary key, "
                 "grp int not null, v int)")
    stream = cfmod.ChangefeedStream(store, cat.desc("cf"),
                                    cfmod.MemorySink())
    stream.poll()  # catch up on the empty table
    emitted, emit_s, lags = 0, 0.0, []
    nb, bsz = 10, 1000
    for b in range(nb):
        burst("cf", b * bsz, bsz)
        t0 = time.perf_counter()
        n = stream.poll()
        emit_s += time.perf_counter() - t0
        emitted += n
        lags.append(_metrics.frontier_lag_ns.value() / 1e6)
    lags.sort()

    # fold vs re-scan refresh at 1k / 10k-row bursts
    bursts = {}
    for n in (1000, 10000):
        t = f"cfv{n}"
        sess.execute(f"create table {t} (k int primary key, "
                     "grp int not null, v int)")
        sess.execute(f"create materialized view m{n} as select grp, "
                     f"count(*) as c, sum(v) as s from {t} group by grp")
        mgr = sess._matviews()
        burst(t, 0, n)
        sess.execute(f"refresh materialized view m{n}")  # initial build
        r0 = mgr.report()[f"m{n}"]["rescans"]
        fold_times, start = [], n
        for _ in range(max(1, runs)):
            burst(t, start, n)
            start += n
            t0 = time.perf_counter()
            sess.execute(f"refresh materialized view m{n}")
            fold_times.append(time.perf_counter() - t0)
        rep = mgr.report()[f"m{n}"]
        rescans_during = rep["rescans"] - r0
        mv = mgr.get(f"m{n}")
        rescan_times = []
        for _ in range(max(1, runs)):
            t0 = time.perf_counter()
            mv._rescan(store.clock.now())
            rescan_times.append(time.perf_counter() - t0)
        t_fold = statistics.median(fold_times)
        t_rescan = statistics.median(rescan_times)
        bursts[str(n)] = {
            "fold_refresh_ms": round(t_fold * 1e3, 2),
            "rescan_refresh_ms": round(t_rescan * 1e3, 2),
            "fold_vs_rescan": round(t_rescan / t_fold, 2),
            "rescans_during_folds": rescans_during,
        }
        assert rescans_during == 0, \
            f"insert-only burst fell off the fold path ({rep})"

    cfg = {
        "emit_rows_per_sec": round(emitted / emit_s) if emit_s else 0,
        "emitted": emitted,
        "frontier_lag_p50_ms": round(lags[len(lags) // 2], 3),
        "frontier_lag_p99_ms": round(
            lags[min(len(lags) - 1, int(len(lags) * 0.99))], 3),
        "bursts": bursts,
    }
    log(f"changefeed: {cfg['emit_rows_per_sec']:,} envelopes/s, lag "
        f"p50={cfg['frontier_lag_p50_ms']}ms "
        f"p99={cfg['frontier_lag_p99_ms']}ms; fold vs rescan "
        + ", ".join(f"{k}: {v['fold_vs_rescan']}x" +
                    (" (rescans=0)" if not v["rescans_during_folds"]
                     else " (DEGRADED)")
                    for k, v in bursts.items()))
    return cfg


def _multichip_child() -> None:
    """Child half of the multichip scaling bench: runs on the 8-device
    virtual CPU mesh (the parent re-execs us with JAX_PLATFORMS=cpu +
    xla_force_host_platform_device_count — the main bench process holds
    the one-chip TPU backend). Prints ONE JSON line:
    per-chip scaling curve for distributed Q3/Q9 at 1/2/4/8 devices
    (rows/s cold+warm, a2a repartition bytes, ingest bytes) plus the
    ingest-shard vs replicate transfer-bytes comparison on the full
    mesh."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from cockroach_tpu.exec import stats
    from cockroach_tpu.exec.operators import ScanOp, walk_operators
    from cockroach_tpu.parallel import make_mesh
    from cockroach_tpu.parallel import ingest
    from cockroach_tpu.parallel.dist_flow import (
        BROADCAST_LIMIT, collect_distributed,
    )
    from cockroach_tpu.util.settings import Settings
    from cockroach_tpu.workload.tpch import TPCH
    from cockroach_tpu.workload import tpch_queries as Q

    sf = float(os.environ.get("BENCH_MULTICHIP_SF", "0.01"))
    cap = 1 << int(os.environ.get("BENCH_MULTICHIP_LOG2_CAP", "12"))
    runs = int(os.environ.get("BENCH_MULTICHIP_RUNS", "3"))
    gen = TPCH(sf=sf)
    n_line = gen.num_rows("lineitem")
    default_limit = Settings().get(BROADCAST_LIMIT)

    def by(col, name):
        s = col.stages.get(name)
        return s.bytes if s else 0

    # q3 runs with the broadcast limit forced down so the a2a repartition
    # path is the thing measured; q9 keeps the planner's default (its
    # build sides all fit the broadcast limit at bench SF, and chaining
    # forced a2a through its 5 joins inflates per-shard capacities
    # n_dev-fold per hop — not a shape the planner would pick)
    queries = (("q3", lambda: Q.q3(gen, cap), 4096),
               ("q9", lambda: Q.q9(gen, cap), default_limit))
    sizes = [n for n in (1, 2, 4, 8) if n <= len(jax.devices())]
    curve = {}
    for n_dev in sizes:
        mesh = make_mesh(n_dev)
        row = {}
        for qname, mk, limit in queries:
            Settings().set(BROADCAST_LIMIT, limit)
            col = stats.enable()
            t0 = time.perf_counter()
            collect_distributed(mk(), mesh)
            t_cold = time.perf_counter() - t0
            stats.disable()
            times = []
            for _ in range(max(1, runs)):
                t0 = time.perf_counter()
                collect_distributed(mk(), mesh)
                times.append(time.perf_counter() - t0)
            warm = statistics.median(times)
            row[qname] = {
                "rows_per_sec": round(n_line / warm),
                "warm_s": round(warm, 4),
                "cold_s": round(t_cold, 2),
                "repartition_bytes": by(col, "dist.a2a"),
                "ingest_shard_bytes": by(col, "dist.ingest_shard"),
                "ingest_replicate_bytes":
                    by(col, "dist.ingest_replicate"),
            }
            log(f"multichip {qname}@{n_dev}: cold={t_cold:.2f}s "
                f"warm={warm * 1e3:.0f}ms "
                f"({row[qname]['rows_per_sec']:,} rows/s), a2a="
                f"{row[qname]['repartition_bytes'] / 1e6:.2f}MB")
        curve[str(n_dev)] = row
    Settings().set(BROADCAST_LIMIT, default_limit)

    # ingest-shard vs replicate: the same (largest) Q3 scan placed both
    # ways on the full mesh — the P2 payoff is the byte ratio
    mesh = make_mesh(sizes[-1])
    scans = [op for op in walk_operators(Q.q3(gen, cap))
             if isinstance(op, ScanOp)]
    sc = max(scans, key=lambda s: getattr(s, "est_rows", 0) or 0)
    ingest.cache_clear()
    items = ingest.host_pack(sc)
    sh = ingest.build(sc, mesh, "x", ingest.SHARDED, ("host", items))
    ingest.cache_clear()
    rep = ingest.build(sc, mesh, "x", ingest.REPLICATED,
                       ("host", items))
    ingest.cache_clear()
    transfer = {
        "n_devices": sizes[-1],
        "shard_bytes": int(sh.nbytes),
        "replicate_bytes": int(rep.nbytes),
        "replicate_vs_shard": round(rep.nbytes / max(sh.nbytes, 1), 2),
    }
    log(f"multichip ingest@{sizes[-1]}: shard "
        f"{transfer['shard_bytes'] / 1e6:.2f}MB vs replicate "
        f"{transfer['replicate_bytes'] / 1e6:.2f}MB "
        f"({transfer['replicate_vs_shard']}x)")
    print(json.dumps({"sf": sf, "lineitem_rows": n_line,
                      "scaling": curve, "ingest_transfer": transfer}))


def _multichip_bench():
    """Parent half: re-exec this file with --multichip-child on a forced
    8-device virtual CPU mesh and return its JSON block (None on
    failure — the main bench must still emit its line)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multichip-child"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True,
        timeout=float(os.environ.get("BENCH_MULTICHIP_TIMEOUT_S",
                                     "900")))
    for line in res.stderr.splitlines():
        log(line)
    if res.returncode != 0:
        log(f"multichip bench failed (rc={res.returncode}); skipping")
        return None
    try:
        return json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        log("multichip bench produced no JSON; skipping")
        return None


def _limit_chunks(scan, n: int):
    """Cap a ScanOp to its first n chunks (bounded bench configs)."""
    import itertools

    inner = scan._chunks

    def limited():
        return itertools.islice(inner(), n)

    scan._chunks = limited
    # the capped stream is NOT the table the cache key describes: opt out
    # of cross-query image sharing (and drop any already-borrowed image)
    scan.cache_key = None
    scan.evict()


def main():
    sf = float(os.environ.get("BENCH_SF", "1"))
    capacity = 1 << int(os.environ.get("BENCH_LOG2_CAP", "20"))
    runs = int(os.environ.get("BENCH_RUNS", "5"))
    # wall-clock budget: optional configs are skipped past this point so
    # the driver ALWAYS gets the final JSON line (a benched-out run beats
    # a killed one)
    t_bench_start = time.perf_counter()
    time_budget = float(os.environ.get("BENCH_TIME_BUDGET_S", "3600"))

    def budget_left() -> bool:
        left = time.perf_counter() - t_bench_start < time_budget
        if not left:
            log("bench time budget exhausted: skipping optional config")
        return left

    import jax

    # the persistent compilation cache is mounted by `import cockroach_tpu`
    # (util/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else .jax_cache)

    from cockroach_tpu.workload.tpch import TPCH
    from cockroach_tpu.workload import tpch_queries as Q
    from cockroach_tpu.exec import stats
    from cockroach_tpu.exec.operators import ScanOp
    from cockroach_tpu.util.settings import Settings, WORKMEM

    # analytics workmem: a single query may use most of the chip's HBM
    # (the reference's 64 MiB default budgets many concurrent OLTP flows;
    # the forced-spill config below still overrides per-operator)
    Settings().set(WORKMEM,
                   int(os.environ.get("BENCH_WORKMEM", str(2 << 30))))

    st = stats.enable()
    gen = TPCH(sf=sf)
    configs = {}

    # ---- TPC-H through the MVCC storage engine (VERDICT r3 #2) -----------
    # Tables are bulk-ingested into the native C++ engine (eng_ingest, the
    # AddSSTable path) and every query's ScanOp streams chunks through the
    # MVCC columnar scanner (scan -> decode -> pack -> device ON the cold
    # clock; warm runs are HBM-resident, the block-cache analog, like the
    # reference's warm runs). BENCH_MVCC=0 restores generator-direct scans.
    catalog = None
    n_line = gen.num_rows("lineitem")
    if os.environ.get("BENCH_MVCC", "1") == "1":
        try:
            from cockroach_tpu.storage import MVCCStore, NativeEngine
            from cockroach_tpu.util.hlc import HLC, ManualClock

            store = MVCCStore(engine=NativeEngine(),
                              clock=HLC(ManualClock(1000)))
            t0 = time.perf_counter()
            catalog = gen.mvcc_load(
                store, ["lineitem", "orders", "customer", "part",
                        "supplier", "partsupp", "nation"])
            t_load = time.perf_counter() - t0
            t0 = time.perf_counter()
            n_scanned = sum(
                len(next(iter(c.values())))
                for c in store.scan_chunks(10, 16, capacity))
            t_scan = time.perf_counter() - t0
            configs["mvcc_ingest"] = {
                "load_s": round(t_load, 2),
                "lineitem_scan_s": round(t_scan, 2),
                "scan_rows_per_sec": round(n_scanned / t_scan)}
            log(f"mvcc ingest sf{sf:g}: load={t_load:.2f}s, lineitem "
                f"scan {n_scanned:,} rows in {t_scan:.2f}s "
                f"({n_scanned / t_scan / 1e6:.1f}M rows/s)")
        except RuntimeError as e:
            log(f"mvcc path unavailable ({e}); generator-direct scans")

    # ---- config #1: Q1 (primary metric) ----------------------------------
    q1_cols = ["l_returnflag", "l_linestatus", "l_quantity",
               "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]
    t0 = time.perf_counter()
    chunks = [{k: c[k] for k in q1_cols}
              for c in gen.chunks("lineitem", capacity)]
    log(f"datagen lineitem sf{sf:g}: {time.perf_counter() - t0:.2f}s")
    flow1 = Q.q1(gen, capacity, catalog=catalog)
    if catalog is None:
        scan1 = flow1
        while not isinstance(scan1, ScanOp):
            scan1 = scan1.child
        scan1._chunks = lambda: iter(chunks)  # datagen off the clock
    q1 = _bench_query("q1", flow1, n_line,
                      lambda: Q.q1_oracle_columnar(gen, chunks), runs)
    configs[f"q1_sf{sf:g}"] = q1

    # ---- config #2: Q3 (3-way join) --------------------------------------
    configs[f"q3_sf{sf:g}"] = _bench_query(
        "q3", Q.q3(gen, capacity, catalog=catalog), n_line,
        lambda: Q.q3_oracle_columnar(gen), runs)

    # ---- config #3: Q9 (6-way join) --------------------------------------
    configs[f"q9_sf{sf:g}"] = _bench_query(
        "q9", Q.q9(gen, capacity, catalog=catalog), n_line,
        lambda: Q.q9_oracle_columnar(gen), runs)

    # ---- config #4: Q18 (large-state agg) + forced-spill variant ---------
    # Q18's fully-materialized fused program (two multi-M aggregations +
    # three joins in one XLA module) compiles for 40+ minutes on the AOT
    # helper; bounding its operators to a 512 MiB workmem keeps the
    # memory-bounded fold path (smaller per-step programs) — that IS the
    # config's point: large-state aggregation under a budget
    from cockroach_tpu.exec.operators import walk_operators

    def cap_workmem(flow, budget):
        for op in walk_operators(flow):
            if hasattr(op, "workmem"):
                op.workmem = min(op.workmem, budget)
        return flow

    # round 5: the int-key sort aggregation + group-join collapse run
    # Q18 as ONE fused program with no per-chunk fold (exec/fused.py);
    # the old 512 MiB cap that forced the memory-bounded fold would now
    # only disable the fast paths. BENCH_Q18_FUSE=0 restores the
    # streaming comparison run
    q18_cap = capacity
    q18_fuse = os.environ.get("BENCH_Q18_FUSE", "1") == "1"
    configs[f"q18_sf{sf:g}"] = _bench_query(
        "q18", Q.q18(gen, capacity=q18_cap, catalog=catalog),
        n_line, lambda: Q.q18_oracle_columnar(gen), runs, fuse=q18_fuse)
    if os.environ.get("BENCH_SPILL", "1") == "1" and budget_left():
        # forced grace/spill paths vs the UNBOUNDED fused path on the
        # SAME row-capped input (VERDICT r4: the two configs must
        # measure the same work, with an oracle): 8 lineitem chunks;
        # the spill run gets a 32 MiB per-operator budget (host-RAM +
        # disk partitions), the reference run the normal budget. The
        # results are asserted EQUAL — the differential is the oracle.
        spill_cap = min(capacity, 1 << 18)
        spill_chunks = int(os.environ.get("BENCH_SPILL_CHUNKS", "8"))

        def capped_q18():
            f = Q.q18(gen, capacity=spill_cap)
            for op in walk_operators(f):
                if isinstance(op, ScanOp):
                    _limit_chunks(op, spill_chunks)
            return f

        n_capped = min(n_line, spill_chunks * spill_cap)
        from cockroach_tpu.exec import collect as _collect

        ref_flow = capped_q18()
        _make_resident(ref_flow)
        ref_cfg = _bench_query("q18(capped,fused)", ref_flow, n_capped,
                               None, 1)
        spill_flow = cap_workmem(capped_q18(), 32 << 20)
        _make_resident(spill_flow)
        spill_cfg = _bench_query("q18(spill)", spill_flow, n_capped,
                                 None, 1, fuse=False)
        # differential oracle: same input, same answer
        ref_res = _collect(ref_flow)
        spill_res = _collect(spill_flow, fuse=False)
        for k in ref_res:
            import numpy as _np

            if not _np.array_equal(_np.asarray(ref_res[k]),
                                   _np.asarray(spill_res[k])):
                log(f"SPILL DIFFERENTIAL MISMATCH on {k}")
                break
        else:
            log("spill differential: EXACT MATCH vs fused")
        spill_cfg["vs_fused_same_input"] = round(
            ref_cfg["warm_s"] / spill_cfg["warm_s"], 3)
        configs[f"q18_capped_sf{sf:g}"] = ref_cfg
        configs[f"q18_spill_sf{sf:g}"] = spill_cfg

    # ---- config #5: YCSB-E -----------------------------------------------
    try:
        if budget_left():
            configs["ycsb_e"] = _ycsb_bench(runs)
    except RuntimeError as e:
        log(f"ycsb-e skipped: {e}")  # no C++ toolchain

    # ---- config #6: device-resident MVCC scan ----------------------------
    if budget_left() and os.environ.get("BENCH_MVCC_SCAN", "1") == "1":
        try:
            configs["mvcc_scan"] = _mvcc_scan_bench(runs)
        except RuntimeError as e:
            log(f"mvcc-scan skipped: {e}")

    # ---- config #6b: changefeed emit + incremental view folds ------------
    if budget_left() and os.environ.get("BENCH_CHANGEFEED", "1") == "1":
        configs["changefeed"] = _changefeed_bench(runs)

    # ---- config #5b: cross-session continuous batching (serving) ---------
    # N pgwire client threads of warm YCSB range reads, serving off then
    # on, same preloaded catalog: the speedup is the continuous-batching
    # win at equal client count (sql/serving.py); every read verifies
    # bit-exact against a serial reference inside the harness
    if budget_left() and os.environ.get("BENCH_SERVING", "1") == "1":
        from cockroach_tpu.workload import servebench

        cmp = servebench.compare(
            threads=int(os.environ.get("BENCH_SERVING_THREADS", "16")),
            ops_per_thread=int(os.environ.get("BENCH_SERVING_OPS",
                                              "40")),
            emit=log)
        sq = cmp["batched"]["serving_queue"]
        serving_cfg = {
            "threads": cmp["batched"]["threads"],
            "aggregate_qps": cmp["batched"]["qps"],
            "unbatched_qps": cmp["unbatched"]["qps"],
            "speedup": cmp["speedup"],
            "p50_ms": cmp["batched"]["latency"]["ycsb"]["p50_ms"],
            "p99_ms": cmp["batched"]["latency"]["ycsb"]["p99_ms"],
            "unbatched_p99_ms":
                cmp["unbatched"]["latency"]["ycsb"]["p99_ms"],
            "occupancy": sq["occupancy"],
            "coalesce_depth_p50": sq["coalesce_depth_p50"],
            "coalesce_depth_p99": sq["coalesce_depth_p99"],
            "queue_delay_p50_ms": sq["queue_delay_p50_ms"],
            "queue_delay_p99_ms": sq["queue_delay_p99_ms"],
            "batched_dispatches": sq["batched_dispatch_total"],
            "mismatches": (cmp["batched"]["mismatches"]
                           + cmp["unbatched"]["mismatches"]),
        }
        assert serving_cfg["mismatches"] == 0, \
            "serving bench rows diverged from the serial reference"
        # per-class off/on comparisons at the same client count: each
        # of the widened compatibility classes (aggregates, non-pk
        # top-K, batched vector top-K, EXECUTE binds) gets its own
        # speedup row, still bit-exact against the serial reference
        cls_ops = int(os.environ.get("BENCH_SERVING_CLASS_OPS", "24"))
        serving_cfg["classes"] = {}
        for cls in ("agg", "topk", "vector", "execute"):
            ccmp = servebench.compare(
                threads=int(os.environ.get("BENCH_SERVING_THREADS",
                                           "16")),
                ops_per_thread=cls_ops, classes=(cls,), emit=log)
            csq = ccmp["batched"]["serving_queue"]["classes"]
            serving_cfg["classes"][cls] = {
                "batched_qps": ccmp["batched"]["qps"],
                "unbatched_qps": ccmp["unbatched"]["qps"],
                "speedup": ccmp["speedup"],
                "p50_ms": ccmp["batched"]["latency"][cls]["p50_ms"],
                "p99_ms": ccmp["batched"]["latency"][cls]["p99_ms"],
                "coalesced": csq[cls]["coalesced_statements"],
                "batched_dispatches": csq[cls]
                    ["batched_dispatch_total"],
                "occupancy": csq[cls].get("occupancy", 0.0),
                "mismatches": (ccmp["batched"]["mismatches"]
                               + ccmp["unbatched"]["mismatches"]),
            }
            assert serving_cfg["classes"][cls]["mismatches"] == 0, \
                f"serving class {cls} diverged from serial reference"
        configs["serving"] = serving_cfg
        log(f"serving: {serving_cfg['aggregate_qps']:,} q/s batched vs "
            f"{serving_cfg['unbatched_qps']:,} unbatched "
            f"({serving_cfg['speedup']}x) at {serving_cfg['threads']} "
            f"clients; occupancy={serving_cfg['occupancy']}, depth p50="
            f"{serving_cfg['coalesce_depth_p50']}, queue delay p99="
            f"{serving_cfg['queue_delay_p99_ms']}ms; per-class speedup "
            + ", ".join(f"{c}={v['speedup']}x"
                        for c, v in serving_cfg["classes"].items()))

    # ---- vector search: exact vs clustered-ANN top-K ---------------------
    if budget_left():
        from cockroach_tpu.workload import vectorbench

        configs["vector"] = vectorbench.run(
            n=int(os.environ.get("BENCH_VECTOR_N", "100000")),
            d=int(os.environ.get("BENCH_VECTOR_D", "64")),
            n_queries=int(os.environ.get("BENCH_VECTOR_QUERIES", "64")),
            k=10, runs=max(1, runs // 2), log=log)

    # ---- multichip: per-chip scaling curve on the virtual CPU mesh ------
    # distributed Q3/Q9 rows/s + repartition bytes at 1/2/4/8 devices and
    # the ingest-shard vs replicate transfer-bytes differential (child
    # subprocess: the sharded DistSQL path needs a multi-device backend,
    # which a one-chip process does not have)
    if budget_left() and os.environ.get("BENCH_MULTICHIP", "1") == "1":
        mc = _multichip_bench()
        if mc is not None:
            configs["multichip"] = mc

    # ---- cold start: first-execution latency, cold vs xla-cache-warm
    # vs plan-vault-warm (fresh runners per regime; throwaway cache
    # dirs, the bench's own warm caches are untouched) -------------------
    if budget_left() and os.environ.get("BENCH_COLDSTART", "1") == "1":
        from cockroach_tpu.workload import coldstart

        configs["coldstart"] = coldstart.run(log=log)

    # ---- hash-join GB/s microbench (two sizes: the fixed round trip is
    # a large share of a 4M-row join's wall time; 8M shows the amortized
    # rate) ----------------------------------------------------------------
    if budget_left():
        configs["join_microbench"] = _join_microbench(runs)
    if budget_left() and "BENCH_JOIN_LOG2" not in os.environ:
        os.environ["BENCH_JOIN_LOG2"] = "23"
        try:
            configs["join_microbench_8m"] = _join_microbench(
                max(runs // 2, 1))
        finally:
            del os.environ["BENCH_JOIN_LOG2"]

    log("--- per-stage stats (host-side attribution) ---")
    log(st.report())

    # resilience accounting for the whole bench run: nonzero restarts/
    # degradations/retries here mean the numbers above were produced on
    # a degraded tier — the JSON must say so
    from cockroach_tpu.util import circuit as _circuit
    from cockroach_tpu.util.metric import default_registry as _metrics

    _reg = _metrics()
    resilience = {
        "flow_restarts": _reg.counter("sql_flow_restarts_total").value(),
        "retries": _reg.counter("sql_resilience_retries_total").value(),
        "degradations":
            _reg.counter("sql_resilience_degradations_total").value(),
        "breaker_trips":
            _reg.counter("sql_resilience_breaker_trips_total").value(),
        "breakers": {name: b.state()
                     for name, b in _circuit.all_breakers().items()},
    }

    # per-query placement decisions + fused coverage (sql/plan_compile.py)
    try:
        placement = _placement_block(gen, catalog, capacity)
        log(f"placement: {placement['fused_coverage']}/"
            f"{placement['total_queries']} queries whole-fused")
    except Exception as e:  # noqa: BLE001 — advisory block
        placement = {"error": str(e)}

    platform = jax.devices()[0].platform
    print(json.dumps({
        "metric": f"tpch_q1_sf{sf:g}_rows_per_sec_per_chip",
        "value": q1["rows_per_sec"],
        "unit": f"rows/s ({platform}; warm median of {runs}; "
                f"numpy-cpu baseline {round(n_line / q1['numpy_s'])} rows/s)",
        "vs_baseline": q1["vs_baseline"],
        "configs": configs,
        # per-stage host-side attribution, machine-readable (the stderr
        # tail above is the human rendering of the same collection)
        "stages": st.as_dict(),
        "resilience": resilience,
        "placement": placement,
        "sqlstats": _sqlstats_block(),
    }))


if __name__ == "__main__":
    if "--multichip-child" in sys.argv:
        _multichip_child()
    else:
        main()
