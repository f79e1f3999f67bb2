"""EXPERIMENT: fused group-join Q3 — validate the round-5 perf design.

Hypothesis (from the measured v5e cost model in ARCHITECTURE.md):
Q3's aggregation groups BY the join key (l_orderkey), so ONE narrow sort
of [orders ++ lineitem] keyed on (orderkey, build-first tag) performs the
join AND the grouping: build payload (odate|prio, <=25 bits) broadcasts
to its run via one cummax; revenue sums are segmented cumsum diffs at
run ends; run-ends compact via one (u32 key, i32 iota) sort; no row
gathers of probe-side data at all.  Key+tag fit u32 through SF100, and
rev fits u32, so the big sort is (u32, u32) — half the bytes of the
round-4 (u64, i32) + (i32, i32) pair, and there is exactly ONE big sort
instead of two plus a row-matrix gather.

Target: warm <= 0.217 s (numpy columnar baseline) at SF1.
"""
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from cockroach_tpu.workload.tpch import TPCH, _days
from cockroach_tpu.workload import tpch_queries as Q

SF = float(os.environ.get("SF", "1"))
gen = TPCH(sf=SF)
Q3_DATE = Q.Q3_DATE

c = gen.table("customer")
o = gen.table("orders")
l = gen.table("lineitem")
seg = gen.schema("customer").dicts["c_mktsegment"]
BUILDING = int(np.nonzero(seg == "BUILDING")[0][0])

# device inputs (resident, like the warm bench)
d = {
    "c_ckey": jnp.asarray(c["c_custkey"].astype(np.int32)),
    "c_seg": jnp.asarray(c["c_mktsegment"].astype(np.int32)),
    "o_okey": jnp.asarray(o["o_orderkey"].astype(np.int32)),
    "o_ckey": jnp.asarray(o["o_custkey"].astype(np.int32)),
    "o_date": jnp.asarray(o["o_orderdate"].astype(np.int32)),
    "o_prio": jnp.asarray(o["o_shippriority"].astype(np.int32)),
    "l_okey": jnp.asarray(l["l_orderkey"].astype(np.int32)),
    "l_px": jnp.asarray(l["l_extendedprice"].astype(np.int32)),
    "l_dc": jnp.asarray(l["l_discount"].astype(np.int32)),
    "l_ship": jnp.asarray(l["l_shipdate"].astype(np.int32)),
}

OUT_K = 10
CCAP = 1 << int(os.environ.get("LOG2_CCAP", "16"))  # run-end compaction cap


def q3_groupjoin(d):
    # ---- orders semi customer(BUILDING) + date filter (u32 sort) --------
    # build = BUILDING customers keyed c_custkey, probe = orders keyed
    # o_custkey; carry orders lane index as payload to recover matches.
    ckey = d["c_ckey"]
    olive = d["o_date"] < Q3_DATE
    nb, no = ckey.shape[0], d["o_okey"].shape[0]
    cl = d["c_seg"] == BUILDING
    # key<<1|tag fits u32: custkey <= 150K*SF (SF100: 15M -> 24b+1)
    TOPC = np.uint32(1 << 31)
    pk_c = jnp.where(cl, (ckey.astype(jnp.uint32) << np.uint32(1)),
                     TOPC | jnp.arange(nb, dtype=jnp.uint32) * 2 + 1)
    pk_o = jnp.where(
        olive, (d["o_ckey"].astype(jnp.uint32) << np.uint32(1)) | 1,
        TOPC | (jnp.arange(no, dtype=jnp.uint32) * 2 + 1))
    pk = jnp.concatenate([pk_c, pk_o])
    # payload = destination lane: customers (live or dead) land PAST the
    # orders span so the resort's first `no` slots are exactly the orders
    pay = jnp.concatenate([
        jnp.int32(no) + jnp.arange(nb, dtype=jnp.int32),
        jnp.arange(no, dtype=jnp.int32)])
    spk, spay = jax.lax.sort((pk, pay), num_keys=1)
    prev = jnp.concatenate([spk[:1] | np.uint32(1), spk[:-1]])
    newrun = (spk >> np.uint32(1)) != (prev >> np.uint32(1))
    newrun = newrun.at[0].set(True)
    is_b = ((spk & np.uint32(1)) == 0) & (spk < TOPC)
    runid = jnp.cumsum(newrun.astype(jnp.int32))
    has_b = jax.lax.cummax(jnp.where(is_b, runid, 0)) == runid
    o_sorted_flag = (has_b & ~is_b & (spk < TOPC)).astype(jnp.int32)
    _, oflag = jax.lax.sort((spay, o_sorted_flag), num_keys=1)
    omatch = oflag[:no].astype(jnp.bool_)  # in orders lane order

    # ---- the group-join sort: [orders ++ lineitem] on orderkey ----------
    llive = d["l_ship"] > Q3_DATE
    nl = d["l_okey"].shape[0]
    TOP = np.uint32(1 << 31)
    # key<<1|tag: orderkey SF1 6M=23b (SF10 26b, SF100 29b) + tag -> u32
    gk_o = jnp.where(omatch, d["o_okey"].astype(jnp.uint32) << np.uint32(1),
                     TOP | np.uint32(1))
    gk_l = jnp.where(
        llive, (d["l_okey"].astype(jnp.uint32) << np.uint32(1)) | 1,
        TOP | np.uint32(1))
    rev = (d["l_px"].astype(jnp.int64)
           * (100 - d["l_dc"].astype(jnp.int64)))  # <=1e9: fits u32
    # payload u32: build lanes carry (date 24b | prio 4b ... date ~9.2K-
    # 13.2K fits 14b; give date 27b | prio 4b) ; probe lanes carry rev
    pay_o = (d["o_date"].astype(jnp.uint32) << np.uint32(4)) | jnp.clip(
        d["o_prio"], 0, 15).astype(jnp.uint32)
    pay_l = rev.astype(jnp.uint32)
    gk = jnp.concatenate([gk_o, gk_l])
    gv = jnp.concatenate([pay_o, pay_l])
    sgk, sgv = jax.lax.sort((gk, gv), num_keys=1)

    prev = jnp.concatenate([sgk[:1] | np.uint32(1), sgk[:-1]])
    newrun = (sgk >> np.uint32(1)) != (prev >> np.uint32(1))
    newrun = newrun.at[0].set(True)
    is_b = ((sgk & np.uint32(1)) == 0) & (sgk < TOP)
    runid = jnp.cumsum(newrun.astype(jnp.int32))  # <= n, 23b at SF1
    # broadcast build payload to the run: (runid<<32 | pay+1) cummax
    enc = (runid.astype(jnp.int64) << np.int64(32)) | jnp.where(
        is_b, sgv.astype(jnp.int64) + 1, 0)
    m = jax.lax.cummax(enc)
    bpay = (m & np.int64(0xFFFFFFFF)).astype(jnp.int64)  # pay+1 or 0
    matched = (bpay > 0) & ~is_b & (sgk < TOP)
    revm = jnp.where(matched, sgv.astype(jnp.int64), 0)
    s = jnp.cumsum(revm)
    cnt = jnp.cumsum(matched.astype(jnp.int32))
    # run END lanes: next lane starts a new run (shift newrun left)
    nxt = jnp.concatenate([newrun[1:], jnp.ones((1,), jnp.bool_)])
    # a run with >=1 matched probe necessarily ENDS on a matched probe
    # lane (build sorts first in its run), so `matched` at the end lane
    # selects exactly the non-empty groups
    is_end = nxt & matched

    # ---- compact run-ends: ONE (u32, i32) sort, then tiny gathers -------
    n = sgk.shape[0]
    lane = jnp.arange(n, dtype=jnp.uint32)
    ckey_sort = jnp.where(is_end, lane, np.uint32(0xFFFFFFFF))
    _, cidx = jax.lax.sort((ckey_sort, lane.astype(jnp.int32)), num_keys=1)
    top = cidx[:CCAP]
    e_key = (sgk[top] >> np.uint32(1)).astype(jnp.int32)
    e_pay = bpay[top] - 1
    e_s = s[top]
    e_cnt = cnt[top]
    e_valid = (jnp.arange(CCAP) < jnp.sum(is_end))
    # per-run totals: diff of cumsums at consecutive compacted ends
    # (between two matched runs every contribution is 0)
    p_s = jnp.concatenate([jnp.zeros((1,), jnp.int64), e_s[:-1]])
    p_cnt = jnp.concatenate([jnp.zeros((1,), jnp.int32), e_cnt[:-1]])
    tot = e_s - p_s
    npr = e_cnt - p_cnt
    e_valid = e_valid & (npr > 0)
    overflow = jnp.sum(is_end) > CCAP

    # ---- top-10 by (revenue desc, date asc) over 64K lanes --------------
    date = (e_pay >> np.int64(4)).astype(jnp.int32)
    prio = (e_pay & np.int64(15)).astype(jnp.int32)
    # tot <= ~2^34 at SF1-100 (per-order revenue): (2^36 - tot)<<14 | date
    # stays inside i64 and sorts (revenue desc, date asc)
    skey = jnp.where(
        e_valid, (((jnp.int64(1) << 36) - tot) << np.int64(14))
        | date.astype(jnp.int64), jnp.int64(1) << 51)
    _, oidx = jax.lax.sort((skey, jnp.arange(CCAP, dtype=jnp.int32)),
                           num_keys=1)
    w = oidx[:OUT_K]
    # ONE packed output buffer -> ONE device->host readback (each
    # separate np.asarray is another round trip)
    return jnp.concatenate([
        e_key[w].astype(jnp.int64), tot[w],
        date[w].astype(jnp.int64), prio[w].astype(jnp.int64),
        e_valid[w].astype(jnp.int64), overflow[None].astype(jnp.int64)])


def _stage_progs():
    """Incremental prefixes of the pipeline; warm-time deltas attribute
    device cost per stage (each dispatch adds the same ~107ms floor)."""
    def semi(d):
        ckey = d["c_ckey"]
        olive = d["o_date"] < Q3_DATE
        nb, no = ckey.shape[0], d["o_okey"].shape[0]
        cl = d["c_seg"] == BUILDING
        TOPC = np.uint32(1 << 31)
        pk_c = jnp.where(cl, (ckey.astype(jnp.uint32) << np.uint32(1)),
                         TOPC | jnp.arange(nb, dtype=jnp.uint32) * 2 + 1)
        pk_o = jnp.where(
            olive, (d["o_ckey"].astype(jnp.uint32) << np.uint32(1)) | 1,
            TOPC | (jnp.arange(no, dtype=jnp.uint32) * 2 + 1))
        pk = jnp.concatenate([pk_c, pk_o])
        pay = jnp.concatenate([
            jnp.int32(no) + jnp.arange(nb, dtype=jnp.int32),
            jnp.arange(no, dtype=jnp.int32)])
        spk, spay = jax.lax.sort((pk, pay), num_keys=1)
        prev = jnp.concatenate([spk[:1] | np.uint32(1), spk[:-1]])
        newrun = (spk >> np.uint32(1)) != (prev >> np.uint32(1))
        newrun = newrun.at[0].set(True)
        is_b = ((spk & np.uint32(1)) == 0) & (spk < TOPC)
        runid = jnp.cumsum(newrun.astype(jnp.int32))
        has_b = jax.lax.cummax(jnp.where(is_b, runid, 0)) == runid
        flag = (has_b & ~is_b & (spk < TOPC)).astype(jnp.int32)
        return spay, flag

    def s1_sort1(d):
        spay, flag = semi(d)
        return jnp.sum(spay) + jnp.sum(flag)

    def s2_semi(d):
        spay, flag = semi(d)
        _, oflag = jax.lax.sort((spay, flag), num_keys=1)
        return jnp.sum(oflag)

    def gsort(d, omatch):
        llive = d["l_ship"] > Q3_DATE
        TOP = np.uint32(1 << 31)
        gk_o = jnp.where(omatch,
                         d["o_okey"].astype(jnp.uint32) << np.uint32(1),
                         TOP | np.uint32(1))
        gk_l = jnp.where(
            llive, (d["l_okey"].astype(jnp.uint32) << np.uint32(1)) | 1,
            TOP | np.uint32(1))
        rev = (d["l_px"].astype(jnp.int64)
               * (100 - d["l_dc"].astype(jnp.int64)))
        pay_o = (d["o_date"].astype(jnp.uint32) << np.uint32(4)) | jnp.clip(
            d["o_prio"], 0, 15).astype(jnp.uint32)
        pay_l = rev.astype(jnp.uint32)
        gk = jnp.concatenate([gk_o, gk_l])
        gv = jnp.concatenate([pay_o, pay_l])
        return jax.lax.sort((gk, gv), num_keys=1)

    def s3_gsort(d, omatch):
        sgk, sgv = gsort(d, omatch)
        return jnp.sum(sgv.astype(jnp.int64)) + jnp.sum(sgk.astype(jnp.int64))

    def s4_cums(d, omatch):
        sgk, sgv = gsort(d, omatch)
        TOP = np.uint32(1 << 31)
        prev = jnp.concatenate([sgk[:1] | np.uint32(1), sgk[:-1]])
        newrun = (sgk >> np.uint32(1)) != (prev >> np.uint32(1))
        newrun = newrun.at[0].set(True)
        is_b = ((sgk & np.uint32(1)) == 0) & (sgk < TOP)
        runid = jnp.cumsum(newrun.astype(jnp.int32))
        enc = (runid.astype(jnp.int64) << np.int64(32)) | jnp.where(
            is_b, sgv.astype(jnp.int64) + 1, 0)
        m = jax.lax.cummax(enc)
        bpay = (m & np.int64(0xFFFFFFFF)).astype(jnp.int64)
        matched = (bpay > 0) & ~is_b & (sgk < TOP)
        revm = jnp.where(matched, sgv.astype(jnp.int64), 0)
        s = jnp.cumsum(revm)
        cnt = jnp.cumsum(matched.astype(jnp.int32))
        return jnp.sum(s[-1:]) + jnp.sum(cnt[-1:]) + jnp.sum(bpay[-1:])

    def s5_comp(d, omatch):
        sgk, sgv = gsort(d, omatch)
        n = sgk.shape[0]
        lane = jnp.arange(n, dtype=jnp.uint32)
        mask = (sgv & np.uint32(1)) == 0  # pseudo end-mask, same density
        ckey_sort = jnp.where(mask, lane, np.uint32(0xFFFFFFFF))
        _, cidx = jax.lax.sort((ckey_sort, lane.astype(jnp.int32)),
                               num_keys=1)
        return jnp.sum(cidx[:CCAP])

    return {"s1_sort1+semi_cums": s1_sort1, "s2_semi_resort": s2_semi,
            "s3_gsort": s3_gsort, "s4_cums": s4_cums, "s5_compsort": s5_comp}


if os.environ.get("STAGES"):
    omatch_host = jnp.asarray(
        np.zeros(d["o_okey"].shape[0], np.bool_))
    for name, fn in _stage_progs().items():
        p = jax.jit(fn)
        args = (d,) if name.startswith(("s1", "s2")) else (d, omatch_host)
        t0 = time.perf_counter()
        np.asarray(p(*args))
        cold = time.perf_counter() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(p(*args))
            ts.append(time.perf_counter() - t0)
        print(f"{name}: cold={cold:.1f}s warm={statistics.median(ts):.4f}s",
              flush=True)

prog = jax.jit(q3_groupjoin)
t0 = time.perf_counter()
out = jax.block_until_ready(prog(d))
print(f"cold {time.perf_counter() - t0:.1f}s", flush=True)
res = np.asarray(out)  # enter sync (post-readback) mode

times = []
for i in range(5):
    t0 = time.perf_counter()
    res = np.asarray(prog(d))
    times.append(time.perf_counter() - t0)
print("warm", [round(t, 4) for t in times],
      "median", round(statistics.median(times), 4), flush=True)

if os.environ.get("PROFILE"):
    import glob
    import gzip
    import json
    import shutil

    tdir = "/tmp/gjtrace"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(tdir):
        res = np.asarray(prog(d))
    agg = {}
    for p in glob.glob(tdir + "/**/*.trace.json.gz", recursive=True):
        with gzip.open(p, "rt") as f:
            data = json.load(f)
        for ev in data.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            name = ev.get("name", "")
            agg.setdefault(name, [0, 0])
            agg[name][0] += ev.get("dur", 0)
            agg[name][1] += 1
    for name, (dur, cntv) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:30]:
        print(f"{dur / 1e3:9.1f} ms  x{cntv:<4d} {name[:100]}", flush=True)

# numpy baseline on this host
Q.q3_oracle_columnar(gen)
t0 = time.perf_counter()
oracle = Q.q3_oracle_columnar(gen)
tnp = time.perf_counter() - t0
print(f"numpy {tnp:.4f}s -> {tnp / statistics.median(times):.2f}x", flush=True)

K = OUT_K
e_key, tot, date, prio, valid, ovf = (
    res[:K], res[K:2 * K], res[2 * K:3 * K], res[3 * K:4 * K],
    res[4 * K:5 * K], res[5 * K])
got = [(int(e_key[i]), int(tot[i]), int(date[i]), int(prio[i]))
       for i in range(OUT_K) if valid[i]]
assert not bool(ovf), "run-end compaction overflow"
assert got == oracle, f"MISMATCH\n got={got}\n want={oracle}"
print("oracle: EXACT MATCH", flush=True)
